"""CPU rehearsal of the chip benchmark's harness, at a tiny size.

Covers finding a cell's parts by name, adding a cell as data, the pass
reset by ``Session.restore`` against the plain reference, and
``run_cell.py`` refusing to run without a TPU.
"""
import ast
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import harness
import open_cell

CHIP_DIR = Path(harness.__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
CELLS = ("lanl_pe_w.backlog", "lanl_ff_kernel.backlog", "lanl_pe_w.open")


def with_open_cell(tmp: Path) -> Path:
    """A copy of the benchmark whose ``BENCHMARK.json`` also holds the
    open cell's entries (``open_cell.py``)."""
    shutil.copytree(CHIP_DIR, tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "BENCHMARK.json").write_text(json.dumps(open_cell.add(bench)))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return with_open_cell(tmp_path_factory.mktemp("bench"))


def tiny(cell: harness.Cell) -> harness.Cell:
    """The cell at 64 PEs and 300 jobs (sizes scaled to the machine)."""
    cell = copy.deepcopy(cell)
    cell.config["workload"].update(n_jobs=300, n_pe=64, u_low=2.0,
                                   u_med=4.0, u_hi=6.0)
    cell.config["service"]["n_pe"] = 64
    if cell.mix["loop"] == "open":
        cell.mix.update(rate_per_s=600, warm_chunks=2)
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_finds_parts_by_name(name, root):
    cell = harness.find_cell(name, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == name)
    assert cell.config["name"] == wl["config"]
    assert cell.mix == json.loads(
        (CHIP_DIR / "mixes" / f"{wl['traffic']}.json").read_text())
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    for metric in cell.per_layer:
        assert callable(harness.load_reader(CHIP_DIR, metric))


def test_unknown_cell_is_refused():
    with pytest.raises(harness.SetupError):
        harness.find_cell("no_such.cell", ROOT)


def test_open_cell_waits_outside_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert open_cell.WORKLOAD["name"] not in {
        w["name"] for w in bench["workloads"]}
    for m in open_cell.PER_LAYER:
        assert callable(harness.load_reader(CHIP_DIR, m["name"]))


def _digest(tree: Path) -> dict:
    return {p.relative_to(tree).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in tree.rglob("*") if p.is_file()}


def test_new_mix_file_is_a_new_cell(tmp_path):
    chip = with_open_cell(tmp_path) / "benchmarks" / "chip"
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    before = _digest(tmp_path)
    # a later change adds files and entries, and edits no file's content
    mix = dict(loop="open", rate_per_s=123, warm_chunks=1,
               trace_seconds=1.0, arrivals="poisson")
    (chip / "mixes" / "slow.json").write_text(json.dumps(mix))
    bench["workloads"].append(dict(
        name="lanl_pe_w.slow", config="lanl_cm5_pe_w", traffic="slow",
        chips=1, why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lanl_pe_w.open" in m.get("workloads", []):
            m["workloads"].append("lanl_pe_w.slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digest(tmp_path)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}      # entries added, files new
    assert set(after) - set(before) == {"benchmarks/chip/mixes/slow.json"}
    cell = harness.find_cell("lanl_pe_w.slow", tmp_path, chip)
    assert cell.mix == mix
    assert cell.config["name"] == "lanl_cm5_pe_w"
    assert "decision_p95_ms" in cell.end_to_end
    assert "pad_step_share.open" in cell.per_layer


@pytest.mark.parametrize("name", CELLS)
def test_restored_passes_match_the_reference(name, root):
    cell = tiny(harness.find_cell(name, root))
    res = harness.run(cell, 2**31 + 12, 1.0, False, time.perf_counter(),
                      jax.devices())
    assert res["checks"]["mismatched"]["value"] == 0
    assert res["checks"]["missing"]["value"] == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["info"]["passes"] >= 2          # restore ran in the window
    assert res["info"]["compiles_in_window"] == 0
    assert set(res["metrics"]) == set(cell.end_to_end)
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("module", ["plain_ref.py", "lanl_stream.py"])
def test_reference_imports_nothing_of_the_program(module):
    tree = ast.parse((CHIP_DIR / module).read_text())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module.split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and n.module}
    assert imported <= {"__future__", "heapq", "typing", "numpy"}


def _run_cell(cwd: Path, tmp_path: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HOME=str(tmp_path), TMPDIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_cell_refuses_without_tpu(tmp_path):
    out = _run_cell(ROOT, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_cell_refuses_without_program(tmp_path):
    bare = tmp_path / "bare"
    shutil.copytree(CHIP_DIR, bare / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", bare / "BENCHMARK.json")
    out = _run_cell(bare, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
