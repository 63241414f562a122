"""The check that decides ``correct`` fails its control and each fault.

The control is the plain reference with its one tempting shortcut (PEs
tested free at each window's start only), which breaks exclusive
reservations.  The faults are planted in the program under a whole
run of the harness, at a tiny size on the CPU: a scan step that keeps
its state unchanged, half of each offer left undecided, and an answer
altered where it is produced.  Every one has to read ``correct:
false``.  (One chip: no exchange between chips to leave out.)
"""
import copy
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import lanl_stream
import open_cell
import plain_ref

CHIP_DIR = Path(harness.__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
TINY = dict(n_jobs=300, n_pe=64, u_low=2.0, u_med=4.0, u_hi=6.0)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the open cell's entries added."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(CHIP_DIR, tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "BENCHMARK.json").write_text(json.dumps(open_cell.add(bench)))
    return tmp


def tiny_cell(name: str, root: Path) -> harness.Cell:
    cell = copy.deepcopy(harness.find_cell(name, root))
    cell.config["workload"].update(TINY)
    cell.config["service"]["n_pe"] = 64
    if cell.mix["loop"] == "open":
        cell.mix.update(rate_per_s=600, warm_chunks=2)
    return cell


@pytest.mark.parametrize("policy", plain_ref.POLICIES)
@pytest.mark.parametrize("seed", [3, 2**31 + 7, 40_000])
def test_control_is_not_correct(policy, seed):
    stream = lanl_stream.generate(TINY, seed)
    ref = plain_ref.decide(stream, 64, policy)
    acc, ts, mask = plain_ref.decide(stream, 64, policy, start_only=True)
    rec = harness.Recorder()
    rec.new_pass()
    rec.add(dict(acc=acc, t_s=ts, mask=mask, steps=len(acc)))
    cmp = harness.compare(rec, ref)
    checks = harness.checks_of(cmp, len(acc), True)
    assert checks["mismatched"]["value"] > checks["mismatched"]["limit"]


def test_sound_decisions_pass_the_same_check():
    stream = lanl_stream.generate(TINY, 3)
    ref = plain_ref.decide(stream, 64, "PE_W")
    rec = harness.Recorder()
    for _ in range(2):
        rec.new_pass()
        rec.add(dict(acc=ref[0], t_s=ref[1], mask=ref[2], steps=300))
    checks = harness.checks_of(harness.compare(rec, ref), 600, True)
    assert all(c["value"] <= c["limit"] for c in checks.values())


@pytest.fixture
def fresh_programs():
    """Planted faults change traced code: compile afresh around them."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(name: str, root: Path):
    return harness.run(tiny_cell(name, root), 5, 0.5, False,
                       time.perf_counter(), jax.devices())


def _plant_in_step(monkeypatch, fault):
    from repro.core import batch
    orig = batch._admit_impl

    def broken(state, req, *a, **k):
        new, dec = orig(state, req, *a, **k)
        return fault(state, new, dec)

    monkeypatch.setattr(batch, "_admit_impl", broken)


CELLS = ("lanl_pe_w.backlog", "lanl_ff_kernel.backlog", "lanl_pe_w.open")


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged_is_caught(name, root, monkeypatch,
                                       fresh_programs):
    _plant_in_step(monkeypatch, lambda old, new, dec: (old, dec))
    res = _run(name, root)
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_is_caught(name, root, monkeypatch, fresh_programs):
    def shift(old, new, dec):
        return new, dec._replace(
            t_s=jnp.where(dec.accepted, dec.t_s + 1, dec.t_s))

    _plant_in_step(monkeypatch, shift)
    res = _run(name, root)
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_half_left_out_is_caught(name, root, monkeypatch):
    from repro.api.service import Session
    offer = Session.offer

    def half(self, requests, **kw):
        reqs = list(requests)
        return offer(self, reqs[:max(len(reqs) // 2, 1)], **kw)

    monkeypatch.setattr(Session, "offer", half)
    res = _run(name, root)
    assert res["correct"] is False
    assert (res["checks"]["missing"]["value"]
            + res["checks"]["mismatched"]["value"]) > 0


def test_wrong_search_path_is_caught(root):
    cell = tiny_cell("lanl_ff_kernel.backlog", root)
    cell.config["service"]["use_kernel"] = False
    res = harness.run(cell, 5, 0.3, False, time.perf_counter(),
                      jax.devices())
    assert res["correct"] is False
    assert res["checks"]["off_path"]["value"] == 1
    assert np.isfinite(res["metrics"]["admits_per_s"]["value"])
