"""Dry-run machinery: artifact consistency + one real subprocess cell.

The full 512-device sweep runs via ``python -m repro.launch.dryrun``
(artifacts are committed under artifacts/dryrun); here we verify the
recorded artifacts are complete and self-consistent, and (slow) that
one cell lowers+compiles end-to-end in a fresh process with the forced
512-device platform.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import ALL_SHAPES, ARCH_IDS, applicable, get_config

ART = Path(__file__).resolve().parent.parent / "artifacts" / "dryrun"

pytestmark = pytest.mark.skipif(
    not ART.exists(), reason="run repro.launch.dryrun first")


def _load_all():
    return [json.loads(p.read_text()) for p in ART.glob("*.json")]


def test_every_cell_present_and_green():
    recs = _load_all()
    assert len(recs) == len(ARCH_IDS) * len(ALL_SHAPES) * 2  # 2 meshes
    by_status = {}
    for r in recs:
        by_status.setdefault(r["status"], []).append(r)
    assert "FAILED" not in by_status, by_status.get("FAILED")
    # exactly the documented long_500k skips
    skips = by_status.get("SKIPPED", [])
    assert all(r["shape"] == "long_500k" for r in skips)
    assert len(skips) == 16     # 8 full-attention archs x 2 meshes


def test_skips_match_applicability_rules():
    for r in _load_all():
        cfg = get_config(r["arch"])
        shape = next(s for s in ALL_SHAPES if s.name == r["shape"])
        ok, _ = applicable(cfg, shape)
        assert (r["status"] == "SKIPPED") == (not ok)


def test_roofline_terms_recorded_and_positive():
    for r in _load_all():
        if r["status"] != "OK":
            continue
        t = r["roofline"]
        assert t["compute_s"] > 0 and t["memory_s"] > 0
        assert t["dominant"] in ("compute", "memory", "collective")
        assert r["analytic"]["model_flops"] > 0
        assert r["hlo_raw"]["collectives"]["total"] > 0  # sharded!


def test_decode_cells_are_memory_bound():
    """Decode physics: every decode cell must be memory-dominated."""
    for r in _load_all():
        if r["status"] == "OK" and r["kind"] == "decode":
            assert r["roofline"]["dominant"] == "memory", \
                (r["arch"], r["shape"])


def test_serve_memory_fits_everywhere():
    for r in _load_all():
        if r["status"] == "OK" and r["kind"] != "train":
            assert r["memory"]["model_fits_16g_hbm"], \
                (r["arch"], r["shape"], r["mesh"])


@pytest.mark.slow
def test_one_cell_compiles_in_subprocess(tmp_path):
    # the child compiles on the CPU backend: it must never try to take
    # an accelerator the test process (or another worker) may hold
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "stablelm-1.6b", "--shape", "decode_32k",
         "--mesh", "single", "--out", str(tmp_path), "--force"],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parent.parent)
    assert "1 ok, 0 skipped, 0 failed" in out.stdout, out.stdout[-2000:]
