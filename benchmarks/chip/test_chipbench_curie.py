"""The CEA Curie thin-node cell: its parts, its reference, its reader.

``curie_ff_kernel.backlog`` runs First-Fit on the Pallas kernel at
80,640 PEs.  Its reference is the plain host reference unchanged (only
``n_pe`` differs from the LANL-CM5 cells), so on the Curie stream, cut
to 500 jobs, it has to decide as the program's host engine does.
"""
from pathlib import Path

import pytest

import harness
import lanl_stream
import plain_ref

CHIP_DIR = Path(harness.__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
CELL = "curie_ff_kernel.backlog"
PER_LAYER = ["scan_step_us.backlog", "device_idle_share.backlog",
             "availscan_us", "availscan_roofline",
             "availscan_step_share.backlog"]


def test_finds_the_cell_by_name():
    cell = harness.find_cell(CELL, ROOT)
    assert cell.chips == 1
    assert cell.config["name"] == "curie_thin_ff_kernel"
    assert cell.config["service"]["n_pe"] == 80640
    assert cell.config["workload"]["n_pe"] == 80640
    assert cell.config["search_path"] == "kernel"
    assert lanl_stream.params(cell.config["workload"])["u_low"] == 4.5
    assert cell.mix["loop"] == "closed"
    assert cell.end_to_end == ["admits_per_s", "setup_s"]
    assert cell.per_layer == PER_LAYER
    for name in PER_LAYER:
        assert callable(harness.load_reader(CHIP_DIR, name))


def test_reference_decides_as_the_host_engine():
    from repro.core.types import Policy
    from repro.sim import simulate
    cfg = harness.find_cell(CELL, ROOT).config
    stream = lanl_stream.generate({**cfg["workload"], "n_jobs": 500},
                                  3142000001)
    assert stream["n_pe"].max() > 16384     # past one kernel block
    acc, t_s, _ = plain_ref.decide(stream, 80640, "FF")
    sim = simulate(harness.to_requests(stream), 80640, Policy.FF,
                   engine="host", record_decisions=True)
    assert [(bool(a), int(t) if a else -1) for a, t in zip(acc, t_s)] \
        == [(a, t) for a, t in sim.decisions]
    assert 0 < acc.sum() < len(acc)


def _reading(ops, modules):
    return harness.Reading(
        ops=ops, modules=modules, spans=[], t0=0, t1=1000, busy_ns=0,
        counters={}, shapes=dict(capacity=128, n_pe=80640, chunk_size=64),
        peaks={})


def test_step_share_reads_kernel_over_scan():
    read = harness.load_reader(CHIP_DIR, "availscan_step_share.backlog")
    modules = [("jit_admit_stream_donated(1)", 0, 300),
               ("jit_admit_stream(2)", 400, 500),
               ("jit_restore(3)", 500, 900)]
    ops = [("availscan_select.3", 10, 70), ("availscan_select.3", 410, 430),
           ("fusion.12", 70, 200)]
    assert read(_reading(ops, modules)) == pytest.approx(100 * 80 / 400)
    # the jnp path calls no kernel: nothing to read
    assert read(_reading(ops[2:], modules)) is None
    assert read(_reading(ops, [])) is None
