"""The trace reduction, checked on a small trace recorded on the chip.

``fixtures/trace_small.json`` holds 3 ms of a traced pass of
``lanl_ff_kernel.backlog`` on a TPU v5e, from just before its first
device op: the device's ``XLA Ops`` (by instruction name) and ``XLA
Modules`` events and the benchmark's host spans, in ns from the
slice's start.  Each reduction is compared with a brute-force count
over a 1-ns grid.
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness
import trace_ops

FIXTURE = Path(trace_ops.__file__).resolve().parent / "fixtures" / \
    "trace_small.json"


@pytest.fixture(scope="module")
def fx():
    d = json.loads(FIXTURE.read_text())
    for k in ("ops", "modules", "spans"):
        d[k] = [tuple(e) for e in d[k]]
    return d


def _grid(events, t1):
    busy = np.zeros(t1, bool)
    for _, s, e in events:
        busy[max(s, 0):min(e, t1)] = True
    return busy


def _reading(fx, **counters):
    t0, t1 = fx["window"]
    return harness.Reading(
        ops=fx["ops"], modules=fx["modules"], spans=fx["spans"], t0=t0,
        t1=t1, busy_ns=trace_ops.busy_ns(fx["modules"], t0, t1),
        counters=counters, shapes=dict(capacity=128, n_pe=1024),
        peaks=dict(hbm_bytes_per_s=819e9))


def test_fixture_has_what_the_readers_read(fx):
    names = {n for n, _, _ in fx["ops"]}
    assert any(n.startswith("availscan_select") for n in names)
    assert any("admit_stream" in n for n, _, _ in fx["modules"])
    assert {n for n, _, _ in fx["spans"]} >= {"bench.offer"}


def test_busy_union_and_idle_share(fx):
    t0, t1 = fx["window"]
    grid = _grid(fx["modules"], t1)
    assert trace_ops.busy_ns(fx["modules"], t0, t1) == int(grid.sum())
    idle = trace_ops.gaps(fx["modules"], t0, t1)
    assert sum(b - a for a, b in idle) == t1 - int(grid.sum())
    assert all(not grid[a:b].any() for a, b in idle)
    read = harness.load_reader(FIXTURE.parents[1],
                               "device_idle_share.backlog")
    assert read(_reading(fx)) == pytest.approx(
        100.0 * (1 - grid.sum() / t1))


def test_kernel_time(fx):
    hits = [e - s for n, s, e in fx["ops"]
            if n.startswith("availscan_select")]
    assert trace_ops.named_time(fx["ops"], "availscan_select") == (
        sum(hits), len(hits))
    chip = FIXTURE.parents[1]
    us = harness.load_reader(chip, "availscan_us")(_reading(fx))
    assert us == pytest.approx(sum(hits) / len(hits) / 1e3)
    share = harness.load_reader(chip, "availscan_roofline")(_reading(fx))
    least = 128 * (1024 // 8 + 8) + (2 * 128 + 2) * 4
    assert share == pytest.approx(
        100 * least / 819e9 / (sum(hits) / len(hits) / 1e9))
    assert 0 < share <= 100


def test_gap_attribution(fx):
    t0, t1 = fx["window"]
    idle = ~_grid(fx["modules"], t1)
    owner = np.full(t1, -1)
    spans = sorted(fx["spans"], key=lambda ev: ev[1])
    for i, (_, s, e) in enumerate(spans):   # later start = inner span
        owner[max(s, 0):min(e, t1)] = i
    want = {}
    for i, n in zip(*np.unique(owner[idle], return_counts=True)):
        name = "no span" if i < 0 else spans[i][0][len("bench."):]
        want[name] = want.get(name, 0) + int(n)
    got = trace_ops.attribute(trace_ops.gaps(fx["modules"], t0, t1),
                              fx["spans"])
    assert got == want


def test_self_times_tile_the_busy_time(fx):
    t0, t1 = fx["window"]
    own = trace_ops.self_times(fx["ops"])
    assert all(ns >= 0 for _, ns in own)
    assert sum(ns for _, ns in own) == trace_ops.busy_ns(fx["ops"], t0, t1)


def test_top_and_clip(fx):
    t0, t1 = fx["window"]
    ranked = trace_ops.top(fx["ops"], 10)
    assert len(ranked) <= 10
    secs = [s for _, s in ranked]
    assert secs == sorted(secs, reverse=True)
    half = (t0 + t1) // 2
    cut = trace_ops.clip(fx["ops"], t0, half)
    assert cut and all(t0 <= s <= e <= half for _, s, e in cut)


def test_load_reads_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with harness.span("offer"):
        f(jnp.ones(8)).block_until_ready()
        time.sleep(0.001)
    jax.profiler.stop_trace()
    red = trace_ops.load(str(tmp_path))
    assert [n for n, _, _ in red["spans"]] == ["bench.offer"]
    (_, s, e), = red["spans"]
    assert e - s >= 1_000_000
    assert red["by_device"] == [] and red["modules"] == []
