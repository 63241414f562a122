"""The scope and program-span reductions, on a trace recorded on the chip.

``fixtures/trace_scoped.json`` holds 3 ms of a traced window of
``lanl_ff_kernel.backlog`` on a TPU v5e, where the device runs out of
queued scan chunks while the host waits in the drain's latch read
(``split_cell.py --fixture``): the
device's ops (by instruction name, each with an index into ``paths``,
its ``op_name`` scope path), modules, the benchmark's ``bench.*``
spans and the program's ``repro.*`` spans, in ns from the slice's
start.  Each reduction of ``trace_scopes`` is compared with a
brute-force count over a 1-ns grid; the self-time split within a few
ns, since rounding ps to ns lets a nested op overhang its parent by
one.  The existing readers are pinned
to the values they read on ``fixtures/trace_small.json``.
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness
import trace_ops
import trace_scopes

CHIP = Path(trace_ops.__file__).resolve().parent
SCOPED = CHIP / "fixtures" / "trace_scoped.json"
SMALL = CHIP / "fixtures" / "trace_small.json"


@pytest.fixture(scope="module")
def fx():
    d = json.loads(SCOPED.read_text())
    d["paths_of_ops"] = [d["paths"][i] for *_, i in d["ops"]]
    d["ops"] = [(n, s, e) for n, s, e, _ in d["ops"]]
    for k in ("modules", "spans", "program_spans"):
        d[k] = [tuple(e) for e in d[k]]
    return d


def _paint(events, t1, order=None):
    """Owner (event index) of every ns: a later start overwrites, and of
    two with one start the shorter (inner) one wins."""
    owner = np.full(t1, -1)
    idx = order if order is not None else sorted(
        range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    for i in idx:
        _, s, e = events[i]
        owner[max(s, 0):min(e, t1)] = i
    return owner


def test_fixture_has_what_the_reductions_read(fx):
    paths = fx["paths_of_ops"]
    assert any(trace_scopes.phase(p) == "admit.search" for p in paths)
    assert any(trace_scopes.phase(p) == "admit.commit" for p in paths)
    assert any(trace_scopes.in_scan(p) and not trace_scopes.in_step(p)
               for p in paths)
    names = {n for n, _, _ in fx["program_spans"]}
    assert {"repro.drain", "repro.drain.sync"} <= names
    assert any("admit_stream" in n for n, _, _ in fx["modules"])


def test_scan_split_matches_a_grid(fx):
    t0, t1 = fx["window"]
    ops, paths = fx["ops"], fx["paths_of_ops"]
    scan = [i for i, p in enumerate(paths) if trace_scopes.in_scan(p)]
    scan_ops = [ops[i] for i in scan]
    owner = _paint(scan_ops, t1)
    own = np.bincount(owner[owner >= 0], minlength=len(scan_ops))
    want = {}
    for k, i in enumerate(scan):
        name, path = ops[i][0], paths[i]
        if trace_scopes.in_step(path):
            key = trace_scopes.scope_key(path)
        elif name.startswith("while"):
            key = "loop control"
        else:
            key = "outside admit"
        want[key] = want.get(key, 0) + int(own[k])
    mods = np.zeros(t1, bool)
    for n, s, e in fx["modules"]:
        if "admit_stream" in n:
            mods[s:e] = True
    want["loop control"] = want.get("loop control", 0) + int(
        (mods & (owner < 0)).sum())
    got = trace_scopes.scan_split(ops, paths, fx["modules"], t0, t1)
    assert got == pytest.approx(want, abs=8)
    assert trace_scopes.phase_ns(got, "admit.search") == sum(
        v for k, v in got.items() if k.startswith("admit.search"))
    assert trace_scopes.phase_ns(got, "admit.nothing") is None


def test_coverage_matches_a_grid(fx):
    t0, t1 = fx["window"]
    ops, paths = fx["ops"], fx["paths_of_ops"]
    owner = _paint(ops, t1)
    own = np.bincount(owner[owner >= 0], minlength=len(ops))
    step = [i for i, p in enumerate(paths) if trace_scopes.in_step(p)
            and ops[i][0].split(".")[0] not in ("while", "cond")]
    total = sum(int(own[i]) for i in step)
    named = sum(int(own[i]) for i in step
                if trace_scopes.phase(paths[i]) is not None)
    got = trace_scopes.coverage(ops, paths)
    assert got == pytest.approx(named / total)
    assert 0 < got <= 1


def test_idle_by_span_matches_a_grid(fx):
    t0, t1 = fx["window"]
    busy = np.zeros(t1, bool)
    for _, s, e in fx["modules"]:
        busy[s:e] = True
    both = fx["spans"] + [("bench." + n, s, e)
                          for n, s, e in fx["program_spans"]]
    order = sorted(range(len(both)), key=lambda i: both[i][1])
    owner = _paint(both, t1, order)
    want = {}
    for i, n in zip(*np.unique(owner[~busy], return_counts=True)):
        name = "no span" if i < 0 else both[i][0][len("bench."):]
        want[name] = want.get(name, 0) + int(n)
    got = trace_scopes.idle_by_span(fx["modules"], fx["spans"],
                                    fx["program_spans"], t0, t1)
    assert got == want
    # the program's spans split the benchmark's: the drain's idle and
    # what stays in read-back add up to the read-back idle alone
    bench = trace_ops.attribute(trace_ops.gaps(fx["modules"], t0, t1),
                                fx["spans"])
    drain = sum(v for k, v in got.items() if k.startswith("repro.drain"))
    assert drain > 0
    assert drain + got.get("readback", 0) == bench["readback"]
    assert trace_scopes.share_of(got, "repro.drain", t1 - t0) == \
        pytest.approx(100.0 * drain / (t1 - t0))
    assert trace_scopes.share_of(got, "repro.nothing", t1) is None


def test_mean_span(fx):
    t0, t1 = fx["window"]
    hits = [e - s for n, s, e in fx["program_spans"]
            if n == "repro.drain.sync" and s >= t0]
    assert hits
    assert trace_scopes.mean_span_us(
        fx["program_spans"], "repro.drain.sync", t0, t1) == \
        pytest.approx(sum(hits) / len(hits) / 1e3)
    assert trace_scopes.mean_span_us(fx["program_spans"], "repro.none",
                                     t0, t1) is None


def test_scopes_read_nothing_from_an_unscoped_trace(fx):
    t0, t1 = fx["window"]
    blank = [""] * len(fx["ops"])
    assert trace_scopes.scan_split(fx["ops"], blank, fx["modules"],
                                   t0, t1) == {}
    assert trace_scopes.coverage(fx["ops"], blank) is None
    idle = trace_scopes.idle_by_span(fx["modules"], fx["spans"], [],
                                     t0, t1)
    assert trace_scopes.share_of(idle, "repro.drain", t1 - t0) is None


def test_hlo_paths_map_the_compiled_scan():
    import jax.numpy as jnp
    from repro.core import batch as batch_lib
    from repro.core import timeline as tl_lib
    from repro.core.types import ARRequest
    state = tl_lib.init_state(32, 64, 32, index_tile=8)
    batch = batch_lib.requests_to_batch(
        [ARRequest(t_a=i, t_r=i, t_du=5, t_dl=i + 20, n_pe=8)
         for i in range(4)])
    text = batch_lib.admit_stream_donated.lower(
        state, batch, jnp.int32(0), n_pe=64).compile().as_text()
    paths = trace_scopes.hlo_paths(text)
    phases = {trace_scopes.phase(p) for p in paths.values()}
    assert {"admit.release", "admit.search", "admit.commit"} <= phases
    name = next(n for n, p in paths.items()
                if trace_scopes.phase(p) == "admit.search"
                and trace_scopes.in_scan(p))
    mods = [("jit_admit_stream_donated(1)", 0, 100),
            ("jit_admit_stream(2)", 200, 300)]
    maps = {"admit_stream_donated": paths, "admit_stream": {}}
    got = trace_scopes.paths_by_module(
        [(name, 10, 20), (name, 210, 220), (name, 150, 160)], mods, maps)
    assert got == [paths[name], "", ""]


def test_load_keeps_program_spans_apart(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with harness.span("offer"):
        with jax.profiler.TraceAnnotation("repro.offer"):
            f(jnp.ones(4)).block_until_ready()
            time.sleep(0.001)
    jax.profiler.stop_trace()
    red = trace_scopes.load(str(tmp_path))
    assert [n for n, _, _ in red["spans"]] == ["bench.offer"]
    (name, s, e), = red["program_spans"]
    assert name == "repro.offer" and e - s >= 1_000_000


# the readers the benchmark had, on the trace they were written for
SMALL_READINGS = {
    "availscan_roofline": 1.3602267222207587,
    "availscan_us": 1.655258064516129,
    "device_idle_share.backlog": 16.656800000000004,
    "device_idle_share.open": 16.656800000000004,
    "offer_wall_ms.open": 2.0,
    "pad_step_share.open": 0.0,
    "scan_step_us.backlog": 39.067125,
}


@pytest.mark.parametrize("metric", sorted(SMALL_READINGS))
def test_existing_readers_read_as_before(metric):
    d = json.loads(SMALL.read_text())
    ev = {k: [tuple(e) for e in d[k]] for k in ("ops", "modules", "spans")}
    t0, t1 = d["window"]
    reading = harness.Reading(
        **ev, t0=t0, t1=t1, busy_ns=trace_ops.busy_ns(ev["modules"], t0, t1),
        counters=dict(scan_steps=64, decided=64, offer_wall_ms=[1.0, 3.0]),
        shapes=dict(capacity=128, n_pe=1024),
        peaks=dict(hbm_bytes_per_s=819e9))
    assert harness.load_reader(CHIP, metric)(reading) == pytest.approx(
        SMALL_READINGS[metric], rel=1e-12)
