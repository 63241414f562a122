"""Tracing inside the admission path (DESIGN.md §13).

* The scan step's phases carry ``jax.named_scope``\\ s (``admit``,
  ``admit.release``, ``admit.search`` with ``admit.search.reject`` /
  ``.candidates`` / ``.rects`` / ``.mask``, ``admit.commit``) in the
  lowered ``admit_stream_donated``, on the jnp and the kernel path.
* The state's work counters (``n_early_rejects``, ``n_search_tiles``,
  ``n_search_tiles_run``, ``n_search_pe_blocks``) equal a per-request
  brute-force recount of ``summary_reject``, of the kernel's live tiles
  and of the PE blocks it ran for them, filler excluded;
  ``Session.metrics()`` reports them, ensembles sum their lanes, and
  they rewind on ``restore`` and round-trip through a checkpoint.
* Decisions stay those of the host engine with the counters in place.
* A profiler trace of a pipelined offer and its read-back holds the
  service's ``repro.*`` host spans, nested as documented.
"""
import dataclasses
import glob
import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ReservationService, ServiceConfig
from repro.core import availindex as idx_lib
from repro.core import batch as batch_lib
from repro.core import search as search_lib
from repro.core import timeline as tl_lib
from repro.core.types import ARRequest, Policy, T_INF
from repro.kernels import availscan

N_PE, CAPACITY, TILE, CHUNK = 64, 64, 8, 8
COUNTERS = ("early_rejects", "search_tiles", "search_tiles_skipped",
            "search_pe_blocks")


def _stream(n_jobs=60, seed=3):
    """A loaded stream on N_PE PEs: every other request is a long,
    flexible one that fills the future; the rest are wide and tight,
    so the index rejects some whole and the kernel skips tiles."""
    rng = random.Random(seed)
    jobs = []
    for i in range(n_jobs):
        if i % 2 == 0:
            t_r, t_du, slack, k = i, rng.randint(20, 40), 300, \
                rng.randint(33, 48)
        else:
            t_r, t_du, slack, k = i + rng.randint(0, 20), \
                rng.randint(5, 20), rng.randint(0, 10), \
                rng.randint(24, N_PE)
        jobs.append(ARRequest(t_a=i, t_r=t_r, t_du=t_du,
                              t_dl=t_r + t_du + slack, n_pe=k))
    return jobs


def _config(**kw):
    base = dict(n_pe=N_PE, capacity=CAPACITY, chunk_size=CHUNK,
                ring_capacity=32, use_kernel=True, index_tile=TILE,
                policy=Policy.FF)
    base.update(kw)
    return ServiceConfig(**base)


def _decisions(res):
    v = np.asarray(res.valid)
    dec = res.decision
    return (np.asarray(dec.accepted)[v], np.asarray(dec.t_s)[v],
            np.asarray(dec.pe_mask)[v])


def _brute_counts(jobs, use_kernel, n_pe=N_PE):
    """Recount per request, outside the scan: the index's early reject
    on the released state, and the kernel grid's tiles over the pruned
    candidates of every request it does not reject, each live tile
    running every PE block."""
    state = tl_lib.init_state(CAPACITY, n_pe, index_tile=TILE)
    ispec = state.tl.ispec
    deficit = idx_lib.plane_deficit(ispec, None)
    rejects = tiles = run = 0
    pt = availscan.DEFAULT_PT
    n_blocks = availscan.pe_blocks(tl_lib.n_words(n_pe))[1]
    for j in jobs:
        tl = batch_lib.release_due(state, jnp.int32(j.t_a)).tl
        demand = jnp.asarray([j.n_pe], jnp.int32)
        args = (jnp.int32(j.t_r), jnp.int32(j.t_du), jnp.int32(j.t_dl))
        if bool(search_lib.summary_reject(tl, *args, demand, deficit)):
            rejects += 1
        elif use_kernel:
            starts = search_lib.prune_candidates(
                tl, search_lib.candidate_starts(tl, *args),
                jnp.int32(j.t_du), demand, deficit)
            live = np.asarray(starts) < T_INF
            n_tiles = -(-len(live) // pt)
            tiles += n_tiles
            run += sum(live[k * pt:(k + 1) * pt].any()
                       for k in range(n_tiles))
        state, _ = batch_lib.admit(
            state, batch_lib.request_struct(j), jnp.int32(0), n_pe=n_pe,
            use_kernel=use_kernel)
    assert not bool(state.overflow)
    return dict(early_rejects=rejects, search_tiles=tiles,
                search_tiles_skipped=tiles - run,
                search_pe_blocks=run * n_blocks)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "kernel"])
def test_admit_scopes_in_lowered_hlo(use_kernel):
    state = tl_lib.init_state(CAPACITY, N_PE, index_tile=TILE)
    batch = batch_lib.requests_to_batch(_stream(4))
    text = batch_lib.admit_stream_donated.lower(
        state, batch, jnp.int32(0), n_pe=N_PE,
        use_kernel=use_kernel).as_text(debug_info=True)
    for scope in ("admit/admit.release", "admit/admit.search",
                  "admit/admit.commit", "admit.search.reject",
                  "admit.search.candidates", "admit.search.rects",
                  "admit.search.mask"):
        assert scope in text, scope
    if use_kernel:
        assert "admit.search.rects/jit(availscan_select)" in text


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "kernel"])
def test_work_counters_match_brute_force(use_kernel):
    jobs = _stream()
    sess = ReservationService(_config(use_kernel=use_kernel)).session()
    sess.offer(jobs)              # flushed: the last chunk holds filler
    m = sess.metrics()
    assert m["growths"] == 0 and m["chunks"] * CHUNK > len(jobs)
    want = _brute_counts(jobs, use_kernel)
    assert {k: m[k] for k in COUNTERS} == want
    assert want["early_rejects"] > 0
    if use_kernel:
        assert 0 < want["search_tiles_skipped"] < want["search_tiles"]
    else:
        assert want["search_tiles"] == 0


def test_decisions_unchanged_and_counters_rewind_on_restore():
    jobs = _stream()
    host = ReservationService(_config(
        engine="host", use_kernel=False, index_tile=None)).session()
    ref = _decisions(host.offer(jobs))
    sess = ReservationService(_config()).session()
    snap = sess.snapshot()
    first = _decisions(sess.offer(jobs))
    counts = {k: sess.metrics()[k] for k in COUNTERS}
    sess.restore(snap)
    assert all(sess.metrics()[k] == 0 for k in COUNTERS)
    again = _decisions(sess.offer(jobs))
    assert {k: sess.metrics()[k] for k in COUNTERS} == counts
    for a, b, r in zip(first, again, ref):
        np.testing.assert_array_equal(a, r)
        np.testing.assert_array_equal(b, r)


def test_ensemble_sums_lanes_and_partitions_report():
    jobs = _stream()
    one = ReservationService(_config()).session()
    one.offer(jobs)
    single = {k: one.metrics()[k] for k in COUNTERS}
    ens = ReservationService(_config(lanes=2)).session()
    ens.offer([jobs, jobs])
    assert {k: ens.metrics()[k] for k in COUNTERS} == {
        k: 2 * v for k, v in single.items()}
    part = ReservationService(_config(
        n_pe=2 * N_PE, n_partitions=2, chunk_size=None,
        ring_capacity=None)).session()
    part.offer(jobs)
    m = part.metrics()
    assert 0 <= m["search_tiles_skipped"] <= m["search_tiles"]
    assert m["search_tiles"] > 0


@pytest.mark.parametrize("n_pe,block_words", [
    (1024, availscan.BLOCK_WORDS), (320, 4)], ids=["one-block", "blocks"])
def test_pe_block_counter_and_restore(n_pe, block_words, monkeypatch):
    """One PE block per live tile at 1024 PEs, ``ceil(words / block)``
    past one block (320 PEs in blocks of 4 words: three), and
    ``restore`` rewinds the count."""
    monkeypatch.setattr(availscan, "BLOCK_WORDS", block_words)
    jax.clear_caches()
    n_blocks = availscan.pe_blocks(tl_lib.n_words(n_pe))[1]
    assert n_blocks == -(-tl_lib.n_words(n_pe) // block_words)
    jobs = [dataclasses.replace(j, n_pe=j.n_pe * n_pe // N_PE)
            for j in _stream(n_jobs=30)]
    sess = ReservationService(_config(n_pe=n_pe)).session()
    snap = sess.snapshot()
    sess.offer(jobs)
    m = sess.metrics()
    run = m["search_tiles"] - m["search_tiles_skipped"]
    assert m["search_pe_blocks"] == n_blocks * run > 0
    if n_blocks > 1:
        assert {k: m[k] for k in COUNTERS} == _brute_counts(jobs, True,
                                                            n_pe)
    sess.restore(snap)
    assert sess.metrics()["search_pe_blocks"] == 0
    sess.offer(jobs)
    assert sess.metrics()["search_pe_blocks"] == m["search_pe_blocks"]
    jax.clear_caches()


def test_counters_round_trip_a_checkpoint(tmp_path):
    from repro.checkpoint import CheckpointManager
    jobs = _stream()
    state = tl_lib.init_state(CAPACITY, N_PE, index_tile=TILE)
    state, _ = batch_lib.admit_stream_grow(
        state, batch_lib.requests_to_batch(jobs), Policy.FF, n_pe=N_PE,
        use_kernel=True)
    assert int(state.n_search_tiles) > 0
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, state)
    back, step, _ = mgr.restore(tl_lib.init_state(
        CAPACITY, N_PE, index_tile=TILE))
    assert step == 1
    for f in ("n_early_rejects", "n_search_tiles", "n_search_tiles_run",
              "n_search_pe_blocks", "n_accepted"):
        assert int(getattr(back, f)) == int(getattr(state, f)), f


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    return [(e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_service_spans_nest_as_documented(tmp_path):
    jobs = _stream()
    sess = ReservationService(_config(use_kernel=False)).session()
    snap = sess.snapshot()
    sess.restore(snap)
    sess.offer(jobs[:CHUNK], flush=False)   # warm, shared state: eager
    _decisions(sess.offer(jobs[CHUNK:]))    # warm, donated: pipelined
    sess.restore(snap)
    eager = sess.offer(jobs[:CHUNK], flush=False)
    jax.profiler.start_trace(str(tmp_path))
    rest = sess.offer(jobs[CHUNK:])
    _decisions(rest)                        # the drain
    sess.restore(snap)
    jax.profiler.stop_trace()
    del eager
    spans = _host_spans(str(tmp_path))
    names = [n for n, _, _ in spans]
    for want in ("repro.offer", "repro.offer.stage", "repro.offer.dispatch",
                 "repro.offer.settle", "repro.drain", "repro.drain.sync",
                 "repro.drain.concat", "repro.restore"):
        assert want in names, want
    assert "repro.drain.replay" not in names     # no overflow

    def inside(child, parent):
        return [c for c in spans if c[0] == child and not any(
            p[1] <= c[1] and c[2] <= p[2] for p in spans if p[0] == parent)]

    assert not inside("repro.offer.stage", "repro.offer")
    assert not inside("repro.offer.dispatch", "repro.offer")
    # the pipelined offer queues its own settling
    assert not inside("repro.offer.settle", "repro.offer")
    assert not inside("repro.drain.sync", "repro.drain")
    assert not inside("repro.drain.concat", "repro.drain")
    chunks = -(-(len(jobs) - CHUNK) // CHUNK)
    assert names.count("repro.offer.dispatch") == chunks
    # the pipelined offer returns before its drain
    (_, o0, o1), = [s for s in spans if s[0] == "repro.offer"]
    (_, d0, _), = [s for s in spans if s[0] == "repro.drain"]
    assert o1 <= d0
