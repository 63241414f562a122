"""The ReservationService session API (DESIGN.md §5).

Acceptance gates for the streaming redesign:

* chunked ``Session.offer`` over a 1000-job stream is decision- and
  metric-identical to the one-shot scan for all seven policies — with
  the jit cache provably stable after the first chunk (zero
  recompilation) and the staging ring wrapping around;
* mid-stream capacity growth inside a chunk reproduces the
  big-capacity decisions exactly (grow-once high-water protocol);
* the deprecated entry points (``make_scheduler``, ``DeviceScheduler``,
  ``admit_stream_auto``) warn and behave identically;
* the remaining verbs — ``tick``, ``cancel``, ``snapshot``/``restore``,
  ``metrics`` — and the ensemble / host / partition backends.
"""
import jax
import numpy as np
import pytest

from repro.api import OfferResult, ReservationService, ServiceConfig
from repro.core import batch as batch_lib
from repro.core import timeline as tl_lib
from repro.core.types import ALL_POLICIES, ARRequest, Policy
from repro.sim import WorkloadParams, generate

SMALL_SIZES = dict(u_low=2.0, u_med=4.0, u_hi=6.0)


def _workload(n_jobs, n_pe, seed=7):
    jobs = [j for j in generate(WorkloadParams(
        n_jobs=n_jobs, n_pe=n_pe, seed=seed, **SMALL_SIZES))
        if j.n_pe <= n_pe]
    return sorted(jobs, key=lambda j: j.t_a)


def _one_shot(jobs, n_pe, policy, capacity, pending_capacity):
    state = tl_lib.init_state(capacity, n_pe, pending_capacity)
    _, dec = batch_lib.admit_stream_grow(
        state, batch_lib.requests_to_batch(jobs), policy, n_pe=n_pe)
    return (np.asarray(dec.accepted), np.asarray(dec.t_s),
            np.asarray(dec.pe_mask))


def _offered_decisions(results):
    """Valid-only (accepted, t_s, pe_mask) across OfferResults."""
    acc, ts, masks = [], [], []
    for res in results:
        v = np.asarray(res.valid)
        acc.append(np.asarray(res.decision.accepted)[v])
        ts.append(np.asarray(res.decision.t_s)[v])
        masks.append(np.asarray(res.decision.pe_mask)[v])
    return (np.concatenate(acc), np.concatenate(ts),
            np.concatenate(masks))


# ---------------------------------------------------------------------------
# the acceptance gate: 1000 jobs, 7 policies, zero recompilation
# ---------------------------------------------------------------------------


def test_offer_1k_stream_identical_to_one_shot_all_policies():
    """Chunked streaming == one-shot scan, with a stable jit cache
    after the first chunk and a wrapped staging ring."""
    n_pe = 64
    jobs = _workload(1000, n_pe)
    assert len(jobs) >= 1000
    # one-shot references first (their own 1000-long scan shape gets
    # its cache entry out of the way of the chunked-path assertion)
    refs = {policy: _one_shot(jobs, n_pe, policy, 128, 256)
            for policy in ALL_POLICIES}
    rng = np.random.RandomState(0)
    warm_cache = None
    for policy in ALL_POLICIES:
        sess = ReservationService(ServiceConfig(
            n_pe=n_pe, policy=policy, capacity=128,
            pending_capacity=256, chunk_size=64,
            ring_capacity=128)).session()
        results, i = [], 0
        while i < len(jobs):
            take = int(rng.randint(1, 160))
            results.append(sess.offer(jobs[i:i + take]))
            i += take
            if warm_cache is None:
                # first chunk of the first policy compiled the scan;
                # nothing after it may compile again
                warm_cache = batch_lib.admit_stream._cache_size()
        acc, ts, masks = _offered_decisions(results)
        ref_acc, ref_ts, ref_masks = refs[policy]
        np.testing.assert_array_equal(acc, ref_acc)
        np.testing.assert_array_equal(ts, ref_ts)
        np.testing.assert_array_equal(masks, ref_masks)
        m = sess.metrics()
        # metric-identity with the one-shot run
        assert m["accepted"] == int(ref_acc.sum())
        assert m["offered"] == len(jobs)
        assert m["growths"] == 0
        assert m["ring_wrapped"]          # 1000 jobs through 128 slots
        assert m["chunks"] >= len(jobs) // 64
    assert warm_cache == batch_lib.admit_stream._cache_size(), \
        "chunked offer recompiled after warmup"


def test_offer_with_backfilling_compiles_once_per_chunk_shape():
    """Backfilling extension of the cache gate: the deferral mode is
    *traced*, so chunked offers compile once per chunk shape and an
    easy session, a conservative session and every policy share the
    same cache entry."""
    n_pe = 32
    jobs = _workload(260, n_pe, seed=13)
    warm = None
    for mode in ("easy", "conservative"):
        for policy in (Policy.PE_W, Policy.FF):
            sess = ReservationService(ServiceConfig(
                n_pe=n_pe, policy=policy, capacity=128,
                backfill=mode, backfill_queue=8, chunk_size=32,
                ring_capacity=64)).session()
            i = 0
            while i < len(jobs):
                sess.offer(jobs[i:i + 50])
                i += 50
                if warm is None:
                    # the first chunk of the first session compiled
                    # the Q=8 scan; nothing after it may compile
                    warm = batch_lib.admit_stream._cache_size()
    assert warm == batch_lib.admit_stream._cache_size(), \
        "backfilling offer recompiled after warmup"


def test_offer_mid_stream_growth_identical_to_big_capacity():
    """A chunk that overflows grows once (high-water) and re-runs;
    decisions match a session that started with ample capacity."""
    n_pe = 16
    # arrivals that pile up: every reservation is live at once
    jobs = [ARRequest(t_a=i, t_r=i, t_du=5000, t_dl=i + 5000, n_pe=1)
            for i in range(40)]
    small = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=8, pending_capacity=4, chunk_size=8,
        ring_capacity=16)).session()
    big = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=256, pending_capacity=256, chunk_size=8,
        ring_capacity=16)).session()
    res_s = [small.offer(jobs[:25]), small.offer(jobs[25:])]
    res_b = [big.offer(jobs[:25]), big.offer(jobs[25:])]
    acc_s, ts_s, masks_s = _offered_decisions(res_s)
    acc_b, ts_b, masks_b = _offered_decisions(res_b)
    np.testing.assert_array_equal(acc_s, acc_b)
    np.testing.assert_array_equal(ts_s, ts_b)
    np.testing.assert_array_equal(masks_s, masks_b)
    m = small.metrics()
    assert m["growths"] >= 1
    assert m["capacity"] > 8 and m["pending_capacity"] > 4
    assert big.metrics()["growths"] == 0


def test_offer_flush_false_stages_remainder():
    n_pe = 32
    jobs = _workload(90, n_pe, seed=3)
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=64, chunk_size=32,
        ring_capacity=64)).session()
    partial = sess.offer(jobs, flush=False)
    staged = sess.metrics()["ring_staged"]
    assert staged == len(jobs) % 32
    assert partial.n_offered == len(jobs) - staged
    rest = sess.flush()
    assert rest.n_offered == staged
    acc, ts, _ = _offered_decisions([partial, rest])
    ref_acc, ref_ts, _ = _one_shot(jobs, n_pe, Policy.PE_W, 64, 256)
    np.testing.assert_array_equal(acc, ref_acc)
    np.testing.assert_array_equal(ts, ref_ts)


# ---------------------------------------------------------------------------
# the cross-offer drain: results settled ahead, one sticky latch read
# ---------------------------------------------------------------------------

# three in-flight pipelined offers of 8-request chunks: 17 chunks of
# short jobs (one full settle group and a tail), then 8 short jobs and
# 8 that all stay live, then 8 more live ones.  Small capacities latch
# the first live chunk, the second offer's chunk 1 (dispatch 18).
_SHORT = [ARRequest(t_a=i, t_r=i, t_du=1, t_dl=i + 4, n_pe=1)
          for i in range(144)]
_LIVE = [ARRequest(t_a=200 + i, t_r=200 + i, t_du=5000,
                   t_dl=5200 + i, n_pe=1) for i in range(16)]
_BURST = [_SHORT[:136], _SHORT[136:] + _LIVE[:8], _LIVE[8:]]


def _drain_session(**kw):
    return ReservationService(ServiceConfig(
        n_pe=16, chunk_size=8, ring_capacity=64, **kw)).session()


@pytest.mark.parametrize("case", ["clean", "growth", "terminal"])
def test_pipelined_drain_settles_ahead_and_reads_one_latch(
        case, monkeypatch):
    """(a) an all-clear burst installs every offer's prepared results
    after one latch read, bit-identical to the eager path; (b) a growth
    latched in the second offer keeps the first offer's prepared
    results and replays the later two, as a big-capacity session
    decides; (c) a terminal overflow (growth budget spent) truncates
    the second offer, restages it and the third in arrival order, and
    counts nothing twice; (d) along the chain the newest latch copy is
    set exactly when any is — the invariant the one-read sync uses."""
    from repro.api import service as service_mod

    small = dict(clean=dict(capacity=256, pending_capacity=256),
                 growth=dict(capacity=8, pending_capacity=4),
                 terminal=dict(capacity=2, pending_capacity=2,
                               max_growths=1))[case]
    sess = _drain_session(**small)
    res = [sess.offer(reqs) for reqs in _BURST]
    inflight = list(sess._backend._inflight)
    assert len(inflight) == 3
    prepared = [ctx["prepared"] for ctx in inflight]
    latches = np.concatenate([np.asarray(jax.device_get(ctx["ovfs"]))
                              for ctx in inflight])
    # (d) sticky: once set, every later dispatch's latch stays set
    assert latches.tolist() == sorted(latches.tolist())
    assert bool(latches[-1]) == bool(latches.any())
    assert latches.any() == (case != "clean")
    if case != "clean":
        assert int(latches.argmax()) == 18

    calls = {"n": 0}
    real = service_mod._device_fetch

    def counting(tree):
        calls["n"] += 1
        return real(tree)

    monkeypatch.setattr(service_mod, "_device_fetch", counting)
    if case == "terminal":
        with pytest.raises(batch_lib.GrowthError):
            res[0].decision
    else:
        res[0].decision
    # the sync reads one scalar; only a latch reads the rest
    assert calls["n"] == (1 if case == "clean" else 2)
    monkeypatch.setattr(service_mod, "_device_fetch", real)

    kept = {"clean": 3, "growth": 1, "terminal": 1}[case]
    for r, prep in zip(res[:kept], prepared):
        assert r.decision is prep[0] and r.batch is prep[1]
    for r, prep in zip(res[kept:], prepared[kept:]):
        assert r.decision is not prep[0]
    m = sess.metrics()
    assert m["settled_ahead"] == kept
    assert m["settled_after_replay"] == {
        "clean": 0, "growth": 2, "terminal": 1}[case]

    if case == "terminal":
        # offer 2 decided its short chunk only; its live chunk and all
        # of offer 3 wait at the ring front, in arrival order
        assert [r.n_offered for r in res] == [136, 8, 0]
        assert m["accepted"] == 144 and m["chunks"] == 18
        assert m["offered"] == 160
        ring = sess._backend.ring
        batch, valid = ring.pop_chunk(ring.count, 16)
        assert np.asarray(batch.t_a)[np.asarray(valid)].tolist() == \
            [r.t_a for r in _LIVE]
        return
    # the reference: the eager path (clean), or ample capacity (growth)
    ref = _drain_session(donate=False, **(
        small if case == "clean" else dict(capacity=256,
                                           pending_capacity=256)))
    ref_res = [ref.offer(reqs) for reqs in _BURST]
    for r, e in zip(res, ref_res):
        np.testing.assert_array_equal(r.valid, e.valid)
        for got, want in zip(jax.tree_util.tree_leaves(
                (r.decision, r.batch)), jax.tree_util.tree_leaves(
                (e.decision, e.batch))):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
    assert m["accepted"] == ref.metrics()["accepted"] == 160


# ---------------------------------------------------------------------------
# the other verbs
# ---------------------------------------------------------------------------


def test_tick_releases_and_cancel_is_idempotent():
    sess = ReservationService(ServiceConfig(
        n_pe=8, capacity=32, chunk_size=4, ring_capacity=8)).session()
    r1 = sess.offer([ARRequest(t_a=0, t_r=0, t_du=10, t_dl=20,
                               n_pe=8)])
    assert r1.n_accepted == 1
    assert sess.tick(5) == 0              # nothing due yet
    assert sess.tick(15) == 1             # released
    assert sess.records() == []
    r2 = sess.offer([ARRequest(t_a=20, t_r=20, t_du=10, t_dl=40,
                               n_pe=8)])
    alloc = r2.allocations()[0]
    assert sess.cancel(alloc) is True
    assert sess.cancel(alloc) is False    # already withdrawn: no-op
    assert sess.records() == []
    # the capacity freed by cancel is immediately reusable
    r3 = sess.offer([ARRequest(t_a=20, t_r=20, t_du=10, t_dl=40,
                               n_pe=8)])
    assert r3.allocations()[0].t_s == alloc.t_s
    m = sess.metrics()
    assert (m["released"], m["cancelled"]) == (1, 1)


def test_snapshot_restore_roundtrip():
    n_pe = 32
    jobs = _workload(60, n_pe, seed=5)
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=64, chunk_size=8,
        ring_capacity=16)).session()
    sess.offer(jobs[:30])
    snap = sess.snapshot()
    records = sess.records()
    metrics = sess.metrics()
    sess.offer(jobs[30:])
    assert sess.metrics()["offered"] == len(jobs)
    sess.restore(snap)
    assert sess.records() == records
    assert sess.metrics() == metrics
    # the restored session continues identically
    again = sess.offer(jobs[30:])
    assert again.n_offered == len(jobs) - 30


# ---------------------------------------------------------------------------
# ensemble and host backends through the same verb set
# ---------------------------------------------------------------------------


def test_ensemble_session_matches_single_lane_sessions():
    n_pe = 32
    jobs = _workload(120, n_pe, seed=2)
    policies = [Policy.FF, Policy.PE_W, Policy.DU_B]
    streams = [jobs, jobs[:70], jobs[:45]]
    esess = ReservationService(ServiceConfig(
        n_pe=n_pe, lanes=3, capacity=64, chunk_size=16,
        ring_capacity=32)).session()
    eres = esess.offer(streams, policy=policies)
    acc = np.asarray(eres.decision.accepted)
    ts = np.asarray(eres.decision.t_s)
    for lane, (pol, stream) in enumerate(zip(policies, streams)):
        ssess = ReservationService(ServiceConfig(
            n_pe=n_pe, policy=pol, capacity=64, chunk_size=16,
            ring_capacity=32)).session()
        sres = ssess.offer(stream)
        v = eres.valid[lane]
        np.testing.assert_array_equal(
            acc[lane][v],
            np.asarray(sres.decision.accepted)[sres.valid])
        np.testing.assert_array_equal(
            ts[lane][v],
            np.asarray(sres.decision.t_s)[sres.valid])
    # ensemble tick releases the still-pending tail on every lane;
    # afterwards every accepted reservation has been released
    horizon = max(j.t_dl for j in jobs) + 1
    assert esess.tick(horizon) > 0
    states = esess._backend.states
    assert int(np.asarray(states.n_released).sum()) == \
        int(np.asarray(states.n_accepted).sum())
    for lane in range(3):
        assert esess._backend.records(lane) == []


def test_ensemble_filler_never_releases_ahead_of_staged_requests():
    """A lane contributing filler (flush=False) while it still holds
    staged requests must not advance that lane's release clock past
    them — filler is stamped with the last *popped* arrival."""
    n_pe = 4
    a = ARRequest(t_a=0, t_r=0, t_du=5, t_dl=5, n_pe=4)
    d = ARRequest(t_a=3, t_r=3, t_du=2, t_dl=5, n_pe=4)  # blocked by a
    e = ARRequest(t_a=7, t_r=7, t_du=2, t_dl=10, n_pe=4)
    lane0 = [ARRequest(t_a=t, t_r=t, t_du=1, t_dl=t + 3, n_pe=1)
             for t in range(8)]
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, lanes=2, capacity=32, chunk_size=4,
        ring_capacity=8)).session()
    r1 = sess.offer([[], [a]])                 # admit a on lane 1
    # lane 0 drives full-chunk drains while lane 1 stages d, e; the
    # filler chunks lane 1 contributes must not release a early
    r2 = sess.offer([lane0, [d, e]], flush=False)
    r3 = sess.flush()
    lane1 = np.concatenate(
        [np.asarray(r.decision.accepted)[1][np.asarray(r.valid)[1]]
         for r in (r1, r2, r3)])
    ref = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=32, chunk_size=4,
        ring_capacity=8)).session()
    ref_acc = np.concatenate([
        np.asarray(r.decision.accepted)[r.valid]
        for r in (ref.offer([a]), ref.offer([d, e]))])
    np.testing.assert_array_equal(lane1, ref_acc)
    assert list(ref_acc) == [True, False, True]


def test_host_and_device_sessions_agree():
    n_pe = 32
    jobs = _workload(80, n_pe, seed=11)
    dev = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=64, chunk_size=16,
        ring_capacity=32)).session()
    host = ReservationService(ServiceConfig(
        n_pe=n_pe, engine="host")).session()
    dres = dev.offer(jobs)
    hres = host.offer(jobs)
    np.testing.assert_array_equal(
        np.asarray(dres.decision.accepted)[dres.valid],
        np.asarray(hres.decision.accepted))
    np.testing.assert_array_equal(
        np.asarray(dres.decision.t_s)[dres.valid],
        np.asarray(hres.decision.t_s))
    assert dev.records() == host.records()
    horizon = max(j.t_dl for j in jobs) + 1
    assert dev.tick(horizon) == host.tick(horizon)
    assert host.records() == []


def test_partition_session_routes_bulk_offers():
    reqs = [ARRequest(t_a=0, t_r=0, t_du=100, t_dl=1000, n_pe=8)
            for _ in range(6)]
    sess = ReservationService(ServiceConfig(
        n_pe=32, n_partitions=2, auto_release=False,
        chunk_size=None)).session()
    res = sess.offer(reqs, routing="round_robin")
    allocs = res.allocations()
    assert sum(a is not None for a in allocs) == 6
    lanes = {a.pe_ids[0] // 16 for a in allocs}
    assert lanes == {0, 1}                 # spread across partitions
    assert sess.cancel(allocs[0]) is True
    assert sess.metrics()["chips_per_partition"] == 16


# ---------------------------------------------------------------------------
# config validation and the deprecation shims
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="unknown engine"):
        ServiceConfig(n_pe=8, engine="gpu")
    with pytest.raises(ValueError, match="exclusive"):
        ServiceConfig(n_pe=8, lanes=2, n_partitions=2)
    with pytest.raises(ValueError, match="device"):
        ServiceConfig(n_pe=8, engine="host", lanes=2)
    with pytest.raises(ValueError, match="divisible"):
        ServiceConfig(n_pe=10, n_partitions=3)
    with pytest.raises(ValueError, match="routing"):
        ServiceConfig(n_pe=8, routing="nearest")
    with pytest.raises(ValueError, match="ring_capacity"):
        ServiceConfig(n_pe=8, chunk_size=64, ring_capacity=8)
    with pytest.raises(TypeError, match="unknown device engine"):
        ServiceConfig.from_engine_kwargs(8, "device", buckets=True)
    # partitioned sessions handle completions either way now (lanes
    # auto-release via tick, or the caller deletes); growth stays
    # internal to the core
    assert ServiceConfig(n_pe=8, n_partitions=2).auto_release
    assert not ServiceConfig(n_pe=8, n_partitions=2,
                             auto_release=False).auto_release
    with pytest.raises(ValueError, match="auto_grow"):
        ServiceConfig(n_pe=8, n_partitions=2, auto_release=False,
                      auto_grow=False)
    with pytest.raises(ValueError, match="first-class"):
        ServiceConfig(n_pe=8, engine="device",
                      engine_kwargs={"capacity": 4})


def test_auto_grow_false_raises_before_any_growth():
    jobs = [ARRequest(t_a=i, t_r=i, t_du=5000, t_dl=i + 5000, n_pe=1)
            for i in range(30)]
    sess = ReservationService(ServiceConfig(
        n_pe=16, capacity=8, pending_capacity=4, auto_grow=False,
        chunk_size=8, ring_capacity=16)).session()
    with pytest.raises(RuntimeError, match="overflowing"):
        sess.offer(jobs)
    m = sess.metrics()
    assert m["growths"] == 0
    assert m["capacity"] == 8 and m["pending_capacity"] == 4
    # the overflowing chunk's requests went back to the ring, so a
    # manual recovery (e.g. a grown session restore) loses nothing
    assert m["ring_staged"] > 0


def test_ensemble_cancel_targets_the_named_lane():
    r = ARRequest(t_a=0, t_r=0, t_du=100, t_dl=200, n_pe=4)
    sess = ReservationService(ServiceConfig(
        n_pe=8, lanes=2, capacity=32, chunk_size=4,
        ring_capacity=8)).session()
    res = sess.offer([[r], [r]])
    allocs = [
        batch_lib.decisions_to_allocations(
            batch_lib.Decision(*[np.asarray(f)[lane]
                                 for f in res.decision]))[0]
        for lane in range(2)]
    # cancelling on lane 1 must not touch lane 0's timeline
    assert sess.cancel(allocs[1], lane=1) is True
    assert sess._backend.records(0) != []
    assert sess._backend.records(1) == []
    assert sess.cancel(allocs[1], lane=1) is False   # idempotent
    with pytest.raises(ValueError, match="out of range"):
        sess.cancel(allocs[0], lane=5)
    # non-ensemble sessions reject a lane
    flat = ReservationService(ServiceConfig(
        n_pe=8, chunk_size=4, ring_capacity=8)).session()
    a = flat.offer([r]).allocations()[0]
    with pytest.raises(ValueError, match="ensemble"):
        flat.cancel(a, lane=1)


def test_flush_false_rejected_without_a_ring():
    r = ARRequest(t_a=0, t_r=0, t_du=10, t_dl=100, n_pe=2)
    for cfg in (ServiceConfig(n_pe=8, chunk_size=None),
                ServiceConfig(n_pe=8, engine="host"),
                ServiceConfig(n_pe=8, n_partitions=2,
                              auto_release=False, chunk_size=None)):
        sess = ReservationService(cfg).session()
        with pytest.raises(ValueError, match="flush=False"):
            sess.offer([r], flush=False)


def test_make_scheduler_shim_forwards_host_engine_kwargs():
    import warnings

    from repro.core.scheduler import make_scheduler

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        host = make_scheduler(16, engine="host", candidate_chunk=32)
        assert host._chunk == 32
        with pytest.raises(TypeError):
            make_scheduler(16, engine="host", capacity=64)
        with pytest.raises(TypeError):
            make_scheduler(16, engine="list", candidate_chunk=32)


def test_offer_requires_arrival_order_and_rejects_atomically():
    late = ARRequest(t_a=100, t_r=100, t_du=5, t_dl=110, n_pe=1)
    early = ARRequest(t_a=50, t_r=50, t_du=5, t_dl=60, n_pe=1)
    for cfg in (ServiceConfig(n_pe=8, chunk_size=4, ring_capacity=8),
                ServiceConfig(n_pe=8, engine="host")):
        sess = ReservationService(cfg).session()
        sess.offer([late])
        with pytest.raises(ValueError, match="arrival-ordered"):
            sess.offer([late, early])
        m = sess.metrics()
        # the rejected offer left nothing behind: no staging, no
        # counter drift (the in-order prefix was not half-admitted)
        assert m["offered"] == 1
        assert m.get("ring_staged", 0) == 0


def test_ensemble_flush_false_keeps_partial_lanes_staged():
    r = [ARRequest(t_a=i, t_r=i, t_du=10, t_dl=i + 50, n_pe=1)
         for i in range(8)]
    sess = ReservationService(ServiceConfig(
        n_pe=8, lanes=2, capacity=32, chunk_size=4,
        ring_capacity=8)).session()
    res = sess.offer([r, r[:1]], flush=False)
    # lane 0 drained its two full chunks; lane 1's single request
    # stays staged (the flush=False contract)
    assert res.n_offered == 8
    assert [ring.count for ring in sess._backend.rings] == [0, 1]
    rest = sess.flush()
    assert rest.n_offered == 1
    assert sum(ring.count for ring in sess._backend.rings) == 0


def _paper_example(s, pes=list):
    s.add_allocation(0, 300, pes(range(0, 20)))
    s.add_allocation(0, 100, pes(range(20, 50)))
    s.add_allocation(800, 1000, pes(range(0, 25)))


def test_make_scheduler_shim_warns_and_matches_service():
    req = ARRequest(t_a=0, t_r=200, t_du=200, t_dl=900, n_pe=40)
    for engine in ("list", "host", "device"):
        with pytest.warns(DeprecationWarning,
                          match="make_scheduler is deprecated"):
            from repro.core.scheduler import make_scheduler
            old = make_scheduler(100, engine=engine)
        _paper_example(old, set if engine == "list" else list)
        sess = ReservationService(ServiceConfig(
            n_pe=100, engine=engine)).session()
        _paper_example(sess)
        for pol in ALL_POLICIES:
            a = old.find_allocation(req, pol)
            b = sess.find_allocation(req, pol)
            assert (a.t_s, a.t_e, a.pe_ids, a.rectangle) == \
                (b.t_s, b.t_e, b.pe_ids, b.rectangle)


def test_device_scheduler_shim_warns_and_matches_engine():
    from repro.core.scheduler import DeviceEngine, DeviceScheduler

    with pytest.warns(DeprecationWarning,
                      match="DeviceScheduler is deprecated"):
        old = DeviceScheduler(100, capacity=64)
    assert isinstance(old, DeviceEngine)
    new = ReservationService(ServiceConfig(
        n_pe=100, engine="device", capacity=64)).session().engine
    assert isinstance(new, DeviceEngine)
    _paper_example(old)
    _paper_example(new)
    req = ARRequest(t_a=0, t_r=200, t_du=200, t_dl=900, n_pe=40)
    a = old.admit(req, Policy.PE_W)
    b = new.admit(req, Policy.PE_W)
    assert (a.t_s, a.t_e, a.pe_ids) == (b.t_s, b.t_e, b.pe_ids)
    assert old.records() == new.records()


def test_admit_stream_auto_shim_warns_and_matches_grow():
    n_pe = 16
    jobs = _workload(50, n_pe, seed=9)
    batch = batch_lib.requests_to_batch(jobs)
    state = tl_lib.init_state(64, n_pe, 64)
    with pytest.warns(DeprecationWarning,
                      match="admit_stream_auto is deprecated"):
        out_a, dec_a = batch_lib.admit_stream_auto(
            state, batch, Policy.PE_W, n_pe=n_pe)
    out_b, dec_b = batch_lib.admit_stream_grow(
        state, batch, Policy.PE_W, n_pe=n_pe)
    np.testing.assert_array_equal(np.asarray(dec_a.accepted),
                                  np.asarray(dec_b.accepted))
    np.testing.assert_array_equal(np.asarray(dec_a.t_s),
                                  np.asarray(dec_b.t_s))
    np.testing.assert_array_equal(np.asarray(out_a.tl.times),
                                  np.asarray(out_b.tl.times))


def test_offer_result_empty_and_prepacked_guard():
    sess = ReservationService(ServiceConfig(
        n_pe=8, chunk_size=4, ring_capacity=8)).session()
    empty = sess.offer([])
    assert isinstance(empty, OfferResult)
    assert empty.n_offered == 0 and empty.allocations() == []
    with pytest.raises(ValueError, match="bypasses the ring"):
        sess.offer(batch_lib.requests_to_batch(
            [ARRequest(t_a=0, t_r=0, t_du=5, t_dl=10, n_pe=1)]))


def test_push_front_recovers_arrival_order_across_repeated_latches():
    """Three consecutive latched offers restage to the ring *front*:
    contents stay in arrival order through physical wraparound, and
    ``last_popped_t_a`` stays rewound to the newest decided arrival
    so later partial chunks cannot release undecided predecessors."""
    import warnings

    sess = ReservationService(ServiceConfig(
        n_pe=16, capacity=8, pending_capacity=4, auto_grow=False,
        chunk_size=8, ring_capacity=16)).session()
    ring = sess._backend.ring
    # feasible warm-up advances the ring head and the filler stamp
    warm = [ARRequest(t_a=i, t_r=i, t_du=1, t_dl=i + 4, n_pe=1)
            for i in range(10)]
    res = sess.offer(warm)
    assert int(np.asarray(res.decision.accepted).sum()) == 10
    assert ring._head == 10 and ring.last_popped_t_a == 9
    # three overflowing waves, each fully restaged (no drops)
    over = [ARRequest(t_a=100 + i, t_r=100 + i, t_du=5000,
                      t_dl=100 + i + 5000, n_pe=1)
            for i in range(16)]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        for lo, hi in ((0, 6), (6, 11), (11, 16)):
            with pytest.raises(RuntimeError, match="overflowing"):
                sess.offer(over[lo:hi])
            assert ring.count == hi           # everything restaged...
            assert ring._head == 10           # ...at the front
            assert ring.last_popped_t_a == 9  # stamp stays rewound
    assert sess.metrics()["growths"] == 0
    # count 16 at head 10 means the ring physically wrapped; popping
    # must replay the undecided requests in exact arrival order
    batch, valid = ring.pop_chunk(ring.count, 16)
    assert np.asarray(batch.t_a)[np.asarray(valid)].tolist() \
        == [100 + i for i in range(16)]
