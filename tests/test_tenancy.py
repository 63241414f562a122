"""Multi-tenant admission (DESIGN.md §10), locked down differentially.

The tenancy subsystem threads a :class:`repro.tenancy.TenantTable`
through the fused admit step: a quota gate before the search, a
weighted fair-share ranking in the deferral-queue sweeps, overdue
reaping in ``Session.tick`` and per-tenant telemetry folded into the
device-resident accumulators.  The gates here:

* **zero-tenant default**: ``tenants=None`` contributes no pytree
  leaves — state, decisions and metrics are exactly the PR 7 ones;
* **equal-weight / unlimited-quota neutrality**: a tenant table whose
  weights are all equal and whose quotas/caps are unlimited is
  bit-identical to no table at all — decisions, records, queue state
  and counters — across the 1000-job × 7-policy × 3-backfill matrix
  (the FCFS-equivalence invariant of the fair-share key);
* **host oracle**: :class:`repro.core.hostsched.TenantOracle` matches
  the device path bit-for-bit on quota rejections, fair-share
  promotion order, reaping, and every per-tenant counter including
  the fixed-point EWMAs (whose int32 device arithmetic equals their
  exact integer definitions on any backend);
* **poll-cheap telemetry**: an idle ``Session.metrics()`` performs
  zero device fetches (satellite: the ``_device_fetch`` choke point).
"""
import dataclasses

import numpy as np
import pytest

from repro.api import ReservationService, ServiceConfig
from repro.core import batch as batch_lib
from repro.core import ensemble as ens_lib
from repro.core import timeline as tl_lib
from repro.core.hostsched import TenantOracle
from repro.core.policies import policy_index
from repro.core.types import ALL_POLICIES, ARRequest, Policy, T_INF
from repro.sim import WorkloadParams, generate_filtered
from repro.tenancy import (TenantSpec, init_table, stack_tables,
                           tenant_view)

N_PE = 16
SIZES = dict(u_low=2.0, u_med=3.0, u_hi=4.0)
MODES = ("none", "easy", "conservative")


def _workload(n_jobs, seed, load=2.0, n_pe=N_PE, n_tenants=0):
    jobs = generate_filtered(WorkloadParams(
        n_jobs=n_jobs, n_pe=n_pe, seed=seed, arrival_factor=load,
        **SIZES), max_pe=n_pe)
    jobs = sorted(jobs, key=lambda j: j.t_a)
    if n_tenants:
        rng = np.random.default_rng(seed + 1)
        jobs = [dataclasses.replace(
            j, tenant=int(rng.integers(0, n_tenants))) for j in jobs]
    return jobs


def _records(state):
    times = np.asarray(state.tl.times)
    occ = np.asarray(state.tl.occ)
    return [(int(t), frozenset(batch_lib.mask32_to_ids(o)))
            for t, o in zip(times, occ) if t < T_INF]


def _queue(state):
    """Parked entries with the tenancy-only keys stripped."""
    drop = ("tenant", "t_a")
    return [{k: v for k, v in e.items() if k not in drop}
            for e in batch_lib.parked_entries(state)]


def _run_device(jobs, policy, mode, spec, *, Q=8, capacity=64,
                pending=128, n_pe=N_PE):
    table = (init_table(spec, pending, Q)
             if spec is not None else None)
    state = tl_lib.init_state(capacity, n_pe, pending,
                              park_capacity=Q, tenants=table)
    out, dec = batch_lib.admit_stream_grow(
        state,
        batch_lib.requests_to_batch(jobs,
                                    with_tenant=spec is not None),
        policy, n_pe=n_pe, backfill=mode)
    trace = [(bool(a), int(t), bool(p)) for a, t, p in
             zip(np.asarray(dec.accepted), np.asarray(dec.t_s),
                 np.asarray(dec.parked))]
    return trace, out


# ---------------------------------------------------------------------------
# the neutrality gate: equal weights + unlimited quotas == no tenants
# ---------------------------------------------------------------------------


def test_equal_weight_unlimited_is_bit_identical_to_no_tenants():
    """1000 jobs × 7 policies × 3 backfill modes, one vmapped
    ensemble dispatch per variant: an all-equal tenant table must not
    change a single decision, record, queue entry or counter."""
    n_pe = 64
    jobs = generate_filtered(WorkloadParams(
        n_jobs=1000, n_pe=n_pe, seed=3, arrival_factor=1.0),
        max_pe=n_pe)
    jobs = sorted(jobs, key=lambda j: j.t_a)
    assert len(jobs) >= 500
    jobs = [dataclasses.replace(j, tenant=i % 3)
            for i, j in enumerate(jobs)]
    cells = [(p, m) for p in ALL_POLICIES for m in MODES]
    spec = TenantSpec(weights=(1.0, 1.0, 1.0))   # unlimited quotas

    def run(tenants):
        sess = ReservationService(ServiceConfig(
            n_pe=n_pe, lanes=len(cells), capacity=128,
            pending_capacity=256, chunk_size=None,
            backfill=tuple(m for _, m in cells),
            backfill_queue=8, tenants=tenants)).session()
        batch, valid = batch_lib.pad_streams(
            [jobs] * len(cells), n_pe,
            with_tenant=tenants is not None)
        pids = np.asarray([policy_index(p) for p, _ in cells],
                          np.int32)
        res = sess.offer((batch, valid), policy=pids)
        return sess, res

    sess0, res0 = run(None)
    sess1, res1 = run((spec,) * len(cells))
    for f in ("accepted", "t_s", "parked"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res0.decision, f)),
            np.asarray(getattr(res1.decision, f)))
    for lane in range(len(cells)):
        m0 = ens_lib.member(sess0._backend.states, lane)
        m1 = ens_lib.member(sess1._backend.states, lane)
        assert _records(m0) == _records(m1), cells[lane]
        assert _queue(m0) == _queue(m1), cells[lane]
        for c in ("n_parked", "n_promoted", "n_moved", "n_released"):
            assert int(getattr(m0, c)) == int(getattr(m1, c)), \
                (cells[lane], c)
    assert ens_lib.member(sess0._backend.states, 0).tenants is None
    assert "tenants" not in sess0.metrics()
    assert "tenants" in sess1.metrics()


def test_fair_key_reduces_to_fcfs_under_equal_weights():
    """Host statement of the same invariant: the weighted key with
    equal weights sorts exactly like the FCFS seq order."""
    spec = TenantSpec(weights=(2.5, 2.5, 2.5))
    orc = TenantOracle(N_PE, Policy.FF, "easy", spec)
    entries = [dict(seq=s, tenant=s % 3, t_a=t)
               for s, t in enumerate([0, 0, 3, 3, 7])]
    for t_now in (7, 10, 100):
        order = sorted(entries,
                       key=lambda p: orc._order_key(p, t_now))
        assert [p["seq"] for p in order] == [0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# device == TenantOracle: gate, fair share, counters, EWMAs
# ---------------------------------------------------------------------------


SPEC = TenantSpec(weights=(1.0, 4.0, 2.0),
                  quotas=(500.0, None, 800.0),
                  max_live=(None, 6, None))


def test_device_matches_tenant_oracle_bit_for_bit():
    jobs = _workload(300, seed=3, n_tenants=3)
    for mode in MODES:
        for policy in (Policy.FF, Policy.PE_B, Policy.PEDU_W):
            trace, out = _run_device(jobs, policy, mode, SPEC)
            orc = TenantOracle(N_PE, policy, mode, SPEC,
                               park_capacity=8)
            assert trace == [orc.admit(r) for r in jobs], \
                (mode, policy)
            assert _records(out) == orc.records(), (mode, policy)
            t, a = out.tenants, orc.accounts
            for f in ("used", "live", "n_accepted", "n_rejected",
                      "n_quota_rejected", "n_parked", "acc_ewma",
                      "slow_ewma"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(t, f)), getattr(a, f),
                    err_msg=f"{mode}/{policy}/{f}")
            assert np.asarray(t.occ_ewma) == a.occ_ewma
            assert int(np.asarray(t.n_quota_rejected).sum()) > 0


@pytest.mark.parametrize("kind", ["ratio", "ewma"])
def test_fixed_point_ewma_matches_exact_definition(kind):
    """The int32 device arithmetic of the telemetry EWMAs equals the
    exact (Python int) definitions, overflow edges included."""
    import jax.numpy as jnp

    from repro.tenancy import table as tn_lib
    rng = np.random.default_rng(11)
    top = 2**31 - 1
    if kind == "ratio":
        cases = [(0, 1), (5, 1), (1, 3), (top, 1), (top, top),
                 (2**15, 1), (2**15 - 1, 1), (7, 2**30 + 3),
                 (123456789, 10800), (-4, 3)]
        cases += list(zip(rng.integers(0, top, 500).tolist(),
                          rng.integers(1, top, 500).tolist()))
        cases += list(zip(rng.integers(0, 2**20, 500).tolist(),
                          rng.integers(1, 2**16, 500).tolist()))
        cols = [jnp.asarray(c, jnp.int32) for c in zip(*cases)]
        got = np.asarray(tn_lib.ratio_q16(*cols))
        want = [tn_lib.ratio_q16_exact(*c) for c in cases]
    else:
        one = tn_lib.EWMA_ONE
        cases = [(0, top, one), (top, 0, one), (top, 0, 1), (0, top, 1),
                 (one, 0, 3277), (0, one, 3277), (5, 5, 100)]
        cases += list(zip(rng.integers(0, top, 1000).tolist(),
                          rng.integers(0, top, 1000).tolist(),
                          rng.integers(1, one + 1, 1000).tolist()))
        cols = [jnp.asarray(c, jnp.int32) for c in zip(*cases)]
        got = np.asarray(tn_lib.ewma_q16(*cols))
        want = [tn_lib.ewma_q16_exact(*c) for c in cases]
    np.testing.assert_array_equal(got, np.asarray(want, np.int64))


def test_fair_share_changes_promotion_order_and_matches_oracle():
    """A heavy tenant's parked reservation outranks an earlier light
    one in the EASY retry sweep — and the device still matches the
    oracle bit for bit under the skewed weights."""
    spec = TenantSpec(weights=(1.0, 16.0))
    jobs = _workload(300, seed=9, n_tenants=2)
    base = TenantSpec(weights=(1.0, 1.0))
    for policy in (Policy.FF, Policy.PE_B):
        skew, out_s = _run_device(jobs, policy, "easy", spec)
        flat, out_f = _run_device(jobs, policy, "easy", base)
        orc = TenantOracle(N_PE, policy, "easy", spec,
                           park_capacity=8)
        assert skew == [orc.admit(r) for r in jobs], policy
        assert _records(out_s) == orc.records(), policy
    # the weights must be observable somewhere across seeds/policies
    diffs = 0
    for seed in (9, 10, 11):
        jb = _workload(300, seed=seed, n_tenants=2)
        for policy in (Policy.FF, Policy.PE_B):
            s, _ = _run_device(jb, policy, "easy", spec)
            f, _ = _run_device(jb, policy, "easy", base)
            diffs += s != f
    assert diffs > 0, "weight skew never changed any decision"


def test_reaping_matches_oracle_and_charges_owner():
    spec = TenantSpec(weights=(1.0, 1.0), grace=3)
    jobs = _workload(200, seed=5, n_tenants=2)
    trace, out = _run_device(jobs, Policy.FF, "easy", spec)
    orc = TenantOracle(N_PE, Policy.FF, "easy", spec,
                       park_capacity=8)
    ref = [orc.admit(r) for r in jobs]
    assert trace == ref
    horizon = max(j.t_a for j in jobs) + 6000
    out = batch_lib.reap_until(out, horizon, 3)
    n = orc.reap(horizon)
    assert n > 0
    assert _records(out) == orc.records()
    t, a = out.tenants, orc.accounts
    np.testing.assert_array_equal(np.asarray(t.n_reaped), a.n_reaped)
    np.testing.assert_array_equal(np.asarray(t.live), a.live)
    assert int(np.asarray(t.n_reaped).sum()) == n


def test_session_tick_reaps_overdue_reservations():
    spec = TenantSpec(weights=(1.0,), grace=5)
    sess = ReservationService(ServiceConfig(
        n_pe=8, capacity=32, chunk_size=4, ring_capacity=8,
        auto_release=False, tenants=spec)).session()
    r = ARRequest(t_a=0, t_r=0, t_du=10, t_dl=20, n_pe=4, tenant=0)
    assert bool(np.asarray(sess.offer([r]).decision.accepted)[0])
    assert sess.metrics(tenant=0)["live"] == 1
    assert sess.tick(14) == 0          # t_e + grace = 15 not yet due
    assert sess.tick(15) == 1
    m = sess.metrics(tenant=0)
    assert m["live"] == 0 and m["n_reaped"] == 1
    assert sess.metrics()["reaped"] == 1


def test_ensemble_lane_tables_and_reaping():
    spec0 = TenantSpec(weights=(1.0, 1.0), grace=4)
    spec1 = TenantSpec(weights=(1.0,))          # no grace: never reaps
    sess = ReservationService(ServiceConfig(
        n_pe=8, lanes=2, capacity=32, chunk_size=4, ring_capacity=8,
        auto_release=False, tenants=(spec0, spec1))).session()
    r0 = ARRequest(t_a=0, t_r=0, t_du=6, t_dl=20, n_pe=4, tenant=1)
    r1 = ARRequest(t_a=0, t_r=0, t_du=6, t_dl=20, n_pe=4, tenant=0)
    sess.offer([[r0], [r1]])
    m = sess.metrics()
    assert m["tenants"]["live"].tolist() == [[0, 1], [1, 0]]
    assert sess.tick(9) == 0
    assert sess.tick(10) == 1          # lane 0 reaps at t_e+4
    m = sess.metrics()
    assert m["tenants"]["live"].tolist() == [[0, 0], [1, 0]]
    assert m["tenants"]["n_reaped"].tolist() == [[0, 1], [0, 0]]


# ---------------------------------------------------------------------------
# telemetry: tenant views and the idle-poll fast path
# ---------------------------------------------------------------------------


def test_metrics_tenant_view_and_errors():
    spec = TenantSpec(weights=(1.0, 2.0), quotas=(100.0, None))
    sess = ReservationService(ServiceConfig(
        n_pe=8, capacity=32, chunk_size=4, ring_capacity=8,
        tenants=spec)).session()
    reqs = [ARRequest(t_a=i, t_r=i, t_du=20, t_dl=i + 40, n_pe=2,
                      tenant=i % 2) for i in range(6)]
    sess.offer(reqs)
    v0 = sess.metrics(tenant=0)
    assert v0["tenant"] == 0 and v0["weight"] == 1.0
    assert v0["live"] + sess.metrics(tenant=1)["live"] \
        == int(sess.metrics()["tenants"]["live"].sum())
    with pytest.raises(ValueError, match="out of range"):
        sess.metrics(tenant=2)
    plain = ReservationService(ServiceConfig(
        n_pe=8, chunk_size=4, ring_capacity=8)).session()
    with pytest.raises(ValueError, match="multi-tenant"):
        plain.metrics(tenant=0)
    with pytest.raises(ValueError, match="out of range"):
        sess.offer([ARRequest(t_a=9, t_r=9, t_du=5, t_dl=30, n_pe=1,
                              tenant=7)])


def test_idle_metrics_performs_zero_device_fetches(monkeypatch):
    """Satellite gate: polling an idle session costs no device sync.
    Every device->host metric transfer goes through the
    ``service._device_fetch`` choke point; count its calls."""
    from repro.api import service as service_mod

    calls = {"n": 0}
    real = service_mod._device_fetch

    def counting(tree):
        calls["n"] += 1
        return real(tree)

    monkeypatch.setattr(service_mod, "_device_fetch", counting)
    for cfg in (ServiceConfig(n_pe=8, capacity=32, chunk_size=4,
                              ring_capacity=8,
                              tenants=TenantSpec(weights=(1.0, 1.0))),
                ServiceConfig(n_pe=8, capacity=32, chunk_size=4,
                              ring_capacity=8),
                ServiceConfig(n_pe=8, lanes=2, capacity=32,
                              chunk_size=4, ring_capacity=8)):
        sess = ReservationService(cfg).session()
        reqs = [ARRequest(t_a=0, t_r=0, t_du=10, t_dl=30, n_pe=2)]
        sess.offer(reqs if cfg.lanes == 1 else [reqs] * cfg.lanes)
        sess.metrics()                 # warms the snapshot cache
        calls["n"] = 0
        for _ in range(5):
            sess.metrics()             # idle polls
            if cfg.tenants is not None:
                sess.metrics(tenant=0)
        assert calls["n"] == 0, cfg
        # a new offer invalidates the cache: exactly one refresh fetch
        # (plus the pipelined drain's latch read)
        sess.offer(
            [ARRequest(t_a=5, t_r=5, t_du=10, t_dl=40, n_pe=2)]
            if cfg.lanes == 1 else
            [[ARRequest(t_a=5, t_r=5, t_du=10, t_dl=40, n_pe=2)]] * 2)
        calls["n"] = 0
        sess.metrics()
        after_offer = calls["n"]
        assert after_offer >= 1
        calls["n"] = 0
        sess.metrics()
        assert calls["n"] == 0, cfg


# ---------------------------------------------------------------------------
# state plumbing: growth, grids, partitions, config validation
# ---------------------------------------------------------------------------


def test_growth_preserves_tenant_accounting():
    spec = TenantSpec(weights=(1.0, 1.0), quotas=(None, None))
    jobs = _workload(400, seed=2, n_tenants=2)
    # tiny capacities force the grow-once protocol mid-stream
    trace_small, out_small = _run_device(jobs, Policy.FF, "easy",
                                         spec, capacity=8, pending=8)
    trace_big, out_big = _run_device(jobs, Policy.FF, "easy", spec,
                                     capacity=512, pending=512)
    assert trace_small == trace_big
    t0, t1 = out_small.tenants, out_big.tenants
    for f in ("used", "live", "n_accepted", "n_rejected", "acc_ewma",
              "slow_ewma"):
        np.testing.assert_array_equal(np.asarray(getattr(t0, f)),
                                      np.asarray(getattr(t1, f)), f)
    pend = np.asarray(out_small.tenants.pend_tenant)
    assert pend.shape[0] == int(out_small.pend_te.shape[0])
    assert ((pend >= -1) & (pend < 2)).all()


def test_simulate_grid_tenant_mix_axis():
    from repro.sim.sweep import GridSpec, simulate_grid

    spec = GridSpec(
        policies=(Policy.FF, Policy.PE_B),
        arrival_factors=(1.0,), seeds=(0,), flex_factors=(3.0,),
        backfill_modes=("none", "easy"),
        tenant_mixes=(None, TenantSpec(weights=(1.0, 3.0),
                                       quotas=(4000.0, None))),
        n_pe=64, n_jobs=100)
    res = simulate_grid(spec, cross_check=True)
    assert res.acceptance.shape == (2, 2, 1, 1, 1, 2)
    assert (res.n_jobs > 0).all()
    legacy = simulate_grid(dataclasses.replace(
        spec, tenant_mixes=(None,)), cross_check=True)
    assert legacy.acceptance.shape == (2, 2, 1, 1, 1)
    np.testing.assert_array_equal(res.acceptance[..., 0],
                                  legacy.acceptance)
    # the quota-bound mix must actually bite somewhere
    assert (res.acceptance[..., 1] < res.acceptance[..., 0]).any()


def test_partition_sessions_gate_route_and_reap():
    spec = TenantSpec(weights=(1.0, 1.0), quotas=(40.0, None),
                      max_live=(None, 2), grace=2)
    sess = ReservationService(ServiceConfig(
        n_pe=8, n_partitions=2, auto_release=False, chunk_size=None,
        tenants=spec)).session()
    reqs = [ARRequest(t_a=i, t_r=i, t_du=10, t_dl=i + 30, n_pe=2,
                      tenant=i % 2) for i in range(8)]
    res = sess.offer(reqs)
    m = sess.metrics()
    snap = m["tenants"]
    assert snap["n_quota_rejected"].sum() > 0
    assert (snap["live"] <= np.asarray([100, 2])).all()
    assert m["ledger_depth"] == int(snap["live"].sum())
    live_before = int(snap["live"].sum())
    reaped = sess.tick(200)
    assert reaped == live_before
    snap = sess.metrics()["tenants"]
    assert int(snap["live"].sum()) == 0
    assert int(snap["n_reaped"].sum()) == reaped
    with pytest.raises(ValueError, match="out of range"):
        sess.offer([ARRequest(t_a=99, t_r=99, t_du=5, t_dl=200,
                              n_pe=1, tenant=5)])


def test_tenant_config_validation_errors():
    spec = TenantSpec(weights=(1.0, 1.0))
    with pytest.raises(ValueError, match="share one tenant spec"):
        ServiceConfig(n_pe=8, n_partitions=2, auto_release=False,
                      chunk_size=None, tenants=(spec, spec))
    with pytest.raises(ValueError, match="tenant specs for"):
        ServiceConfig(n_pe=8, lanes=3, chunk_size=4, ring_capacity=8,
                      tenants=(spec, spec))
    with pytest.raises(ValueError, match="TenantSpec or None"):
        ServiceConfig(n_pe=8, lanes=2, chunk_size=4, ring_capacity=8,
                      tenants=(spec, "notaspec"))
    with pytest.raises(ValueError, match="must be a TenantSpec"):
        ServiceConfig(n_pe=8, chunk_size=4, ring_capacity=8,
                      tenants="gold")
    with pytest.raises(ValueError, match="engine='device'"):
        ServiceConfig(n_pe=8, engine="host", tenants=spec)
    with pytest.raises(ValueError, match="pending-queue size"):
        ServiceConfig(n_pe=8, pending_capacity=4, chunk_size=4,
                      ring_capacity=8,
                      tenants=TenantSpec(weights=(1.0,) * 8))
    with pytest.raises(ValueError, match="over_quota"):
        TenantSpec(weights=(1.0,), over_quota="park")
    with pytest.raises(ValueError, match="weights"):
        TenantSpec(weights=())
    with pytest.raises(ValueError, match="quotas"):
        TenantSpec(weights=(1.0,), quotas=(1.0, 2.0))


def test_tenant_view_helper():
    spec = TenantSpec(weights=(1.0, 2.0))
    table = init_table(spec, 16, 4)
    snap = {f: np.asarray(getattr(table, f))
            for f in ("weight", "quota", "max_live", "used", "live",
                      "n_accepted", "n_rejected", "n_quota_rejected",
                      "n_parked", "n_reaped", "acc_ewma",
                      "slow_ewma")}
    snap["occ_ewma"] = np.float32(0.0)
    v = tenant_view(snap, 1)
    assert v["tenant"] == 1 and v["weight"] == 2.0
    with pytest.raises(ValueError, match="out of range"):
        tenant_view(snap, 2)
    stacked = stack_tables((spec, None), 16, 4)
    assert np.asarray(stacked.weight).shape == (2, 2)
