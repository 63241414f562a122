"""Mesh-sharded ensemble dispatch + donated state buffers (DESIGN.md §8).

Acceptance gates for the scale-out PR:

* the ``launch.mesh`` runtime seam (``make_lane_mesh`` /
  ``resolve_placement``) builds divisor meshes on whatever device
  count the host exposes, and ``ServiceConfig.placement`` validates;
* sharded sessions (``placement="auto"``/``"host"``) are decision-
  **bit-identical** to unsharded (``"single"``) sessions — chunked
  streaming, mid-stream growth, every backfill mode, and the whole
  ``simulate_grid`` matrix;
* donation: the steady-state chunk dispatch consumes its input
  buffers, never recompiles after warmup, and the grow-once /
  snapshot-restore / ``auto_grow=False`` contracts all survive it.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
CI ``test-mesh`` lane) these tests exercise real 8-way sharding; on a
single device the placement degrades to the host mesh with the same
code paths.  ``test_eight_way_subprocess`` forces the 8-device case
from any environment.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ReservationService, ServiceConfig
from repro.core import batch as batch_lib
from repro.core import ensemble as ens_lib
from repro.core import timeline as tl_lib
from repro.core.types import ALL_POLICIES, Policy
from repro.launch import mesh as mesh_lib
from repro.sharding import rules as shard_rules
from repro.sim import WorkloadParams, generate
from repro.sim.sweep import GridSpec, simulate_grid

SMALL_SIZES = dict(u_low=2.0, u_med=4.0, u_hi=6.0)


def _workload(n_jobs, n_pe, seed=7):
    jobs = [j for j in generate(WorkloadParams(
        n_jobs=n_jobs, n_pe=n_pe, seed=seed, **SMALL_SIZES))
        if j.n_pe <= n_pe]
    return sorted(jobs, key=lambda j: j.t_a)


def _lane_streams(n_lanes, n_jobs, n_pe):
    return [_workload(n_jobs, n_pe, seed=11 + e)
            for e in range(n_lanes)]


def _decision_tuple(res):
    return (np.asarray(res.decision.accepted),
            np.asarray(res.decision.t_s),
            np.asarray(res.decision.pe_mask),
            np.asarray(res.valid))


def _assert_same_decisions(a, b):
    for x, y in zip(_decision_tuple(a), _decision_tuple(b)):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# the mesh seam: helpers + config validation
# ---------------------------------------------------------------------------


def test_mesh_helpers():
    host = mesh_lib.make_host_mesh()
    assert mesh_lib.data_shards(host) == 1
    assert host.shape["model"] == 1

    n_dev = len(jax.devices())
    for lanes in (1, 6, 7, 63, 504):
        mesh = mesh_lib.make_lane_mesh(lanes)
        d = mesh_lib.data_shards(mesh)
        assert lanes % d == 0, (lanes, d)
        assert d <= n_dev
        # largest divisor: no k in (d, n_dev] divides lanes
        assert all(lanes % k for k in range(d + 1, n_dev + 1))
    capped = mesh_lib.make_lane_mesh(504, max_shards=2)
    assert mesh_lib.data_shards(capped) == 2 if n_dev >= 2 else 1
    with pytest.raises(ValueError):
        mesh_lib.make_lane_mesh(0)


def test_resolve_placement():
    assert mesh_lib.resolve_placement(None, 8) is None
    assert mesh_lib.resolve_placement("single", 8) is None
    host = mesh_lib.resolve_placement("host", 8)
    assert mesh_lib.data_shards(host) == 1
    auto = mesh_lib.resolve_placement("auto", 8)
    assert 8 % mesh_lib.data_shards(auto) == 0
    one = mesh_lib.resolve_placement(1, 8)
    assert mesh_lib.data_shards(one) == 1
    with pytest.raises(ValueError):
        mesh_lib.resolve_placement("cluster", 8)


def test_production_mesh_helpers_still_build():
    # the dry-run seam must not regress while the runtime reuses it
    if len(jax.devices()) < 256:
        with pytest.raises(ValueError):
            mesh_lib.make_production_mesh()
        return
    mesh = mesh_lib.make_production_mesh()
    assert dict(mesh.shape) == {"data": 16, "model": 16}
    assert mesh_lib.data_shards(mesh) == 16


def test_placement_config_validation():
    ServiceConfig(n_pe=8, placement="auto")
    ServiceConfig(n_pe=8, placement=None, donate=False)
    ServiceConfig(n_pe=8, placement=4)
    for bad in ("cluster", 0, -2, True, 1.5):
        with pytest.raises((ValueError, TypeError)):
            ServiceConfig(n_pe=8, placement=bad)


def test_lane_spec_and_shard_ensemble():
    mesh = mesh_lib.make_lane_mesh(len(jax.devices()))
    states = ens_lib.init_ensemble(len(jax.devices()) or 1, 16, 8, 16)
    sharded = shard_rules.shard_ensemble(mesh, states)
    # lane axis sharded over data, payload axes replicated
    sh = sharded.tl.times.sharding
    # a one-axis entry may be spelled "data" or ("data",): same meaning
    assert sh.spec[0] in ("data", ("data",), ("pod", "data"), None)
    assert all(ax is None for ax in sh.spec[1:])
    np.testing.assert_array_equal(np.asarray(sharded.tl.times),
                                  np.asarray(states.tl.times))
    # mesh=None is the identity
    assert shard_rules.shard_ensemble(None, states) is states


# ---------------------------------------------------------------------------
# sharded == unsharded, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backfill", ["none", "easy", "conservative"])
def test_sharded_ensemble_identical_to_single(backfill):
    """Chunked ensemble streaming under placement="auto" reproduces
    the unsharded session bit-for-bit, including a mid-stream
    collective growth (tiny initial capacity)."""
    n_pe, lanes = 16, 6
    streams = _lane_streams(lanes, 120, n_pe)
    policies = [ALL_POLICIES[e % len(ALL_POLICIES)]
                for e in range(lanes)]
    results = {}
    for placement in ("single", "auto"):
        sess = ReservationService(ServiceConfig(
            n_pe=n_pe, lanes=lanes, capacity=4, pending_capacity=4,
            chunk_size=16, ring_capacity=64, backfill=backfill,
            placement=placement)).session()
        res = sess.offer(streams, policy=policies)
        results[placement] = (res, sess.metrics())
    _assert_same_decisions(results["single"][0], results["auto"][0])
    m_single, m_auto = results["single"][1], results["auto"][1]
    assert m_auto["growths"] >= 1          # capacity=8 must grow
    for key in ("offered", "accepted", "chunks", "growths"):
        assert m_single[key] == m_auto[key], key
    assert m_auto["placement_shards"] == \
        max(k for k in range(1, len(jax.devices()) + 1)
            if lanes % k == 0)


def test_sharded_donation_off_identical():
    """placement and donation are independent axes: all four
    combinations decide identically."""
    n_pe, lanes = 16, 4
    streams = _lane_streams(lanes, 80, n_pe)
    ref = None
    for placement in ("single", "auto"):
        for donate in (False, True):
            sess = ReservationService(ServiceConfig(
                n_pe=n_pe, lanes=lanes, capacity=32,
                pending_capacity=32, chunk_size=16, ring_capacity=64,
                placement=placement, donate=donate)).session()
            res = sess.offer(streams)
            if ref is None:
                ref = res
            else:
                _assert_same_decisions(ref, res)


def test_simulate_grid_sharded_equals_single():
    spec = GridSpec(n_jobs=60, n_pe=16, seeds=(0, 1),
                    arrival_factors=(1.0,), flex_factors=(0.5,),
                    policies=(Policy.FF, Policy.DU_B),
                    backfill_modes=("none", "easy"))
    single = simulate_grid(spec, capacity=32, placement="single",
                           donate=False, record_decisions=True)
    sharded = simulate_grid(spec, capacity=32, placement="auto",
                            record_decisions=True)
    np.testing.assert_array_equal(single.acceptance, sharded.acceptance)
    np.testing.assert_array_equal(single.n_accepted, sharded.n_accepted)
    assert single.decisions == sharded.decisions


# ---------------------------------------------------------------------------
# donation: allocation-free steady state, contracts preserved
# ---------------------------------------------------------------------------


def test_donated_stream_consumes_input_and_matches():
    n_pe = 16
    jobs = _workload(64, n_pe)
    batch = batch_lib.requests_to_batch(jobs)
    state_a = tl_lib.init_state(64, n_pe, 64)
    state_b = tl_lib.init_state(64, n_pe, 64)
    out_a, dec_a = batch_lib.admit_stream(
        state_a, batch, jnp.int32(0), n_pe=n_pe)
    out_b, dec_b = batch_lib.admit_stream_donated(
        state_b, batch, jnp.int32(0), n_pe=n_pe)
    np.testing.assert_array_equal(np.asarray(dec_a.accepted),
                                  np.asarray(dec_b.accepted))
    np.testing.assert_array_equal(np.asarray(dec_a.t_s),
                                  np.asarray(dec_b.t_s))
    np.testing.assert_array_equal(np.asarray(out_a.tl.times),
                                  np.asarray(out_b.tl.times))
    assert state_b.tl.times.is_deleted()      # donated away
    assert not state_a.tl.times.is_deleted()  # non-donated untouched


def test_donated_chunk_cache_stable_after_warmup():
    """Steady-state streaming through the donated dispatch: zero
    recompiles after the first chunk."""
    n_pe = 16
    jobs = _workload(400, n_pe)
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=64, pending_capacity=64, chunk_size=32,
        ring_capacity=64)).session()
    warm = None
    i = 0
    while i < len(jobs):
        sess.offer(jobs[i:i + 50])
        i += 50
        if warm is None:
            warm = batch_lib.admit_stream_donated._cache_size()
    assert warm == batch_lib.admit_stream_donated._cache_size(), \
        "donated chunk dispatch recompiled after warmup"
    assert sess.metrics()["growths"] == 0


def test_donated_grow_rollback_equivalence():
    """Overflow under donation: grow_rollback re-materializes and the
    retry reproduces the never-overflowed decisions exactly."""
    n_pe = 16
    jobs = _workload(200, n_pe)
    batch = batch_lib.requests_to_batch(jobs)
    big, dec_big = batch_lib.admit_stream_grow(
        tl_lib.init_state(256, n_pe, 256), batch, Policy.FF,
        n_pe=n_pe)
    small, dec_small = batch_lib.admit_stream_grow(
        tl_lib.init_state(4, n_pe, 4), batch, Policy.FF,
        n_pe=n_pe, donate=True)
    np.testing.assert_array_equal(np.asarray(dec_big.accepted),
                                  np.asarray(dec_small.accepted))
    np.testing.assert_array_equal(np.asarray(dec_big.t_s),
                                  np.asarray(dec_small.t_s))
    assert int(small.n_accepted) == int(big.n_accepted)


def test_growth_mid_stream_donated_session():
    """A chunked session starting at capacity 4 equals a session that
    started big — the pipelined deferred-overflow replay path."""
    n_pe = 32
    jobs = _workload(300, n_pe, seed=3)
    res, metrics = {}, {}
    for cap in (4, 256):
        sess = ReservationService(ServiceConfig(
            n_pe=n_pe, capacity=cap, pending_capacity=max(cap, 8),
            chunk_size=32, ring_capacity=64)).session()
        out = []
        for i in range(0, len(jobs), 70):
            out.append(sess.offer(jobs[i:i + 70]))
        acc = np.concatenate(
            [np.asarray(r.decision.accepted)[np.asarray(r.valid)]
             for r in out])
        ts = np.concatenate(
            [np.asarray(r.decision.t_s)[np.asarray(r.valid)]
             for r in out])
        res[cap] = (acc, ts)
        metrics[cap] = sess.metrics()
    np.testing.assert_array_equal(res[4][0], res[256][0])
    np.testing.assert_array_equal(res[4][1], res[256][1])
    assert metrics[4]["growths"] >= 1
    assert metrics[4]["accepted"] == metrics[256]["accepted"]


def test_snapshot_restore_with_donation():
    """A snapshot pins the buffers (donation pauses), restore rewinds,
    and the replayed traffic decides identically."""
    n_pe = 16
    jobs = _workload(200, n_pe, seed=5)
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=64, pending_capacity=64, chunk_size=16,
        ring_capacity=64)).session()
    sess.offer(jobs[:100])
    snap = sess.snapshot()
    res_1 = sess.offer(jobs[100:])
    m_1 = sess.metrics()
    sess.restore(snap)
    res_2 = sess.offer(jobs[100:])
    _assert_same_decisions(res_1, res_2)
    assert sess.metrics() == m_1
    # the snapshot's state arrays must have survived both replays
    state, _ = snap[0]
    assert not state.tl.times.is_deleted()


def test_auto_grow_false_with_donation_stays_usable():
    """auto_grow=False: the first overflow raises, the session state
    is rolled back (donation reinstalls it) and admission continues."""
    n_pe = 16
    jobs = _workload(300, n_pe, seed=9)
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=4, pending_capacity=4, chunk_size=16,
        ring_capacity=512, auto_grow=False)).session()
    with pytest.raises(batch_lib.GrowthError):
        sess.offer(jobs)
    m = sess.metrics()
    assert m["growths"] == 0
    assert m["capacity"] == 4                 # rolled back, not grown
    # the overflowing chunk's requests went back to the staging ring
    assert m["ring_staged"] > 0


def test_one_shot_donated_offer_result_usable():
    """The one-shot (chunk_size=None) path donates too; the returned
    decision arrays must be fresh buffers, not aliases of the state."""
    n_pe = 16
    jobs = _workload(50, n_pe)
    sess = ReservationService(ServiceConfig(
        n_pe=n_pe, capacity=64, chunk_size=None)).session()
    r1 = sess.offer(jobs[:25])
    r2 = sess.offer(jobs[25:])
    assert int(np.asarray(r1.decision.accepted).sum()) > 0
    assert int(np.asarray(r2.decision.accepted).sum()) > 0
    assert sess.metrics()["accepted"] == r1.n_accepted + r2.n_accepted


# ---------------------------------------------------------------------------
# the big differential + the forced-8-device run
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("backfill", ["none", "easy", "conservative"])
def test_sharded_differential_500_jobs_all_policies(backfill):
    """>= 500 jobs x 7 policies x backfill mode: sharded chunked
    streaming == unsharded one-shot, bit for bit (the ISSUE gate)."""
    n_pe = 32
    lanes = len(ALL_POLICIES)
    stream = _workload(560, n_pe, seed=21)
    assert len(stream) >= 500
    stream = stream[:500]
    streams = [list(stream) for _ in range(lanes)]
    sharded = ReservationService(ServiceConfig(
        n_pe=n_pe, lanes=lanes, capacity=64, pending_capacity=64,
        chunk_size=64, ring_capacity=128, backfill=backfill,
        placement="auto")).session()
    res = sharded.offer(streams, policy=list(ALL_POLICIES))
    acc = np.asarray(res.decision.accepted)
    ts = np.asarray(res.decision.t_s)
    valid = np.asarray(res.valid)
    for lane, policy in enumerate(ALL_POLICIES):
        single = ReservationService(ServiceConfig(
            n_pe=n_pe, policy=policy, capacity=64,
            pending_capacity=64, chunk_size=None, backfill=backfill,
            placement="single", donate=False)).session()
        ref = single.offer(stream)
        v = valid[lane]
        np.testing.assert_array_equal(
            acc[lane][v], np.asarray(ref.decision.accepted))
        np.testing.assert_array_equal(
            ts[lane][v], np.asarray(ref.decision.t_s))


@pytest.mark.slow
def test_eight_way_subprocess():
    """Force 8 host devices in a subprocess and check a sharded grid
    both shards 8 ways and matches the unsharded decisions."""
    code = """
import os
import numpy as np
from repro.api import ReservationService, ServiceConfig
from repro.sim.sweep import GridSpec, simulate_grid
from repro.core.types import Policy
import jax
assert jax.device_count() == 8, jax.devices()
spec = GridSpec(n_jobs=40, n_pe=16, seeds=(0, 1, 2, 3),
                arrival_factors=(1.0,), flex_factors=(0.5,),
                policies=(Policy.FF, Policy.DU_B),
                backfill_modes=("none",))
single = simulate_grid(spec, capacity=32, placement="single",
                       donate=False, record_decisions=True)
sharded = simulate_grid(spec, capacity=32, placement="auto",
                        record_decisions=True)
np.testing.assert_array_equal(single.acceptance, sharded.acceptance)
assert single.decisions == sharded.decisions
sess = ReservationService(ServiceConfig(
    n_pe=16, lanes=8, capacity=32, chunk_size=8,
    ring_capacity=32)).session()
assert sess.metrics()["placement_shards"] == 8
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
