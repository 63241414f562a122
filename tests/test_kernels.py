"""Pallas availscan kernel: shape/dtype sweeps vs the pure-jnp oracle.

The kernel is integer/boolean-exact, so assertions are equality, not
allclose (n_free counts are exact small-int f32 sums).
"""
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import policies as policies_lib
from repro.core import search as search_lib
from repro.core import timeline as tl_lib
from repro.core.types import ARRequest, T_INF
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref


def _random_timeline(rng, n_pe, capacity, n_jobs):
    tl = tl_lib.empty(capacity, n_pe)
    t = 0
    for _ in range(n_jobs):
        t_s = t + int(rng.integers(0, 10))
        t_e = t_s + int(rng.integers(1, 30))
        ids = rng.choice(n_pe, size=int(rng.integers(1, n_pe // 2 + 1)),
                         replace=False)
        bits = np.zeros(tl.words * 32, np.uint32)
        bits[ids] = 1
        mask = tl_lib.pack_bits(bits[None, :])[0]
        tl, overflow = tl_lib.update(tl, t_s, t_e, mask, is_add=True)
        assert not bool(overflow)
        t = t_s
    return tl


@pytest.mark.parametrize("n_pe", [8, 40, 100, 128, 200])
@pytest.mark.parametrize("capacity", [32, 64])
def test_kernel_matches_ref_sweep(n_pe, capacity):
    rng = np.random.default_rng(n_pe * 1000 + capacity)
    tl = _random_timeline(rng, n_pe, capacity, n_jobs=10)
    t_du = jnp.int32(7)
    t_now = jnp.int32(0)
    starts = search_lib.candidate_starts(
        tl, jnp.int32(2), t_du, jnp.int32(90))
    ref = kernel_ref.availability_rectangles(tl, starts, t_du, t_now,
                                             n_pe)
    got = kernel_ops.availability_rectangles(tl, starts, t_du, t_now,
                                             n_pe)
    np.testing.assert_array_equal(np.asarray(got.n_free),
                                  np.asarray(ref.n_free))
    np.testing.assert_array_equal(np.asarray(got.t_begin),
                                  np.asarray(ref.t_begin))
    np.testing.assert_array_equal(np.asarray(got.t_end),
                                  np.asarray(ref.t_end))
    np.testing.assert_array_equal(np.asarray(got.valid),
                                  np.asarray(ref.valid))


@pytest.mark.parametrize("duration", [1, 13, 64])
def test_kernel_durations(duration):
    rng = np.random.default_rng(duration)
    n_pe = 64
    tl = _random_timeline(rng, n_pe, 32, n_jobs=8)
    t_du = jnp.int32(duration)
    starts = search_lib.candidate_starts(
        tl, jnp.int32(0), t_du, jnp.int32(200))
    ref = kernel_ref.availability_rectangles(
        tl, starts, t_du, jnp.int32(0), n_pe)
    got = kernel_ops.availability_rectangles(
        tl, starts, t_du, jnp.int32(0), n_pe)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_empty_timeline():
    n_pe = 32
    tl = tl_lib.empty(16, n_pe)
    starts = jnp.array([0, 5, T_INF], jnp.int32)
    got = kernel_ops.availability_rectangles(
        tl, starts, jnp.int32(4), jnp.int32(0), n_pe)
    assert int(got.n_free[0]) == n_pe
    assert int(got.t_end[0]) == T_INF
    assert not bool(got.valid[2])


def test_kernel_fallback_on_large_shapes(monkeypatch):
    """Beyond the VMEM budget the wrapper must fall back to the ref."""
    monkeypatch.setattr(kernel_ops, "_MAX_OCC_ELEMS", 16)
    rng = np.random.default_rng(0)
    tl = _random_timeline(rng, 64, 32, n_jobs=4)
    starts = search_lib.candidate_starts(
        tl, jnp.int32(0), jnp.int32(5), jnp.int32(60))
    got = kernel_ops.availability_rectangles(
        tl, starts, jnp.int32(5), jnp.int32(0), 64)
    ref = kernel_ref.availability_rectangles(
        tl, starts, jnp.int32(5), jnp.int32(0), 64)
    np.testing.assert_array_equal(np.asarray(got.n_free),
                                  np.asarray(ref.n_free))


def test_full_find_allocation_with_kernel():
    """End-to-end jitted find_allocation, kernel vs jnp paths."""
    from repro.core.scheduler import DeviceScheduler
    from repro.core.types import ALL_POLICIES, ARRequest
    import random
    random.seed(3)
    a = DeviceScheduler(48, capacity=32, use_kernel=False)
    b = DeviceScheduler(48, capacity=32, use_kernel=True)
    t = 0
    for step in range(60):
        t += random.randint(0, 3)
        du = random.randint(1, 15)
        req = ARRequest(t_a=t, t_r=t + random.randint(0, 5), t_du=du,
                        t_dl=t + du + random.randint(5, 30),
                        n_pe=random.randint(1, 48))
        pol = random.choice(list(ALL_POLICIES))
        ra = a.find_allocation(req, pol, t_now=t)
        rb = b.find_allocation(req, pol, t_now=t)
        assert (ra is None) == (rb is None)
        if ra:
            assert (ra.t_s, ra.pe_ids, ra.rectangle) == \
                (rb.t_s, rb.pe_ids, rb.rectangle)
            a.add_allocation(ra.t_s, ra.t_e, list(ra.pe_ids))
            b.add_allocation(ra.t_s, ra.t_e, list(ra.pe_ids))


@pytest.mark.parametrize("cfg, path", [
    (dict(n_pe=64, use_kernel=True), "kernel"),
    (dict(n_pe=64), "jnp"),
    # 4096 records x 1024 PEs is past the kernel's single-block budget
    (dict(n_pe=1024, capacity=4096, use_kernel=True), "jnp"),
    (dict(n_pe=64, lanes=2, use_kernel=True), "kernel"),
    (dict(n_pe=64, n_partitions=2, use_kernel=True), "kernel"),
    # the CEA Curie thin-node partition: twenty PE blocks of 4096 PEs
    (dict(n_pe=80640, capacity=128, pending_capacity=256, index_tile=16,
          use_kernel=True), "kernel"),
], ids=["kernel", "jnp", "over-budget", "lanes", "partitions", "curie"])
def test_session_reports_search_path(cfg, path):
    from repro.api import ReservationService, ServiceConfig
    sess = ReservationService(ServiceConfig(**cfg)).session()
    assert sess.metrics()["search_path"] == path


@pytest.mark.parametrize("platform, interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_by_platform(platform, interpret, monkeypatch):
    from repro.kernels import availscan as _k
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(NotImplementedError, match="gpu"):
            _k._interpret_mode()
    else:
        assert _k._interpret_mode() is interpret


# ---------------------------------------------------------------------------
# the PE-block grid: a machine wider than one block
# ---------------------------------------------------------------------------

BLOCKED_PE = 640          # 20 words: three blocks of 8 words, one partial


@pytest.fixture
def small_pe_blocks(monkeypatch):
    """Blocks of 8 words (256 PEs), so that a small machine spans
    several; traces made with the default block are dropped."""
    from repro.kernels import availscan as _k
    monkeypatch.setattr(_k, "BLOCK_WORDS", 8)
    jax.clear_caches()
    assert _k.pe_blocks(tl_lib.n_words(BLOCKED_PE)) == (8, 3)
    yield
    jax.clear_caches()


def _jnp_chain(tl, starts, t_du, t_now, n_req, policy_id, n_pe):
    rects = search_lib.availability_rectangles(tl, starts, t_du, t_now,
                                               n_pe)
    feasible = rects.valid & (rects.n_free >= n_req)
    best, found = policies_lib.select(
        policy_id, rects.n_free, rects.t_end - rects.t_begin,
        rects.starts, feasible)
    return rects, dict(found=found, best=best, n_free=rects.n_free[best],
                       t_begin=rects.t_begin[best],
                       t_end=rects.t_end[best])


@pytest.mark.parametrize("tiles", ["live", "pruned", "dead"])
@pytest.mark.parametrize("seed", [0, 1])
def test_pe_blocks_match_jnp_chain(tiles, seed, small_pe_blocks):
    """Three candidate tiles over three PE blocks against the jnp
    chain, element for element: every tile live, random pruned holes,
    and a middle tile with no live candidate."""
    rng = np.random.default_rng(seed)
    tl = _random_timeline(rng, BLOCKED_PE, 128, n_jobs=40)
    P = 2 * 128 + 2
    starts = np.sort(rng.integers(0, 400, P)).astype(np.int32)
    pruned = rng.random(P) < {"live": 0.0, "pruned": 0.4, "dead": 0.3}[
        tiles]
    if tiles == "dead":
        pruned[128:256] = True
    pruned[[0, -1]] = False        # tiles 0 and 2 keep a live start
    starts = jnp.asarray(np.where(pruned, T_INF, starts))
    t_du, t_now = jnp.int32(11), jnp.int32(3)
    rects, _ = _jnp_chain(tl, starts, t_du, t_now, 0, 0, BLOCKED_PE)
    got = kernel_ops.availability_rectangles(tl, starts, t_du, t_now,
                                             BLOCKED_PE)
    for a, b in zip(got[:5], rects[:5]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for policy_id in range(7):
        for n_req in (1, 200, 500):
            sel = kernel_ops.search_select(
                tl, starts, t_du, t_now, jnp.int32(n_req),
                jnp.int32(policy_id), BLOCKED_PE)
            _, want = _jnp_chain(tl, starts, t_du, t_now, n_req,
                                 jnp.int32(policy_id), BLOCKED_PE)
            assert {k: int(sel[k]) for k in want} == {
                k: int(v) for k, v in want.items()}, (policy_id, n_req)
            run = 2 if tiles == "dead" else 3
            assert (int(sel["tiles"]), int(sel["tiles_run"]),
                    int(sel["pe_blocks"])) == (3, run, 3 * run)


def _blocked_stream(n_jobs, seed):
    rng = random.Random(seed)
    jobs, t = [], 0
    for _ in range(n_jobs):
        t += rng.randint(0, 4)
        du = rng.randint(5, 40)
        t_r = t + rng.randint(0, 10)
        jobs.append(ARRequest(t_a=t, t_r=t_r, t_du=du,
                              t_dl=t_r + du + rng.randint(0, 60),
                              n_pe=rng.randint(1, BLOCKED_PE)))
    return jobs


def test_session_on_pe_blocks_matches_host(small_pe_blocks):
    from repro.api import ReservationService, ServiceConfig
    from repro.core.types import Policy
    from repro.sim import simulate
    jobs = _blocked_stream(48, seed=5)
    want = simulate(jobs, BLOCKED_PE, Policy.FF, engine="host",
                    record_decisions=True).decisions
    sess = ReservationService(ServiceConfig(
        n_pe=BLOCKED_PE, policy=Policy.FF, use_kernel=True, capacity=64,
        index_tile=8, chunk_size=16, ring_capacity=32)).session()
    res = sess.offer(jobs)
    valid = np.asarray(res.valid)
    acc = np.asarray(res.decision.accepted)[valid]
    t_s = np.asarray(res.decision.t_s)[valid]
    assert [(bool(a), int(t) if a else -1) for a, t in zip(acc, t_s)] \
        == [(a, t) for a, t in want]
    assert 0 < acc.sum() < len(jobs)
    m = sess.metrics()
    assert m["search_path"] == "kernel" and m["growths"] == 0
    assert m["search_pe_blocks"] == 3 * (
        m["search_tiles"] - m["search_tiles_skipped"]) > 0


def test_curie_shape_takes_the_kernel():
    """80,640 PEs at capacity 128: twenty blocks of 4096 PEs, each
    within the per-block budget; the whole width in one block is not."""
    from repro.kernels import availscan as _k
    words = tl_lib.n_words(80640)
    assert _k.pe_blocks(words) == (128, 20)
    assert kernel_ops.fits(128, 80640)
    assert 128 * words * 32 > kernel_ops._MAX_OCC_ELEMS
    assert not kernel_ops.fits(1024, 80640)
