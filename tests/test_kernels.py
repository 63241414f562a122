"""Pallas availscan kernel: shape/dtype sweeps vs the pure-jnp oracle.

The kernel is integer/boolean-exact, so assertions are equality, not
allclose (n_free counts are exact small-int f32 sums).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import search as search_lib
from repro.core import timeline as tl_lib
from repro.core.types import T_INF
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
], ids=["kernel", "jnp", "over-budget", "lanes", "partitions"])
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
