"""The multi-tenant admission tables (DESIGN.md §10).

The paper's scheduler admits anonymous requests; the production
service (ROADMAP "multi-tenant service hardening") attributes every
request to a *tenant* and enforces per-tenant policy at admission:

``TenantSpec``
    The host-side configuration: per-tenant fair-share weights,
    PE-seconds quotas, concurrent-reservation caps, the overdue
    grace window, and the telemetry EWMA coefficient.  Frozen and
    validated once by ``ServiceConfig``.
``TenantTable``
    The device-resident state: a pytree of ``[T]`` per-tenant
    accumulators plus per-slot ownership columns for the pending
    buffer and the deferral queue.  The tenant axis ``T`` is a
    *static* shape; every weight/quota/cap is a **traced leaf**, so
    reconfiguring tenants never recompiles — exactly like the traced
    policy and backfill ids of the fused admit step.
``HostTenantAccounts``
    The numpy mirror used by the differential ``TenantOracle`` and
    the host-routed partition gate.  Usage accounting is float32 on
    both sides with identical expression shapes; the telemetry EWMAs
    are int32 fixed point (:data:`EWMA_ONE`), whose integer rounding
    is the same on every backend.  So the device table and the host
    mirror agree **bit-for-bit** (the same contract the PR 4 backfill
    oracle established for decisions).

The table hangs off ``SchedulerState.tenants`` as an *optional*
trailing field: ``None`` contributes no pytree leaves, so zero-tenant
sessions compile the byte-identical graphs they had before tenancy
existed.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

#: int32 "+infinity" for unlimited concurrent-reservation caps.
_I32_MAX = 2**31 - 1

#: Fixed-point scale of the telemetry EWMAs: int32 in units of
#: ``1 / EWMA_ONE``.  Float EWMAs cannot match across backends (XLA may
#: or may not fuse ``e*(1-a) + x*a`` into an FMA, and a TPU divides
#: with a reciprocal); integer arithmetic rounds the same everywhere.
EWMA_BITS = 16
EWMA_ONE = 1 << EWMA_BITS

#: Supported over-quota dispositions.  ``"park"`` (defer instead of
#: reject) is reserved for a later PR: parking an over-quota request
#: would hold a reservation mark for work the tenant may never be
#: allowed to run.
OVER_QUOTA_MODES = ("reject",)


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """Host-side tenant configuration (``ServiceConfig.tenants``).

    ``weights``
        one positive fair-share weight per tenant; the tuple length
        *is* the tenant count.  Equal weights make the fair-share
        ranking provably bit-identical to FCFS (DESIGN.md §10).
    ``quotas``
        per-tenant lifetime PE-seconds budgets (``None`` entries are
        unlimited); an admission that would exceed the budget is
        rejected *before* search.
    ``max_live``
        per-tenant concurrent-reservation caps (``None`` = unlimited).
    ``over_quota``
        disposition of gated requests; only ``"reject"`` today.
    ``grace``
        overdue-reservation grace window: on ``Session.tick(t)`` a
        reservation still held past ``t_e + grace`` is reaped
        (batch-deleted, charged to its tenant).  ``None`` disables
        reaping.
    ``ewma_alpha``
        coefficient of the telemetry EWMAs (acceptance, slowdown,
        occupancy), applied in steps of ``1 / EWMA_ONE``.
    """

    weights: Tuple[float, ...] = (1.0,)
    quotas: Optional[Tuple[Optional[float], ...]] = None
    max_live: Optional[Tuple[Optional[int], ...]] = None
    over_quota: str = "reject"
    grace: Optional[int] = None
    ewma_alpha: float = 0.05

    def __post_init__(self):
        if not self.weights:
            raise ValueError("TenantSpec needs at least one tenant "
                             "(weights is empty)")
        ws = tuple(float(w) for w in self.weights)
        object.__setattr__(self, "weights", ws)
        if any(not np.isfinite(w) or w <= 0 for w in ws):
            raise ValueError(
                f"tenant weights must be positive and finite, got "
                f"{self.weights}")
        for name in ("quotas", "max_live"):
            vals = getattr(self, name)
            if vals is None:
                continue
            vals = tuple(vals)
            object.__setattr__(self, name, vals)
            if len(vals) != len(ws):
                raise ValueError(
                    f"{len(vals)} {name} entries for "
                    f"{len(ws)} tenants")
            if any(v is not None and v <= 0 for v in vals):
                raise ValueError(
                    f"{name} entries must be positive (or None for "
                    f"unlimited), got {vals}")
        if self.over_quota not in OVER_QUOTA_MODES:
            raise ValueError(
                f"unknown over_quota {self.over_quota!r}; supported: "
                f"{OVER_QUOTA_MODES} (over_quota='park' is not "
                f"implemented: parking an over-quota request would "
                f"reserve capacity the tenant may never get)")
        if self.grace is not None and self.grace < 0:
            raise ValueError(
                f"grace must be >= 0 (seconds past t_e), got "
                f"{self.grace}")
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError(
                f"ewma_alpha must be in (0, 1], got {self.ewma_alpha}")

    @property
    def n_tenants(self) -> int:
        return len(self.weights)

    def quota_array(self) -> np.ndarray:
        """float32[T] PE-seconds budgets; inf = unlimited."""
        if self.quotas is None:
            return np.full(self.n_tenants, np.inf, np.float32)
        return np.asarray(
            [np.inf if q is None else float(q) for q in self.quotas],
            np.float32)

    def max_live_array(self) -> np.ndarray:
        """int32[T] concurrent caps; INT32_MAX = unlimited."""
        if self.max_live is None:
            return np.full(self.n_tenants, _I32_MAX, np.int32)
        return np.asarray(
            [_I32_MAX if m is None else int(m) for m in self.max_live],
            np.int32)

    def alpha_q16(self) -> int:
        """``ewma_alpha`` in EWMA fixed point, in ``[1, EWMA_ONE]``."""
        return max(1, round(self.ewma_alpha * EWMA_ONE))

    def padded(self, n_tenants: int) -> "TenantSpec":
        """This spec widened to ``n_tenants`` with neutral tenants.

        The padding tenants (weight 1, unlimited) never receive
        requests; padding lets heterogeneous per-lane specs share one
        static tenant axis (the sweep's tenant-mix axis).
        """
        if n_tenants < self.n_tenants:
            raise ValueError(
                f"cannot pad {self.n_tenants} tenants down to "
                f"{n_tenants}")
        pad = n_tenants - self.n_tenants
        if pad == 0:
            return self
        return dataclasses.replace(
            self,
            weights=self.weights + (1.0,) * pad,
            quotas=None if self.quotas is None
            else self.quotas + (None,) * pad,
            max_live=None if self.max_live is None
            else self.max_live + (None,) * pad)


class TenantTable(NamedTuple):
    """Device-resident per-tenant state (a JAX pytree, DESIGN.md §10).

    Configuration leaves (traced — changing values never recompiles):
    ``weight``/``quota``/``max_live``/``alpha``.  Accounting leaves:
    ``used`` (lifetime PE-seconds admitted), ``live`` (currently held
    reservations), the lifetime counters, and the telemetry EWMAs.
    Ownership columns attribute every pending-buffer slot
    (``pend_tenant``) and deferral-queue slot (``park_tenant``, plus
    the arrival stamp ``park_ta`` that feeds the fair-share key) to a
    tenant; ``-1`` marks an unowned slot.
    """

    weight: jax.Array        # float32[T] fair-share weights
    quota: jax.Array         # float32[T] PE-seconds budget; inf = none
    max_live: jax.Array      # int32[T] concurrent cap; I32_MAX = none
    used: jax.Array          # float32[T] lifetime PE-seconds admitted
    live: jax.Array          # int32[T] currently held reservations
    n_accepted: jax.Array    # int32[T]
    n_rejected: jax.Array    # int32[T] (all rejections, incl. gated)
    n_quota_rejected: jax.Array  # int32[T] rejected by the quota gate
    n_parked: jax.Array      # int32[T] accepted into the deferral queue
    n_reaped: jax.Array      # int32[T] reservations reaped overdue
    # the EWMAs and alpha are int32 fixed point (units 1/EWMA_ONE)
    acc_ewma: jax.Array      # int32[T] per-tenant acceptance EWMA
    slow_ewma: jax.Array     # int32[T] per-tenant slowdown EWMA
    occ_ewma: jax.Array      # int32 scalar machine-occupancy EWMA
    alpha: jax.Array         # int32 scalar EWMA coefficient (traced)
    pend_tenant: jax.Array   # int32[K] pending-slot owner; -1 = free
    park_tenant: jax.Array   # int32[Q] queue-slot owner; -1 = free
    park_ta: jax.Array       # int32[Q] queue-slot arrival time

    @property
    def n_tenants(self) -> int:
        return self.weight.shape[-1]


def init_table(spec: TenantSpec, pending_capacity: int,
               park_capacity: int) -> TenantTable:
    """Fresh all-zero device table for one timeline's buffers."""
    T = spec.n_tenants
    # distinct buffers per leaf: aliased zeros would break jit
    # donation (XLA rejects donating one buffer twice)
    zi = lambda: jnp.zeros((T,), jnp.int32)
    zf = lambda: jnp.zeros((T,), jnp.float32)
    return TenantTable(
        weight=jnp.asarray(spec.weights, jnp.float32),
        quota=jnp.asarray(spec.quota_array()),
        max_live=jnp.asarray(spec.max_live_array()),
        used=zf(), live=zi(),
        n_accepted=zi(), n_rejected=zi(), n_quota_rejected=zi(),
        n_parked=zi(), n_reaped=zi(),
        acc_ewma=zi(), slow_ewma=zi(),
        occ_ewma=jnp.int32(0),
        alpha=jnp.int32(spec.alpha_q16()),
        pend_tenant=jnp.full((pending_capacity,), -1, jnp.int32),
        park_tenant=jnp.full((park_capacity,), -1, jnp.int32),
        park_ta=jnp.zeros((park_capacity,), jnp.int32),
    )


def stack_tables(specs, pending_capacity: int,
                 park_capacity: int) -> TenantTable:
    """Per-lane specs -> one stacked ``[E, ...]`` table.

    Heterogeneous lane specs are padded to the widest tenant count
    (:meth:`TenantSpec.padded`); ``None`` entries become neutral
    equal-weight unlimited tables, which are decision-identical to no
    table at all (the FCFS-equivalence invariant, DESIGN.md §10).
    """
    specs = list(specs)
    T = max((s.n_tenants for s in specs if s is not None), default=1)
    tables = [
        init_table((s or TenantSpec(weights=(1.0,) * T)).padded(T),
                   pending_capacity, park_capacity)
        for s in specs]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *tables)


def grow_table(table: TenantTable,
               new_pending_capacity: int) -> TenantTable:
    """Pad the pending ownership column to a grown pending buffer."""
    K = table.pend_tenant.shape[0]
    assert new_pending_capacity >= K
    pad = new_pending_capacity - K
    if pad == 0:
        return table
    return table._replace(pend_tenant=jnp.concatenate(
        [table.pend_tenant, jnp.full((pad,), -1, jnp.int32)]))


def fair_key(table: TenantTable, t_now: jax.Array) -> jax.Array:
    """The weighted wait-time fair-share key of every queue slot.

    ``key = weight[owner] * float32(t_now - t_a)``: float32 on device
    and host alike, so the differential oracle ranks bit-identically.
    Free slots produce garbage keys; every consumer masks by slot
    liveness first.  With equal weights the (-key, seq) order reduces
    exactly to FCFS seq order — arrival stamps are non-decreasing in
    seq, and float32 scaling of non-negative waits is monotone — the
    invariant ``tests/test_tenancy.py`` locks down.
    """
    T = table.weight.shape[-1]
    tid = jnp.clip(table.park_tenant, 0, T - 1)
    wait = (jnp.asarray(t_now, jnp.int32)
            - table.park_ta).astype(jnp.float32)
    return jnp.take(table.weight, tid) * wait


def ratio_q16_exact(num: int, den: int) -> int:
    """``num / den`` in EWMA fixed point: ``floor(num * EWMA_ONE /
    den)`` for ``num >= 0, den > 0``, saturated to the int32 range.
    The definition :func:`ratio_q16` computes on the device."""
    return min((max(num, 0) << EWMA_BITS) // max(den, 1), _I32_MAX)


def ewma_q16_exact(e: int, x: int, a: int) -> int:
    """One fixed-point EWMA step ``e + floor((x - e) * a / EWMA_ONE)``;
    the result lies between ``e`` and ``x``.  The definition
    :func:`ewma_q16` computes on the device."""
    return e + (((x - e) * a) >> EWMA_BITS)


def ratio_q16(num: jax.Array, den: jax.Array) -> jax.Array:
    """:func:`ratio_q16_exact` in int32 arithmetic: integer quotient,
    then the fraction's bits by long division on the remainder (kept
    below ``den`` in uint32, so doubling it never overflows)."""
    num = jnp.maximum(jnp.asarray(num, jnp.int32), 0)
    den = jnp.maximum(jnp.asarray(den, jnp.int32), 1)
    q = num // den
    r = (num - q * den).astype(jnp.uint32)
    d = den.astype(jnp.uint32)
    frac = jnp.uint32(0)
    for _ in range(EWMA_BITS):
        r = r << 1
        bit = r >= d
        r = jnp.where(bit, r - d, r)
        frac = (frac << 1) | bit.astype(jnp.uint32)
    # q * EWMA_ONE overflows int32 exactly when q >= 2**(31 - BITS)
    return jnp.where(q >= (1 << (31 - EWMA_BITS)), jnp.int32(_I32_MAX),
                     (q << EWMA_BITS) | frac.astype(jnp.int32))


def ewma_q16(e: jax.Array, x: jax.Array, a: jax.Array) -> jax.Array:
    """:func:`ewma_q16_exact` in int32 arithmetic.  ``d * a`` can
    exceed int32, so ``d`` splits into its high part (``d >> BITS``,
    a floor) and low bits: ``floor(d*a / ONE) = hi*a + floor(lo*a /
    ONE)``, with ``lo*a < 2**32`` in uint32.  The result lies between
    ``e`` and ``x``, so int32 wrap-around in between cancels out."""
    d = x - e
    hi = d >> EWMA_BITS
    lo = (d & (EWMA_ONE - 1)).astype(jnp.uint32)
    low = (lo * a.astype(jnp.uint32)) >> EWMA_BITS
    return e + hi * a + low.astype(jnp.int32)


class HostTenantAccounts:
    """Numpy mirror of :class:`TenantTable` accounting (bit-exact).

    Shared by the differential :class:`~repro.core.hostsched.
    TenantOracle` and the host-routed partition quota gate.  ``used``
    reproduces the device expression shape in float32 and the EWMAs
    follow the same fixed-point definitions, so the fields match the
    device table bit-for-bit after identical request streams.
    """

    def __init__(self, spec: TenantSpec):
        self.spec = spec
        T = spec.n_tenants
        self.weight = np.asarray(spec.weights, np.float32)
        self.quota = spec.quota_array()
        self.max_live = spec.max_live_array()
        self.used = np.zeros(T, np.float32)
        self.live = np.zeros(T, np.int32)
        self.n_accepted = np.zeros(T, np.int32)
        self.n_rejected = np.zeros(T, np.int32)
        self.n_quota_rejected = np.zeros(T, np.int32)
        self.n_parked = np.zeros(T, np.int32)
        self.n_reaped = np.zeros(T, np.int32)
        self.acc_ewma = np.zeros(T, np.int32)
        self.slow_ewma = np.zeros(T, np.int32)
        self.occ_ewma = np.int32(0)
        self.alpha = spec.alpha_q16()

    @property
    def n_tenants(self) -> int:
        return self.spec.n_tenants

    def clip_tid(self, tenant: int) -> int:
        return min(max(int(tenant), 0), self.n_tenants - 1)

    def allowed(self, tid: int, n_pe: int, t_du: int) -> bool:
        """The quota gate: same float32 compare as the device."""
        demand = np.float32(n_pe) * np.float32(t_du)
        return bool(
            (self.used[tid] + demand <= self.quota[tid])
            and (self.live[tid] < self.max_live[tid]))

    def record(self, tid: int, *, accepted: bool, blocked: bool,
               parked: bool, occ_q: int = 0,
               t_e: int = -1, t_r: int = 0, t_du: int = 1,
               n_pe: int = 0) -> None:
        """One real request's accounting (mirrors ``_admit_impl``).

        ``occ_q`` is the machine occupancy at arrival in EWMA fixed
        point (:func:`ratio_q16_exact` of busy PEs over the machine).
        """
        a = self.alpha
        if accepted:
            self.used[tid] = np.float32(
                self.used[tid]
                + np.float32(n_pe) * np.float32(t_du))
            self.live[tid] += 1
            self.n_accepted[tid] += 1
            if parked:
                self.n_parked[tid] += 1
            slow = ratio_q16_exact(t_e - t_r, t_du)
            self.slow_ewma[tid] = ewma_q16_exact(
                int(self.slow_ewma[tid]), slow, a)
        else:
            self.n_rejected[tid] += 1
            if blocked:
                self.n_quota_rejected[tid] += 1
        x = EWMA_ONE if accepted else 0
        self.acc_ewma[tid] = ewma_q16_exact(int(self.acc_ewma[tid]), x, a)
        self.occ_ewma = np.int32(
            ewma_q16_exact(int(self.occ_ewma), occ_q, a))

    def release(self, tenant: int) -> None:
        if tenant >= 0:
            self.live[self.clip_tid(tenant)] -= 1

    def reap(self, tenant: int) -> None:
        if tenant >= 0:
            tid = self.clip_tid(tenant)
            self.live[tid] -= 1
            self.n_reaped[tid] += 1

    def snapshot(self) -> dict:
        """Same layout as :func:`repro.tenancy.telemetry.snapshot`."""
        return dict(
            weight=self.weight.copy(), quota=self.quota.copy(),
            max_live=self.max_live.copy(),
            used=self.used.copy(), live=self.live.copy(),
            n_accepted=self.n_accepted.copy(),
            n_rejected=self.n_rejected.copy(),
            n_quota_rejected=self.n_quota_rejected.copy(),
            n_parked=self.n_parked.copy(),
            n_reaped=self.n_reaped.copy(),
            acc_ewma=self.acc_ewma / EWMA_ONE,
            slow_ewma=self.slow_ewma / EWMA_ONE,
            occ_ewma=self.occ_ewma / EWMA_ONE)
