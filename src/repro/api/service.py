"""`ReservationService`: one streaming session API over every engine.

The paper's scheduler is a long-lived service admitting *dynamically
arriving* AR requests.  This module is that service: a
:class:`ReservationService` is configured once by a
:class:`~repro.api.config.ServiceConfig` and opens :class:`Session`\\ s
— each session carries device-resident scheduler state across calls
and exposes one coherent verb set over every backend shape (single
timeline, ensemble lanes, cluster partitions, host/list oracles):

``offer(requests)``
    Streaming admission.  Arrivals stage in a fixed-capacity
    :class:`~repro.core.batch.RequestRing` and admit in constant-shape
    ``chunk_size`` chunks of the jitted ``admit_stream`` scan, so a
    session admits continuously with **zero re-padding and zero
    recompilation** after warmup — regardless of how callers group
    their arrivals.  ``chunk_size=None`` selects one-shot mode (each
    offer is one whole-batch scan: the pre-materialised-experiment
    path of ``simulate_batched`` / ``simulate_grid``).
``tick(t)``
    Release-due advancement: delete every pending reservation ending
    by ``t`` (the simulator's completion heap, as a verb).
``cancel(...)``
    Withdraw a committed reservation (idempotent on auto-release
    sessions: an already-released reservation returns ``False``).
``snapshot()`` / ``restore(...)``
    O(1) capture of the functional state — what-if probing for free.
``metrics()``
    Admission counters, growth events, chunk statistics.

Capacity overflow follows the grow-once high-water protocol everywhere
(DESIGN.md §3/§4): the failed dispatch reports the capacity it needed,
the host grows once, and the chunk re-runs deterministically — so
chunked decisions are bit-identical to a one-shot scan that started
with enough capacity.

The classic three operations (``find_allocation`` / ``add_allocation``
/ ``delete_allocation``) remain available on every session, delegating
to the underlying engine, so pre-service consumers (the fleet, the
simulator oracle) migrate without semantic change.
"""
from __future__ import annotations

import copy
import dataclasses
import heapq
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.api.config import ROUTINGS, ServiceConfig, policy_id_of
from repro.core import batch as batch_lib
from repro.core import ensemble as ens_lib
from repro.core import timeline as tl_lib
from repro.core.batch import Decision, RequestBatch, RequestRing
from repro.core.scheduler import DeviceEngine, _make_engine
from repro.core.types import Allocation, ARRequest, Policy, T_INF
from repro.launch.mesh import data_shards, resolve_placement
from repro.sharding import rules as shard_rules
from repro.tenancy.telemetry import _PER_TENANT, to_host


class OfferResult:
    """Outcome of one :meth:`Session.offer` call.

    ``decision`` / ``batch`` / ``valid`` are the stacked fixed-shape
    arrays actually admitted (``[M]``, or ``[E, M]`` on ensemble
    sessions) — ``valid`` masks out ring filler, and consumers reduce
    metrics from them on-device.  :meth:`allocations` unpacks host
    :class:`~repro.core.types.Allocation` objects (or ``None`` per
    rejection) in the order the requests were offered.  Host/list
    sessions build ``decision`` from numpy and leave ``batch`` unset;
    partitioned sessions provide allocations only.

    Pipelined sessions return *deferred* results: the offer's chunks
    are in flight with their overflow latches unread, and any field
    access (or the next state-reading session verb) drains the whole
    in-flight queue in one device sync (DESIGN.md §9).
    """

    def __init__(self, decision: Optional[Decision] = None,
                 batch: Optional[RequestBatch] = None,
                 valid: Optional[np.ndarray] = None,
                 _allocations: Optional[
                     List[Optional[Allocation]]] = None,
                 _finalize: Optional[Any] = None):
        self._decision = decision
        self._batch = batch
        self._valid = valid
        self._allocations = _allocations
        self._finalize = _finalize

    def _materialize(self) -> None:
        if self._finalize is not None:
            fin, self._finalize = self._finalize, None
            fin()

    @property
    def decision(self) -> Optional[Decision]:
        self._materialize()
        return self._decision

    @property
    def batch(self) -> Optional[RequestBatch]:
        self._materialize()
        return self._batch

    @property
    def valid(self) -> Optional[np.ndarray]:
        self._materialize()
        return self._valid

    @property
    def n_offered(self) -> int:
        self._materialize()
        if self._valid is not None:
            return int(np.asarray(self._valid).sum())
        return len(self._allocations or [])

    @property
    def n_accepted(self) -> int:
        self._materialize()
        if self._decision is not None:
            acc = np.asarray(self._decision.accepted)
            return int((acc & np.asarray(self._valid)).sum())
        return sum(a is not None for a in (self._allocations or []))

    def allocations(self) -> List[Optional[Allocation]]:
        """Host allocations for the *valid* offered requests, in order.

        Single-lane sessions only (on ensemble results, index
        ``decision``/``valid`` per lane instead).
        """
        self._materialize()
        if self._allocations is not None:
            return self._allocations
        if self._decision is None:
            return []
        acc = np.asarray(self._decision.accepted)
        if acc.ndim != 1:
            raise ValueError(
                "allocations() is per-lane on ensemble results; use "
                "decision/valid with a lane index")
        allocs = batch_lib.decisions_to_allocations(self._decision)
        self._allocations = [
            a for a, v in zip(allocs, self._valid) if v]
        return self._allocations


def _empty_result() -> OfferResult:
    return OfferResult(decision=None, batch=None, valid=None,
                       _allocations=[])


def _device_fetch(tree):
    """The service's device->host transfer point for metric reads.

    Every poll-path transfer funnels through here so tests can count
    device syncs (``tests/test_tenancy.py::test_idle_metrics_*``):
    an idle ``Session.metrics()`` must perform **zero** calls — the
    device-derived block is cached until the state changes.
    """
    return jax.device_get(tree)


def _mask_np(pe_ids, words: int) -> np.ndarray:
    """PE ids -> uint32[W] bitmask, numpy-only (no device round-trip)."""
    m = np.zeros(words, np.uint32)
    for i in pe_ids:
        m[i // 32] |= np.uint32(1 << (i % 32))
    return m


def _check_demands(rspec, reqs) -> None:
    """Validate request demand vectors against the session's layout.

    On multi-resource sessions every carried ``demand`` must match the
    :class:`~repro.core.resources.ResourceSpec` (length, plane-0 ==
    ``n_pe``, per-plane range); on plain sessions a demand naming
    secondary resources is an error — silently dropping it would admit
    requests against resources the session does not model.
    """
    if rspec is not None:
        for r in reqs:
            rspec.demand_tail(r.demand, r.n_pe)
        return
    for r in reqs:
        if r.demand is not None and len(r.demand) > 1:
            raise ValueError(
                f"request carries a {len(r.demand)}-resource demand "
                f"but this session is single-resource; set "
                f"ServiceConfig.resources")




def _work_counters(s) -> Dict[str, jax.Array]:
    """The admit searches' index and kernel counters of a state, summed
    over its lanes: ``early_rejects`` (real requests the index rejected
    whole), ``search_tiles`` (candidate tiles the kernel covered),
    ``search_tiles_skipped`` (those without a live candidate) and
    ``search_pe_blocks`` (the PE blocks the kernel ran for its live
    tiles)."""
    tiles = jnp.sum(s.n_search_tiles)
    return dict(early_rejects=jnp.sum(s.n_early_rejects),
                search_tiles=tiles,
                search_tiles_skipped=tiles - jnp.sum(s.n_search_tiles_run),
                search_pe_blocks=jnp.sum(s.n_search_pe_blocks))


# chunks a pipelined offer joins per group while its scans still run
_SETTLE_GROUP = 16


def _concat_tree(chunks: List[Any], axis: int):
    """Concatenate a list of equally-structured pytrees."""
    if len(chunks) == 1:
        return chunks[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=axis), *chunks)


@jax.jit
def _join_group(*chunks):
    """One full settle group's chunks, joined in a single dispatch.

    Its arity is always ``_SETTLE_GROUP``, so it compiles once per
    chunk shape; a shorter last group is joined eagerly instead.
    """
    return _concat_tree(list(chunks), axis=0)


def _accepted_count(decision, valid) -> jax.Array:
    """Device count of the valid accepted decisions (no host sync)."""
    return jnp.sum(jnp.logical_and(jnp.asarray(decision.accepted),
                                   jnp.asarray(valid)),
                   dtype=jnp.int32)


def _push_front(ring: RequestRing, rows: List[dict], lta: int) -> int:
    """Reinsert popped requests at the *front* of a ring, in order.

    The terminal-overflow restage path: ``rows`` were popped from this
    very ring, so front-insertion restores their original position
    ahead of anything pushed later.  ``lta`` rewinds the filler
    stamp (``last_popped_t_a``) to the newest arrival actually
    decided, so future partial chunks cannot release staged requests'
    predecessors early.  Returns how many rows did not fit (dropped).
    """
    kept = rows[:ring.free]
    for row in reversed(kept):
        ring._head = (ring._head - 1) % ring.capacity
        for f in ring._fields:
            ring._buf[f][ring._head] = row[f]
        ring.count += 1
        ring.popped -= 1
    ring.last_popped_t_a = lta
    return len(rows) - len(kept)


class Session:
    """One long-lived scheduler conversation (state lives on device).

    Create via :meth:`ReservationService.session`.  All admission
    verbs require arrival-ordered traffic (``t_a`` non-decreasing
    across calls), exactly like the paper's event loop.
    """

    def __init__(self, service: "ReservationService"):
        self.service = service
        self.config = service.config
        cfg = self.config
        self._counters = dict(offered=0, accepted=0, released=0,
                              reaped=0, cancelled=0, chunks=0,
                              growths=0, one_shot_scans=0,
                              settled_ahead=0, settled_after_replay=0)
        self._backend = _make_backend(cfg, self._counters)

    # -- identity ------------------------------------------------------
    @property
    def engine(self):
        """The underlying engine object (three-op surface)."""
        return self._backend.engine

    # -- the streaming verb set ----------------------------------------
    def offer(self, requests, *, policy=None, routing: Optional[str] = None,
              flush: bool = True) -> OfferResult:
        """Admit newly arrived requests; returns their decisions.

        ``requests`` is an arrival-ordered sequence of
        :class:`~repro.core.types.ARRequest` (on ensemble sessions: one
        such sequence per lane).  With ``flush`` (default) every
        offered request is decided before returning — a final partial
        chunk is padded with never-feasible filler, which cannot change
        decisions.  ``flush=False`` only admits full chunks and leaves
        the remainder staged in the ring for the next offer (or
        :meth:`flush`).

        ``policy`` overrides the config default for this call (one
        policy, or one per lane on ensemble sessions); ``routing``
        applies to partitioned sessions only.
        """
        return self._backend.offer(requests, policy=policy,
                                   routing=routing, flush=flush)

    def flush(self, *, policy=None) -> OfferResult:
        """Decide any requests still staged by ``offer(flush=False)``."""
        return self._backend.offer((), policy=policy, routing=None,
                                   flush=True)

    def tick(self, t: int) -> int:
        """Advance to time ``t``: release reservations ending by ``t``.

        Returns the number of reservations released.  Only meaningful
        on auto-release sessions (the service tracks completions);
        sessions with ``auto_release=False`` hand release back to the
        caller via :meth:`cancel` / ``delete_allocation``.
        """
        return self._backend.tick(t)

    def cancel(self, alloc: Optional[Allocation] = None, *,
               t_s: Optional[int] = None, t_e: Optional[int] = None,
               pe_ids: Optional[Sequence[int]] = None,
               lane: int = 0) -> bool:
        """Withdraw one committed reservation; ``True`` if it was held.

        Pass the :class:`~repro.core.types.Allocation` returned at
        admission (or its ``t_s``/``t_e``/``pe_ids`` triple).  On
        ensemble sessions ``lane`` names the timeline the reservation
        was admitted on (elsewhere it must stay 0).  On auto-release
        sessions cancelling an unknown or already-released reservation
        is a safe no-op returning ``False``.
        """
        if alloc is not None:
            t_s, t_e, pe_ids = alloc.t_s, alloc.t_e, alloc.pe_ids
        if t_s is None or t_e is None or pe_ids is None:
            raise ValueError(
                "cancel needs an Allocation or t_s/t_e/pe_ids")
        return self._backend.cancel(int(t_s), int(t_e), list(pe_ids),
                                    lane=lane)

    def cancel_many(self, allocs: Sequence[Allocation],
                    lane: int = 0) -> List[bool]:
        """Withdraw several committed reservations at once.

        On single-engine sessions all cancellations apply in *one*
        fused dispatch (``timeline.update_many`` deletes every matched
        interval in a single boundary-union + merge pass — DESIGN.md
        §7); other backends fall back to sequential :meth:`cancel`.
        Returns one bool per allocation, matching sequential-cancel
        semantics: on auto-release sessions repeated allocations
        report ``False`` after their first occurrence (the slot is
        already cleared); with ``auto_release=False`` cancels are
        blind deletes and every entry reports ``True``, exactly as
        repeated :meth:`cancel` calls would.
        """
        triples = [(int(a.t_s), int(a.t_e), list(a.pe_ids))
                   for a in allocs]
        return self._backend.cancel_many(triples, lane=lane)

    def snapshot(self):
        """Opaque capture of the whole session state (cheap: pytrees
        are immutable, only ring/heap staging is copied)."""
        return (self._backend.snapshot(), dict(self._counters))

    def restore(self, snap) -> None:
        """Rewind the session to a :meth:`snapshot`."""
        payload, counters = snap
        self._backend.restore(payload)
        self._counters.clear()
        self._counters.update(counters)

    def records(self) -> list:
        """Host view of the availability timeline (merged records)."""
        return self._backend.records()

    def pending(self, lane: int = 0) -> list:
        """The live backfilling deferral queue, FCFS order.

        One dict per parked reservation (``seq``/``t_s``/``t_e``/
        ``t_r``/``t_dl``/``n_pe``/``pe_ids``; the first entry is the
        head of queue).  Empty on non-backfilling sessions.  On
        ensemble sessions ``lane`` names the timeline to inspect.
        """
        return self._backend.pending(lane)

    def metrics(self, tenant: Optional[int] = None) -> Dict[str, Any]:
        """Admission counters plus capacity / streaming geometry.

        Device sessions report ``search_path``: ``"kernel"`` when their
        searches run the Pallas kernel, ``"jnp"`` when they run the
        jnp reference (``use_kernel=False``, or a timeline beyond the
        kernel's budget), and the admit searches' work counters
        ``early_rejects``, ``search_tiles``, ``search_tiles_skipped``
        and ``search_pe_blocks`` (DESIGN.md §13), which rewind with
        :meth:`restore`.
        ``settled_ahead`` counts pipelined offers whose results,
        prepared while their chunks ran, were installed unchanged;
        ``settled_after_replay`` those concatenated again after a
        growth replay (DESIGN.md §9).

        On multi-tenant sessions the ``"tenants"`` key carries the
        per-tenant telemetry arrays (weights, quotas, usage, live
        counts, acceptance/slowdown EWMAs — DESIGN.md §10), read in
        one fused device fetch and cached until the state changes,
        so polling an idle session costs zero device syncs.
        ``metrics(tenant=i)`` returns tenant ``i``'s scalar view.
        """
        # backend.metrics() first: it folds the lazily accumulated
        # device-side accepted count into the shared counters dict
        backend = self._backend.metrics()
        out = dict(self._counters)
        out.update(backend)
        out.update(engine=self.config.engine, n_pe=self.config.n_pe,
                   lanes=self.config.lanes,
                   n_partitions=self.config.n_partitions,
                   chunk_size=self.config.chunk_size,
                   backfill=self.config.backfill)
        if tenant is not None:
            snap = out.get("tenants")
            if snap is None:
                raise ValueError(
                    "metrics(tenant=...) needs a multi-tenant "
                    "session (set ServiceConfig.tenants)")
            from repro.tenancy import tenant_view
            return tenant_view(snap, tenant)
        return out

    # -- the classic three operations ----------------------------------
    def find_allocation(self, req: ARRequest, policy=None,
                        t_now: Optional[int] = None
                        ) -> Optional[Allocation]:
        pol = self._backend.resolve_policy(policy)
        return self._backend.find_allocation(req, pol, t_now=t_now)

    def add_allocation(self, t_s: int, t_e: int,
                       pes: Sequence[int]) -> None:
        self._backend.add_allocation(t_s, t_e, pes)

    def delete_allocation(self, t_s: int, t_e: int,
                          pes: Sequence[int]) -> None:
        self._backend.delete_allocation(t_s, t_e, pes)


class ReservationService:
    """The facade: validate one config, open any number of sessions.

    >>> svc = ReservationService(ServiceConfig(n_pe=64))
    >>> session = svc.session()
    >>> result = session.offer(requests)        # stream in arrivals
    >>> session.tick(now)                        # release completions
    """

    def __init__(self, config: Optional[ServiceConfig] = None,
                 **kwargs):
        if config is None:
            config = ServiceConfig(**kwargs)
        elif kwargs:
            config = config.replace(**kwargs)
        self.config = config
        self.sessions: List[Session] = []

    def session(self) -> Session:
        """Open a fresh session (independent all-free state)."""
        s = Session(self)
        self.sessions.append(s)
        return s

    def metrics(self) -> Dict[str, Any]:
        """Config echo plus per-session counters."""
        return {
            "config": dataclasses.asdict(self.config),
            "n_sessions": len(self.sessions),
            "sessions": [s.metrics() for s in self.sessions],
        }


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


def _make_backend(cfg: ServiceConfig, counters: Dict[str, int]):
    if cfg.n_partitions > 1:
        return _PartitionBackend(cfg, counters)
    if cfg.lanes > 1:
        return _EnsembleBackend(cfg, counters)
    if cfg.engine == "device":
        return _StreamBackend(cfg, counters)
    return _HostBackend(cfg, counters)


class _BackendBase:
    """Shared policy resolution + three-op delegation to ``engine``."""

    def __init__(self, cfg: ServiceConfig, counters: Dict[str, int]):
        self.cfg = cfg
        self.counters = counters
        # `_retained`: an outstanding snapshot/restore aliases our
        # state buffers, so donating them would invalidate it; the
        # next successful admit produces fresh buffers and clears it.
        self._retained = False
        self._acc_dev = None      # lazily synced accepted count
        # device-derived metrics block, cached until the state
        # changes: idle polls re-serve it with zero device syncs
        self._dev_metrics: Optional[Dict[str, Any]] = None

    def resolve_policy(self, policy) -> Policy:
        if policy is None:
            return self.cfg.policy
        if isinstance(policy, str):
            return Policy(policy)
        return policy

    @property
    def growth_budget(self) -> int:
        """Growth retries allowed per dispatch: 0 under
        ``auto_grow=False`` — an overflowing dispatch raises without
        growing or committing anything.  Atomicity is per dispatch
        (chunk): earlier chunks of the same ``offer`` stand, and the
        overflowing chunk's requests return to the ring."""
        return self.cfg.max_growths if self.cfg.auto_grow else 0

    def _grow_guard(self, before: Tuple[int, int],
                    after: Tuple[int, int]) -> None:
        if after != before:
            self.counters["growths"] += 1

    def _donate_ok(self) -> bool:
        return self.cfg.donate and not self._retained

    def _search_path(self, capacity: int, n_pe: int) -> str:
        """The candidate search a dispatch at ``capacity`` runs: the
        Pallas kernel when the config asks for it and the shape is
        within the kernel's budget, else the jnp reference.  Capacity
        only grows, so ``"kernel"`` now means every dispatch so far
        ran the kernel."""
        from repro.kernels import ops as kernel_ops
        if self.cfg.use_kernel and kernel_ops.fits(
                capacity, n_pe, self.cfg.rspec):
            return "kernel"
        return "jnp"

    def _defer_accepted(self, decision, valid) -> None:
        """Accumulate the accepted count on-device, no host sync.

        :meth:`_sync_counters` (called from ``metrics``/``snapshot``)
        folds the accumulator into ``counters["accepted"]`` — this is
        what keeps ``offer`` free of per-call device round-trips.
        """
        self._fold_accepted(_accepted_count(decision, valid))

    def _fold_accepted(self, n: jax.Array) -> None:
        self._acc_dev = n if self._acc_dev is None else \
            self._acc_dev + n

    def _sync_counters(self) -> None:
        if self._acc_dev is not None:
            self.counters["accepted"] += int(
                _device_fetch(self._acc_dev))
            self._acc_dev = None

    def pending(self, lane: int = 0) -> list:
        if lane != 0:
            raise ValueError("lane applies to ensemble sessions")
        return []

    def cancel_many(self, triples, lane: int = 0) -> List[bool]:
        """Withdraw several reservations; sequential fallback.

        The single-engine backend overrides this with one fused
        ``timeline.update_many`` dispatch (DESIGN.md §7).
        """
        return [self.cancel(ts, te, list(pes), lane=lane)
                for ts, te, pes in triples]

    # three ops: default engine delegation
    def find_allocation(self, req, policy, t_now=None):
        return self.engine.find_allocation(req, policy, t_now=t_now)

    def add_allocation(self, t_s, t_e, pes):
        self.engine.add_allocation(t_s, t_e, list(pes))

    def delete_allocation(self, t_s, t_e, pes):
        self.engine.delete_allocation(t_s, t_e, list(pes))

    def records(self):
        return self.engine.records()


class _StreamBackend(_BackendBase):
    """Single device timeline with ring-buffer chunked streaming."""

    def __init__(self, cfg, counters):
        super().__init__(cfg, counters)
        mu = cfg.machine_units
        self.engine = DeviceEngine(
            cfg.n_pe, capacity=cfg.capacity, use_kernel=cfg.use_kernel,
            bucketing=cfg.bucketing,
            pending_capacity=cfg.pending_capacity,
            park_capacity=cfg.park_capacity,
            tenants=cfg.tenants, rspec=cfg.rspec,
            live_units=mu[0] if mu is not None else None,
            index_tile=cfg.index_tile)
        self._rspec = cfg.rspec
        self._n_tenants = cfg.tenants.n_tenants if cfg.tenancy else 0
        self._grace = cfg.tenants.grace if cfg.tenancy else None
        self._bf = batch_lib.BF_NONE if not cfg.backfilling else \
            batch_lib.as_backfill_id(cfg.backfill)
        self.ring = RequestRing(cfg.ring_capacity,
                                with_tenant=cfg.tenancy,
                                extra_demand=cfg.extra_demand) \
            if cfg.chunk_size else None
        # pipelined offers whose overflow latches are still unread:
        # one dict per offer, drained together in one device sync
        self._inflight: List[dict] = []

    @property
    def _state(self):
        return self.engine.state

    @_state.setter
    def _state(self, s):
        self.engine.state = s
        self.engine._n_valid = None      # lazily recomputed on search
        self._dev_metrics = None         # device metrics went stale

    def _check_tenants(self, reqs) -> None:
        if self._n_tenants:
            for r in reqs:
                if r.tenant >= self._n_tenants:
                    raise ValueError(
                        f"request tenant {r.tenant} out of range "
                        f"[0, {self._n_tenants}) for this session's "
                        f"TenantSpec")
        _check_demands(self._rspec, reqs)

    def _capacities(self):
        s = self._state
        return (s.tl.capacity, s.pending_capacity)

    def _admit_batch(self, batch: RequestBatch, pid: int) -> Decision:
        before = self._capacities()
        try:
            state, dec = batch_lib.admit_stream_grow(
                self._state, batch, pid, n_pe=self.cfg.n_pe,
                backfill=self._bf,
                auto_release=self.cfg.auto_release,
                use_kernel=self.cfg.use_kernel,
                max_growths=self.growth_budget,
                donate=self._donate_ok())
        except batch_lib.GrowthError as e:
            if e.state is not None:
                # the donated attempt consumed our buffers; reinstall
                # the in-dispatch rollback (latch cleared) so the
                # session stays usable after the raise
                self._state = e.state._replace(
                    overflow=jnp.zeros_like(e.state.overflow))
            raise
        self._grow_guard(before, (state.tl.capacity,
                                  state.pending_capacity))
        self._state = state
        self._retained = False
        return dec

    def pending(self, lane: int = 0) -> list:
        if lane != 0:
            raise ValueError("lane applies to ensemble sessions")
        self._drain_inflight()
        return batch_lib.parked_entries(self._state)

    # three ops + records read (or mutate) the live state: settle any
    # in-flight pipelined offers first
    def find_allocation(self, req, policy, t_now=None):
        self._drain_inflight()
        return self.engine.find_allocation(req, policy, t_now=t_now)

    def add_allocation(self, t_s, t_e, pes):
        self._drain_inflight()
        self.engine.add_allocation(t_s, t_e, list(pes))

    def delete_allocation(self, t_s, t_e, pes):
        self._drain_inflight()
        self.engine.delete_allocation(t_s, t_e, list(pes))

    def records(self):
        self._drain_inflight()
        return self.engine.records()

    def offer(self, requests, *, policy, routing, flush) -> OfferResult:
        with jax.profiler.TraceAnnotation("repro.offer"):
            return self._offer(requests, policy, routing, flush)

    def _offer(self, requests, policy, routing, flush) -> OfferResult:
        if routing is not None:
            raise ValueError("routing applies to partitioned sessions")
        if not flush and self.ring is None:
            raise ValueError(
                "flush=False staging needs the ring buffer; this "
                "session is one-shot (chunk_size=None)")
        pid = policy_id_of(self.resolve_policy(policy))
        if isinstance(requests, RequestBatch):
            # pre-packed batch: the pre-materialised-experiment path
            if self.ring is not None:
                raise ValueError(
                    "a pre-packed RequestBatch bypasses the ring; use "
                    "chunk_size=None (one-shot mode) or offer "
                    "ARRequest sequences")
            n = requests.t_a.shape[0]
            self.counters["offered"] += n
            dec = self._admit_batch(requests, pid)
            self.counters["one_shot_scans"] += 1
            res = OfferResult(decision=dec, batch=requests,
                              valid=np.ones(n, bool))
            self._defer_accepted(res.decision, res.valid)
            return res
        reqs = list(requests)
        self._check_tenants(reqs)
        if self.ring is None:
            self.counters["offered"] += len(reqs)
            if not reqs:
                return _empty_result()
            batch = batch_lib.requests_to_batch(
                reqs, with_tenant=bool(self._n_tenants),
                extra_demand=self.cfg.extra_demand)
            dec = self._admit_batch(batch, pid)
            self.counters["one_shot_scans"] += 1
            valid = np.ones(len(reqs), bool)
            res = OfferResult(decision=dec, batch=batch, valid=valid)
            self._defer_accepted(res.decision, res.valid)
            return res
        batch_lib.check_arrival_order(reqs, self.ring.last_t_a)
        self.counters["offered"] += len(reqs)
        if self._donate_ok() and self.growth_budget > 0:
            return self._offer_pipelined(reqs, pid, flush)
        self._drain_inflight()
        return self._offer_eager(reqs, pid, flush)

    def _offer_eager(self, reqs, pid, flush) -> OfferResult:
        chunk = self.cfg.chunk_size
        decs: List[Decision] = []
        batches: List[RequestBatch] = []
        valids: List[np.ndarray] = []

        def drain_one():
            # keep the ring intact if the chunk raises (auto_grow=False
            # overflow): the popped requests stay staged for a retry
            with jax.profiler.TraceAnnotation("repro.offer.stage"):
                ring_snap = self.ring.snapshot()
                batch, valid = self.ring.pop_chunk(chunk, self.cfg.n_pe)
            try:
                with jax.profiler.TraceAnnotation("repro.offer.dispatch"):
                    decs.append(self._admit_batch(batch, pid))
            except Exception:
                self.ring.restore(ring_snap)
                raise
            batches.append(batch)
            valids.append(valid)
            self.counters["chunks"] += 1

        i = 0
        while i < len(reqs):
            take = min(self.ring.free, len(reqs) - i)
            with jax.profiler.TraceAnnotation("repro.offer.stage"):
                self.ring.push(reqs[i:i + take])
            i += take
            while self.ring.count >= chunk:
                drain_one()
        if flush:
            while self.ring.count:
                drain_one()
        if not decs:
            return _empty_result()
        res = OfferResult(decision=_concat_tree(decs, axis=0),
                          batch=_concat_tree(batches, axis=0),
                          valid=np.concatenate(valids))
        self._defer_accepted(res.decision, res.valid)
        return res

    def _offer_pipelined(self, reqs, pid, flush) -> OfferResult:
        """Chunked drain over the double-buffered device ring.

        Zero per-chunk synchronization: every chunk's admit goes
        through :func:`~repro.core.batch.admit_stream_donated`
        (allocation-free, async), and while the device runs chunk k
        the host pops and uploads chunk k+1 from the ring.  The
        overflow latches are not read here at all: the offer registers
        itself on ``_inflight`` and returns a *deferred*
        :class:`OfferResult`, so consecutive offers keep pipelining
        with zero device syncs between them.  The first result-field
        access or state-reading verb calls :meth:`_drain_inflight`,
        which reads every outstanding latch in one stacked
        ``device_get`` (DESIGN.md §8/§9).  On overflow (rare) the
        sticky in-dispatch rollback left the state exactly at the
        first latched chunk, so the tail replays deterministically on
        a grown state — decisions bit-identical to the eager
        per-chunk path.

        The settling work is queued here, behind the offer's own scans
        while the device still runs them: every ``_SETTLE_GROUP``
        chunks' decisions and batches are joined into one group, and
        the offer ends by joining the groups and counting its accepted
        requests.  The drain installs these prepared results unless a
        replay rewrote the offer's decisions.
        """
        chunk = self.cfg.chunk_size
        decs: List[Decision] = []
        batches: List[RequestBatch] = []
        valids: List[np.ndarray] = []
        ovfs: List[jax.Array] = []
        ltas: List[int] = [self.ring.last_popped_t_a]
        groups: List[Tuple[Decision, RequestBatch]] = []
        staged = None

        def settle_group() -> None:
            # join the chunks dispatched since the last group; the
            # concatenations queue behind their scans
            lo = len(groups) * _SETTLE_GROUP
            part = list(zip(decs[lo:], batches[lo:]))
            with jax.profiler.TraceAnnotation("repro.offer.settle"):
                groups.append(_join_group(*part)
                              if len(part) == _SETTLE_GROUP
                              else _concat_tree(part, axis=0))

        def stage():
            with jax.profiler.TraceAnnotation("repro.offer.stage"):
                popped = self.ring.pop_chunk(chunk, self.cfg.n_pe)
            ltas.append(self.ring.last_popped_t_a)
            return popped

        def dispatch(cur) -> None:
            batch, valid = cur
            with jax.profiler.TraceAnnotation("repro.offer.dispatch"):
                state, dec = batch_lib.admit_stream_donated(
                    self._state, batch, jnp.int32(pid), self._bf,
                    n_pe=self.cfg.n_pe,
                    auto_release=self.cfg.auto_release,
                    use_kernel=self.cfg.use_kernel)
                self._state = state
                # jnp.any copies the latch into a fresh buffer: the
                # next dispatch donates `state` (this leaf included)
                # away
                ovfs.append(jnp.any(state.overflow))
            decs.append(dec)
            batches.append(batch)
            valids.append(valid)
            self.counters["chunks"] += 1
            if len(decs) % _SETTLE_GROUP == 0:
                settle_group()

        def drain(more) -> None:
            nonlocal staged
            while staged is not None or more():
                cur = staged if staged is not None else stage()
                staged = None
                dispatch(cur)          # device admits chunk k ...
                if more():
                    staged = stage()   # ... host stages chunk k+1

        i = 0
        while i < len(reqs):
            take = min(self.ring.free, len(reqs) - i)
            with jax.profiler.TraceAnnotation("repro.offer.stage"):
                self.ring.push(reqs[i:i + take])
            i += take
            drain(lambda: self.ring.count >= chunk)
        if flush:
            drain(lambda: self.ring.count > 0)
        if not decs:
            return _empty_result()
        if len(decs) > len(groups) * _SETTLE_GROUP:
            settle_group()
        with jax.profiler.TraceAnnotation("repro.offer.settle"):
            decision = _concat_tree([g[0] for g in groups], axis=0)
            valid = np.concatenate(valids)
            prepared = (decision,
                        _concat_tree([g[1] for g in groups], axis=0),
                        valid, _accepted_count(decision, valid))
        res = OfferResult(_finalize=self._drain_inflight)
        self._inflight.append(dict(ovfs=ovfs, decs=decs,
                                   batches=batches, valids=valids,
                                   ltas=ltas, pid=pid, result=res,
                                   prepared=prepared))
        return res

    def _drain_inflight(self) -> None:
        """Settle every in-flight pipelined offer in one device sync.

        The overflow latch is sticky along the chain of donated
        dispatches (DESIGN.md §8), so the newest dispatch's latch copy
        is set exactly when some chunk of some in-flight offer latched:
        the common path reads that one scalar and installs each
        offer's prepared results.  On a latch, every latch is read to
        find the first latched chunk.  The sticky in-dispatch rollback
        made every dispatch from it on state-preserving, so ``_state``
        is the pre-latch state sized by the failed tail's watermarks:
        grow once from the rollback, replay the owning offer's tail,
        then replay *all* chunks of every later offer (their original
        decisions are garbage, and so are their prepared results) —
        observably identical to the eager per-chunk path.
        """
        if not self._inflight:
            return
        with jax.profiler.TraceAnnotation("repro.drain"):
            self._drain(self._inflight)

    def _drain(self, inflight: List[dict]) -> None:
        self._inflight = []
        # the drain's single synchronization point: the newest latch
        with jax.profiler.TraceAnnotation("repro.drain.sync"):
            latched = bool(_device_fetch(inflight[-1]["ovfs"][-1]))
        err = None
        if latched:
            with jax.profiler.TraceAnnotation("repro.drain.replay"):
                all_ovfs = [o for ctx in inflight for o in ctx["ovfs"]]
                first = np.asarray(
                    _device_fetch(jnp.stack(all_ovfs))).argmax()
                err = self._replay_from(int(first), inflight)
        with jax.profiler.TraceAnnotation("repro.drain.concat"):
            for ctx in inflight:
                res = ctx["result"]
                res._finalize = None
                if ctx["prepared"] is not None:
                    res._decision, res._batch, res._valid, n = \
                        ctx["prepared"]
                    self._fold_accepted(n)
                    self.counters["settled_ahead"] += 1
                elif ctx["decs"]:
                    res._decision = _concat_tree(ctx["decs"], axis=0)
                    res._batch = _concat_tree(ctx["batches"], axis=0)
                    res._valid = np.concatenate(ctx["valids"])
                    self._defer_accepted(res._decision, res._valid)
                    self.counters["settled_after_replay"] += 1
                else:
                    res._allocations = []
        if err is not None:
            raise err

    def _replay_from(self, g: int, inflight: List[dict]
                     ) -> Optional[Exception]:
        """Grow and replay from dispatch ``g``, the first latched one
        of the drained offers (see :meth:`_drain_inflight`); returns the
        terminal overflow's error, if any, after restaging."""
        c = 0                          # -> (offer c, its chunk g)
        while g >= len(inflight[c]["ovfs"]):
            g -= len(inflight[c]["ovfs"])
            c += 1
        for ctx in inflight[c:]:
            ctx["prepared"] = None     # joined from garbage decisions
        for ci in range(c, len(inflight)):
            ctx = inflight[ci]
            err = self._replay_chunks(
                g if ci == c else 0, ctx, rollback=(ci == c))
            if err is not None:
                # terminal overflow: every later dispatch was
                # state-preserving.  Restage undecided requests in
                # arrival order — newest offer pushed first so the
                # oldest tail ends up at the ring head.
                for later in reversed(inflight[ci + 1:]):
                    self.counters["chunks"] -= len(later["batches"])
                    self._restage_tail(0, later["batches"],
                                       later["valids"], later["ltas"])
                    del later["decs"][:], later["batches"][:], \
                        later["valids"][:]
                k = ctx["fail_k"]
                self._restage_tail(k, ctx["batches"], ctx["valids"],
                                   ctx["ltas"])
                del ctx["decs"][k:], ctx["batches"][k:], \
                    ctx["valids"][k:]
                return err
        return None

    def _replay_chunks(self, j: int, ctx: dict, *,
                       rollback: bool) -> Optional[Exception]:
        """Re-run one offer's chunks ``j..`` after a latched overflow.

        ``rollback`` grows the rolled-back state first (only the offer
        owning the first latched chunk; later offers replay on the
        already-healthy state).  On terminal overflow the offer is
        truncated at the failing chunk (``ctx["fail_k"]``) and the
        :class:`~repro.core.batch.GrowthError` is returned for the
        caller to restage and re-raise.
        """
        if rollback:
            before = self._capacities()
            self._state = batch_lib.grow_rollback(self._state)
            self._grow_guard(before, self._capacities())
        batches, decs = ctx["batches"], ctx["decs"]
        for k in range(j, len(batches)):
            try:
                decs[k] = self._admit_batch(batches[k], ctx["pid"])
            except batch_lib.GrowthError as e:
                ctx["fail_k"] = k
                self.counters["chunks"] -= len(batches) - k
                return e
        return None

    def _restage_tail(self, k: int, batches, valids, ltas) -> None:
        """Return undecided chunks ``k..`` to the front of the ring.

        Terminal overflow during a replay: the eager path would have
        left these requests staged, so reinsert them ahead of anything
        pushed later (order preserved — they were popped from here).
        Requests that no longer fit are dropped with a warning; the
        session itself stays usable on the rolled-back state.
        """
        rows = []
        names = self.ring._fields
        for batch, valid in zip(batches[k:], valids[k:]):
            fields = {f: np.asarray(getattr(batch, f))
                      for f in names}
            for i in np.flatnonzero(valid):
                rows.append({f: int(fields[f][i]) for f in names})
        dropped = _push_front(self.ring, rows, ltas[k])
        if dropped:
            warnings.warn(
                f"ring full while restaging after terminal overflow: "
                f"{dropped} undecided requests dropped",
                RuntimeWarning, stacklevel=2)

    def tick(self, t: int) -> int:
        if not self.cfg.auto_release:
            return self._reap(t)
        self._drain_inflight()
        before_rel = int(self._state.n_released)
        before = self._capacities()
        state = batch_lib.release_until(
            self._state, t, max_growths=self.growth_budget)
        self._grow_guard(before, (state.tl.capacity,
                                  state.pending_capacity))
        self._state = state
        released = int(state.n_released) - before_rel
        self.counters["released"] += released
        return released

    def _reap(self, t: int) -> int:
        """Overdue-reservation reaping (DESIGN.md §10).

        With ``auto_release=False`` the caller owns completion release
        — but a multi-tenant session with a ``grace`` window still
        reclaims reservations held past ``t_e + grace`` on ``tick``,
        batch-deleting them and charging the usage (``n_reaped``) to
        the owning tenant.  Auto-release sessions never reap: their
        ``tick`` already deletes everything ending by ``t``, which is
        strictly earlier than ``t - grace``.
        """
        if self._grace is None:
            return 0
        self._drain_inflight()
        before_rel = int(self._state.n_released)
        before = self._capacities()
        state = batch_lib.reap_until(
            self._state, t, self._grace,
            max_growths=self.growth_budget)
        self._grow_guard(before, (state.tl.capacity,
                                  state.pending_capacity))
        self._state = state
        reaped = int(state.n_released) - before_rel
        self.counters["reaped"] += reaped
        return reaped

    def cancel(self, t_s: int, t_e: int, pe_ids: List[int],
               lane: int = 0) -> bool:
        if lane != 0:
            raise ValueError("lane applies to ensemble sessions")
        self._drain_inflight()
        mask = tl_lib.ids_to_mask32(pe_ids, self._state.tl.words)
        before = self._capacities()
        state, done = batch_lib.cancel_one(
            self._state, t_s, t_e, mask,
            require_pending=self.cfg.auto_release,
            max_growths=self.growth_budget)
        self._grow_guard(before, (state.tl.capacity,
                                  state.pending_capacity))
        self._state = state
        self.counters["cancelled"] += int(done)
        return done

    def cancel_many(self, triples, lane: int = 0) -> List[bool]:
        if lane != 0:
            raise ValueError("lane applies to ensemble sessions")
        self._drain_inflight()
        W = self._state.tl.words
        entries = [(ts, te, tl_lib.ids_to_mask32(pes, W))
                   for ts, te, pes in triples]
        before = self._capacities()
        state, done = batch_lib.cancel_many(
            self._state, entries,
            require_pending=self.cfg.auto_release,
            max_growths=self.growth_budget)
        self._grow_guard(before, (state.tl.capacity,
                                  state.pending_capacity))
        self._state = state
        self.counters["cancelled"] += sum(done)
        return done

    def snapshot(self):
        self._drain_inflight()
        self._sync_counters()
        self._retained = True    # snapshot aliases these buffers
        return (self._state,
                self.ring.snapshot() if self.ring else None)

    def restore(self, payload):
        with jax.profiler.TraceAnnotation("repro.restore"):
            # settle results against the old state
            self._drain_inflight()
            state, ring_snap = payload
            self._state = state
            self._retained = True    # ...and so does a restored payload
            self._acc_dev = None     # accumulated after the snapshot
            if self.ring and ring_snap is not None:
                self.ring.restore(ring_snap)

    def _refresh_dev_metrics(self) -> None:
        """One fused device read of every state-derived counter."""
        s = self._state
        vals: Dict[str, Any] = dict(
            n_pending=jnp.sum(s.pend_te != T_INF, dtype=jnp.int32),
            **_work_counters(s))
        if self.cfg.backfilling:
            vals.update(
                n_parked_now=jnp.sum(s.park_seq != T_INF,
                                     dtype=jnp.int32),
                n_parked=s.n_parked, n_promoted=s.n_promoted,
                n_moved=s.n_moved)
        if s.tenants is not None:
            vals["tenants"] = {
                f: getattr(s.tenants, f)
                for f in _PER_TENANT + ("occ_ewma",)}
        host = _device_fetch(vals)
        self._dev_metrics = {
            k: to_host(v) if k == "tenants" else int(v)
            for k, v in host.items()}

    def metrics(self):
        # fast path (satellite: idle polls cost no device sync): with
        # nothing in flight, no deferred accepted count, and a warm
        # cache, this performs zero device fetches
        if self._inflight:
            self._drain_inflight()
        self._sync_counters()
        if self._dev_metrics is None:
            self._refresh_dev_metrics()
        cap, pend = self._capacities()
        out = dict(capacity=cap, pending_capacity=pend,
                   search_path=self._search_path(cap, self.cfg.n_pe))
        out.update(self._dev_metrics)
        if self.ring:
            out.update(ring_capacity=self.ring.capacity,
                       ring_staged=self.ring.count,
                       ring_wrapped=self.ring.wrapped)
        if self.cfg.backfilling:
            out["park_capacity"] = self._state.park_capacity
        return out


class _EnsembleBackend(_BackendBase):
    """E whole-machine replica lanes behind one vmapped state."""

    def __init__(self, cfg, counters):
        super().__init__(cfg, counters)
        # lane axis -> mesh data axis (DESIGN.md §8): every stacked
        # leaf is sharded on its leading (ensemble) dimension, so the
        # vmapped admit scan runs one program with each device owning
        # lanes/n_shards lanes — decisions are placement-invariant.
        self.mesh = resolve_placement(cfg.placement, cfg.lanes)
        states = ens_lib.init_ensemble(
            cfg.lanes, cfg.capacity, cfg.n_pe, cfg.pending_capacity,
            cfg.park_capacity, rspec=cfg.rspec,
            machine_units=cfg.machine_units,
            index_tile=cfg.index_tile)
        self._lane_specs = cfg.lane_tenant_specs
        if self._lane_specs is not None:
            # per-lane tables stack to one [E, ...] pytree and shard
            # on the lane axis with everything else (DESIGN.md §10);
            # None entries become neutral tables, decision-identical
            # to no table (the FCFS-equivalence invariant)
            from repro.tenancy import stack_tables
            states = states._replace(tenants=stack_tables(
                self._lane_specs, cfg.pending_capacity,
                cfg.park_capacity))
        self.states = self._put(states)
        self._bf_ids = self._put(
            ens_lib.backfill_ids(cfg.backfill, cfg.lanes))
        self.rings = [RequestRing(cfg.ring_capacity,
                                  with_tenant=cfg.tenancy,
                                  extra_demand=cfg.extra_demand)
                      for _ in range(cfg.lanes)] \
            if cfg.chunk_size else None

    def _put(self, tree):
        """Lane-shard a stacked pytree (no-op on unsharded sessions,
        and for leaves already carrying the target sharding)."""
        return shard_rules.shard_ensemble(self.mesh, tree)

    @property
    def states(self):
        return self._states_val

    @states.setter
    def states(self, s):
        self._states_val = s
        self._dev_metrics = None         # device metrics went stale

    @property
    def engine(self):
        return self

    def _capacities(self):
        return ens_lib.lane_capacity(self.states)

    def _resolve_pids(self, policy) -> jax.Array:
        E = self.cfg.lanes
        if policy is None:
            policy = self.cfg.policy
        if isinstance(policy, (Policy, int, str)):
            return jnp.full((E,), policy_id_of(policy), jnp.int32)
        if isinstance(policy, jax.Array):
            return policy
        pids = [policy_id_of(p) for p in policy]
        if len(pids) != E:
            raise ValueError(
                f"{len(pids)} policies for {E} lanes")
        return jnp.asarray(pids, jnp.int32)

    def _admit_batch(self, batch: RequestBatch,
                     pids: jax.Array) -> Decision:
        before = self._capacities()
        try:
            states, dec = ens_lib.admit_stream_ensemble_auto(
                self.states, self._put(batch), pids,
                n_pe=self.cfg.n_pe,
                backfills=self._bf_ids,
                auto_release=self.cfg.auto_release,
                use_kernel=self.cfg.use_kernel,
                max_growths=self.growth_budget,
                donate=self._donate_ok())
        except batch_lib.GrowthError as e:
            if e.state is not None:
                self.states = e.state._replace(
                    overflow=jnp.zeros_like(e.state.overflow))
            raise
        after = ens_lib.lane_capacity(states)
        self._grow_guard(before, after)
        if after != before:
            # growth re-materialized the lanes outside the donated
            # dispatch; re-pin the lane sharding deterministically
            states = self._put(states)
        self.states = states
        self._retained = False
        return dec

    def pending(self, lane: int = 0) -> list:
        if not 0 <= lane < self.cfg.lanes:
            raise ValueError(
                f"lane {lane} out of range for {self.cfg.lanes} lanes")
        return batch_lib.parked_entries(
            ens_lib.member(self.states, lane))

    def offer(self, streams, *, policy, routing, flush) -> OfferResult:
        if routing is not None:
            raise ValueError("routing applies to partitioned sessions")
        if not flush and self.rings is None:
            raise ValueError(
                "flush=False staging needs the ring buffers; this "
                "session is one-shot (chunk_size=None)")
        pids = self._resolve_pids(policy)
        if isinstance(streams, tuple) and len(streams) == 2 \
                and isinstance(streams[0], RequestBatch):
            # pre-padded (batch, valid): the grid's one-shot path
            if self.rings is not None:
                raise ValueError(
                    "a pre-padded (RequestBatch, valid) pair bypasses "
                    "the rings; use chunk_size=None (one-shot mode)")
            batch, valid = streams
            self.counters["offered"] += int(valid.sum())
            dec = self._admit_batch(batch, pids)
            self.counters["one_shot_scans"] += 1
            res = OfferResult(decision=dec, batch=batch, valid=valid)
            self._defer_accepted(res.decision, res.valid)
            return res
        streams = [list(s) for s in streams] or \
            [[] for _ in range(self.cfg.lanes)]
        if len(streams) != self.cfg.lanes:
            raise ValueError(
                f"{len(streams)} per-lane streams for "
                f"{self.cfg.lanes} lanes")
        if self.rings is not None:
            for ring, stream in zip(self.rings, streams):
                batch_lib.check_arrival_order(stream, ring.last_t_a)
        if self._lane_specs is not None:
            for e, (spec, stream) in enumerate(
                    zip(self._lane_specs, streams)):
                limit = spec.n_tenants if spec is not None else 1
                for r in stream:
                    if r.tenant >= limit:
                        raise ValueError(
                            f"request tenant {r.tenant} out of range "
                            f"[0, {limit}) for lane {e}'s TenantSpec")
        for stream in streams:
            _check_demands(self.cfg.rspec, stream)
        self.counters["offered"] += sum(map(len, streams))
        if self.rings is None:
            if not any(streams):
                return _empty_result()
            batch, valid = batch_lib.pad_streams(
                streams, self.cfg.n_pe, with_tenant=self.cfg.tenancy,
                extra_demand=self.cfg.extra_demand)
            dec = self._admit_batch(batch, pids)
            self.counters["one_shot_scans"] += 1
            res = OfferResult(decision=dec, batch=batch, valid=valid)
            self._defer_accepted(res.decision, res.valid)
            return res
        if self._donate_ok() and self.growth_budget > 0:
            return self._offer_pipelined(streams, pids, flush)
        return self._offer_eager(streams, pids, flush)

    def _offer_eager(self, streams, pids, flush) -> OfferResult:
        chunk = self.cfg.chunk_size
        decs, batches, valids = [], [], []

        def drain_one(full_only: bool):
            # a lane below a full chunk keeps its requests staged
            # unless this is a flushing drain (flush=False contract)
            ring_snaps = [r.snapshot() for r in self.rings]
            batch, valid = batch_lib.pop_chunk_ensemble(
                self.rings, chunk, self.cfg.n_pe, full_only=full_only)
            try:
                decs.append(self._admit_batch(batch, pids))
            except Exception:
                for r, s in zip(self.rings, ring_snaps):
                    r.restore(s)
                raise
            batches.append(batch)
            valids.append(valid)
            self.counters["chunks"] += 1

        cursors = [0] * self.cfg.lanes
        while any(c < len(s) for c, s in zip(cursors, streams)):
            for e, (ring, stream) in enumerate(
                    zip(self.rings, streams)):
                take = min(ring.free, len(stream) - cursors[e])
                ring.push(stream[cursors[e]:cursors[e] + take])
                cursors[e] += take
            while any(r.count >= chunk for r in self.rings):
                drain_one(full_only=not flush)
        if flush:
            while any(r.count for r in self.rings):
                drain_one(full_only=False)
        if not decs:
            return _empty_result()
        res = OfferResult(decision=_concat_tree(decs, axis=1),
                          batch=_concat_tree(batches, axis=1),
                          valid=np.concatenate(valids, axis=1))
        self._defer_accepted(res.decision, res.valid)
        return res

    def _offer_pipelined(self, streams, pids, flush) -> OfferResult:
        """Lane-stacked pipelined drain (see the stream backend).

        One donated vmapped dispatch per chunk across all lanes —
        sharded lanes run their slices in the same program — while the
        host pops and lane-shards the next chunk.  All overflow
        latches are read once at the end; a latched chunk replays on
        a collectively grown ensemble, bit-identical to the eager
        per-chunk growth path.
        """
        chunk = self.cfg.chunk_size
        pids = self._put(pids)
        decs, batches, valids, ovfs = [], [], [], []
        ltas = [[r.last_popped_t_a for r in self.rings]]
        staged = None

        def stage(full_only: bool):
            batch, valid = batch_lib.pop_chunk_ensemble(
                self.rings, chunk, self.cfg.n_pe, full_only=full_only)
            ltas.append([r.last_popped_t_a for r in self.rings])
            return self._put(batch), valid

        def dispatch(cur) -> None:
            batch, valid = cur
            states, dec = ens_lib.admit_stream_ensemble_donated(
                self.states, batch, pids, self._bf_ids,
                n_pe=self.cfg.n_pe,
                auto_release=self.cfg.auto_release,
                use_kernel=self.cfg.use_kernel)
            self.states = states
            ovfs.append(jnp.any(states.overflow))
            decs.append(dec)
            batches.append(batch)
            valids.append(valid)
            self.counters["chunks"] += 1

        def drain(more, full_only: bool) -> None:
            nonlocal staged
            while staged is not None or more():
                cur = staged if staged is not None \
                    else stage(full_only)
                staged = None
                dispatch(cur)
                if more():
                    staged = stage(full_only)

        cursors = [0] * self.cfg.lanes
        while any(c < len(s) for c, s in zip(cursors, streams)):
            for e, (ring, stream) in enumerate(
                    zip(self.rings, streams)):
                take = min(ring.free, len(stream) - cursors[e])
                ring.push(stream[cursors[e]:cursors[e] + take])
                cursors[e] += take
            drain(lambda: any(r.count >= chunk for r in self.rings),
                  full_only=not flush)
        if flush:
            drain(lambda: any(r.count for r in self.rings),
                  full_only=False)
        if not decs:
            return _empty_result()
        latched = np.asarray(jax.device_get(jnp.stack(ovfs)))
        if latched.any():
            self._replay_overflow(int(latched.argmax()), batches,
                                  pids, decs, valids, ltas)
        res = OfferResult(decision=_concat_tree(decs, axis=1),
                          batch=_concat_tree(batches, axis=1),
                          valid=np.concatenate(valids, axis=1))
        self._defer_accepted(res.decision, res.valid)
        return res

    def _replay_overflow(self, j: int, batches, pids, decs, valids,
                         ltas) -> None:
        """Collective-growth replay of chunks ``j..`` after rollback."""
        before = self._capacities()
        self.states = self._put(
            ens_lib.grow_rollback_ensemble(self.states))
        self._grow_guard(before, self._capacities())
        for k in range(j, len(batches)):
            try:
                decs[k] = self._admit_batch(batches[k], pids)
            except batch_lib.GrowthError:
                self._restage_tail(k, batches, valids, ltas)
                self.counters["chunks"] -= len(batches) - k
                del decs[k:], batches[k:], valids[k:]
                raise

    def _restage_tail(self, k: int, batches, valids, ltas) -> None:
        """Per-lane front-reinsertion of undecided chunks ``k..``."""
        dropped = 0
        for e, ring in enumerate(self.rings):
            rows = []
            names = ring._fields
            for batch, valid in zip(batches[k:], valids[k:]):
                fields = {f: np.asarray(getattr(batch, f)[e])
                          for f in names}
                for i in np.flatnonzero(valid[e]):
                    rows.append({f: int(fields[f][i]) for f in names})
            dropped += _push_front(ring, rows, ltas[k][e])
        if dropped:
            warnings.warn(
                f"rings full while restaging after terminal overflow: "
                f"{dropped} undecided requests dropped",
                RuntimeWarning, stacklevel=2)

    def tick(self, t: int) -> int:
        if not self.cfg.auto_release:
            return self._reap(t)
        before_rel = int(jnp.sum(self.states.n_released))
        before = self._capacities()
        states = ens_lib.release_until_ensemble(
            self.states, t, max_growths=self.growth_budget)
        self._grow_guard(before, ens_lib.lane_capacity(states))
        self.states = self._put(states)
        released = int(jnp.sum(states.n_released)) - before_rel
        self.counters["released"] += released
        return released

    def _reap(self, t: int) -> int:
        """Per-lane overdue reaping (see the stream backend's _reap).

        Each lane reaps with its own spec's grace; lanes without one
        get a ``T_INF`` grace, whose cutoff precedes every arrival.
        """
        if self._lane_specs is None:
            return 0
        graces = [T_INF if s is None or s.grace is None else s.grace
                  for s in self._lane_specs]
        if all(g == T_INF for g in graces):
            return 0
        before_rel = int(jnp.sum(self.states.n_released))
        before = self._capacities()
        states = ens_lib.reap_until_ensemble(
            self.states, t, np.asarray(graces, np.int32),
            max_growths=self.growth_budget)
        self._grow_guard(before, ens_lib.lane_capacity(states))
        self.states = self._put(states)
        reaped = int(jnp.sum(states.n_released)) - before_rel
        self.counters["reaped"] += reaped
        return reaped

    def cancel(self, t_s, t_e, pe_ids, lane: int = 0) -> bool:
        if not 0 <= lane < self.cfg.lanes:
            raise ValueError(
                f"lane {lane} out of range for {self.cfg.lanes} lanes")
        one = ens_lib.member(self.states, lane)
        mask = tl_lib.ids_to_mask32(pe_ids, one.tl.words)
        state, done = batch_lib.cancel_one(
            one, t_s, t_e, mask,
            require_pending=self.cfg.auto_release,
            max_growths=self.growth_budget)
        if state.tl.capacity != one.tl.capacity or \
                state.pending_capacity != one.pending_capacity:
            # growth must stay collective (shared static lane shape)
            self.states = self._put(ens_lib.grow_ensemble(
                self.states, state.tl.capacity,
                state.pending_capacity))
            self.counters["growths"] += 1
            one = ens_lib.member(self.states, lane)
            state, done = batch_lib.cancel_one(
                one, t_s, t_e, mask,
                require_pending=self.cfg.auto_release,
                max_growths=self.growth_budget)
        self.states = self._put(
            ens_lib.set_member(self.states, lane, state))
        self.counters["cancelled"] += int(done)
        return done

    def find_allocation(self, req, policy, t_now=None):
        raise NotImplementedError(
            "ensemble sessions decide per lane; use offer() with "
            "per-lane streams")

    add_allocation = delete_allocation = find_allocation

    def records(self, lane: int = 0):
        times = np.asarray(self.states.tl.times[lane])
        occ = np.asarray(self.states.tl.occ[lane])
        return [(int(t), frozenset(batch_lib.mask32_to_ids(o)))
                for t, o in zip(times, occ) if t < T_INF]

    def snapshot(self):
        self._sync_counters()
        self._retained = True
        return (self.states,
                [r.snapshot() for r in self.rings]
                if self.rings else None)

    def restore(self, payload):
        states, ring_snaps = payload
        self.states = states
        self._retained = True
        self._acc_dev = None
        if self.rings and ring_snaps is not None:
            for r, s in zip(self.rings, ring_snaps):
                r.restore(s)

    def _refresh_dev_metrics(self) -> None:
        """One fused device read of every state-derived counter."""
        s = self.states
        vals: Dict[str, Any] = _work_counters(s)
        if self.cfg.backfilling:
            vals.update(
                n_parked_now=jnp.sum(s.park_seq != T_INF,
                                     dtype=jnp.int32),
                n_parked=jnp.sum(s.n_parked),
                n_promoted=jnp.sum(s.n_promoted),
                n_moved=jnp.sum(s.n_moved))
        if s.tenants is not None:
            vals["tenants"] = {
                f: getattr(s.tenants, f)
                for f in _PER_TENANT + ("occ_ewma",)}
        host = _device_fetch(vals)
        self._dev_metrics = {
            k: to_host(v) if k == "tenants" else int(v)
            for k, v in host.items()}

    def metrics(self):
        self._sync_counters()
        if self._dev_metrics is None:
            self._refresh_dev_metrics()
        cap, pend = self._capacities()
        out = dict(capacity=cap, pending_capacity=pend,
                   search_path=self._search_path(cap, self.cfg.n_pe),
                   placement_shards=data_shards(self.mesh)
                   if self.mesh is not None else 1)
        out.update(self._dev_metrics)
        if self.rings:
            out.update(ring_capacity=self.cfg.ring_capacity,
                       ring_staged=sum(r.count for r in self.rings),
                       ring_wrapped=any(r.wrapped for r in self.rings))
        if self.cfg.backfilling:
            out["park_capacity"] = int(
                self.states.park_seq.shape[-1])
        return out


class _PartitionBackend(_BackendBase):
    """Cluster partitions (machine slices) with routed bulk admission."""

    def __init__(self, cfg, counters):
        super().__init__(cfg, counters)
        from repro.runtime.fleet import PartitionedCore

        bf = cfg.backfill if isinstance(cfg.backfill, str) \
            else cfg.backfill[0]
        self.engine = PartitionedCore(
            cfg.n_pe, cfg.n_partitions, capacity=cfg.capacity,
            pending_capacity=cfg.pending_capacity,
            use_kernel=cfg.use_kernel, placement=cfg.placement,
            park_capacity=cfg.park_capacity, backfill=bf,
            auto_release=cfg.auto_release,
            index_tile=cfg.index_tile)
        # partitions enforce tenancy at the host router (the lane
        # states keep tenants=None): a HostTenantAccounts gate before
        # routing, and a completion ledger attributing each held
        # reservation to its tenant for release / overdue reaping
        self._accounts = None
        self._grace = None
        if cfg.tenancy:
            from repro.tenancy import HostTenantAccounts
            self._accounts = HostTenantAccounts(cfg.tenants)
            self._grace = cfg.tenants.grace
        self._ledger: list = []   # heap of (t_e, seq, tid, t_s, ids)
        self._lseq = 0

    def _ledger_release(self, t: int) -> None:
        """Mirror the engine's completion releases ending by ``t``."""
        while self._ledger and self._ledger[0][0] <= t:
            _, _, tid, _, _ = heapq.heappop(self._ledger)
            self._accounts.release(tid)

    def offer(self, requests, *, policy, routing, flush) -> OfferResult:
        routing = routing or self.cfg.routing
        if routing not in ROUTINGS:
            raise ValueError(
                f"unknown routing {routing!r}; pick one of {ROUTINGS}")
        if not flush:
            raise ValueError(
                "flush=False staging is a ring-buffer (device "
                "session) feature; partitioned sessions decide every "
                "offer immediately")
        reqs = list(requests)
        self.counters["offered"] += len(reqs)
        if not reqs:
            return _empty_result()
        pol = self.resolve_policy(policy)
        if self._accounts is None:
            allocs = self.engine.admit_stream_allocations(
                reqs, pol, routing)
        else:
            allocs = self._offer_gated(reqs, pol, routing)
        self.counters["accepted"] += \
            sum(a is not None for a in allocs)
        self.counters["one_shot_scans"] += 1
        return OfferResult(decision=None, batch=None, valid=None,
                           _allocations=allocs)

    def _offer_gated(self, reqs, pol, routing):
        """Quota-gated routing: reject over-quota before the probe.

        Same gate order as the device path (DESIGN.md §10): releases
        ending by the arrival settle first (so ``live`` reflects the
        post-release population), then the float32 quota /
        concurrency check, then routing for requests that pass.
        Occupancy EWMA is not tracked at the router (no single
        machine occupancy exists across partitions): ``occ_q=0``.
        """
        acc = self._accounts
        allocs: List[Optional[Allocation]] = []
        for req in reqs:
            if acc.n_tenants and req.tenant >= acc.n_tenants:
                raise ValueError(
                    f"request tenant {req.tenant} out of range "
                    f"[0, {acc.n_tenants}) for this session's "
                    f"TenantSpec")
            if self.cfg.auto_release:
                self._ledger_release(req.t_a)
            tid = acc.clip_tid(req.tenant)
            if not acc.allowed(tid, req.n_pe, req.t_du):
                acc.record(tid, accepted=False, blocked=True,
                           parked=False)
                allocs.append(None)
                continue
            alloc = self.engine.admit_stream_allocations(
                [req], pol, routing)[0]
            acc.record(tid, accepted=alloc is not None,
                       blocked=False, parked=False,
                       t_e=alloc.t_e if alloc else -1,
                       t_r=req.t_r, t_du=req.t_du, n_pe=req.n_pe)
            if alloc is not None:
                heapq.heappush(
                    self._ledger,
                    (alloc.t_e, self._lseq, tid, alloc.t_s,
                     tuple(alloc.pe_ids)))
                self._lseq += 1
            allocs.append(alloc)
        return allocs

    def tick(self, t: int) -> int:
        # with auto_release=False the client owns completion release
        # (cancel/delete_allocation); otherwise advance every lane's
        # pending buffer in one dispatch
        if not self.cfg.auto_release:
            return self._reap(t)
        before = int(np.asarray(
            self.engine.states.n_released).sum())
        self.engine.release_until(t)
        if self._accounts is not None:
            self._ledger_release(t)
        released = int(np.asarray(
            self.engine.states.n_released).sum()) - before
        self.counters["released"] += released
        return released

    def _reap(self, t: int) -> int:
        """Ledger-driven overdue reaping at the host router."""
        if self._accounts is None or self._grace is None:
            return 0
        reaped = 0
        cutoff = t - self._grace
        while self._ledger and self._ledger[0][0] <= cutoff:
            t_e, _, tid, t_s, ids = heapq.heappop(self._ledger)
            self.engine.delete_allocation(t_s, t_e, list(ids))
            self._accounts.reap(tid)
            reaped += 1
        self.counters["reaped"] += reaped
        return reaped

    def pending(self, lane: int = 0) -> list:
        if not 0 <= lane < self.cfg.n_partitions:
            raise ValueError(
                f"lane {lane} out of range for "
                f"{self.cfg.n_partitions} partitions")
        if not self.cfg.backfilling:
            return []
        return batch_lib.parked_entries(
            ens_lib.member(self.engine.states, lane))

    def cancel(self, t_s, t_e, pe_ids, lane: int = 0) -> bool:
        if lane != 0:
            raise ValueError(
                "partitioned sessions address reservations by global "
                "chip ids, not lanes")
        if not self.cfg.auto_release:
            self.engine.delete_allocation(t_s, t_e, list(pe_ids))
            self._ledger_cancel(t_s, t_e, pe_ids)
            self.counters["cancelled"] += 1
            return True
        # auto-release lanes track completions in the pending buffer:
        # cancel through cancel_one so the slot clears with the
        # interval (a blind delete would double-release at tick)
        eng = self.engine
        part, local = eng._split(pe_ids)
        state = ens_lib.member(eng.states, part)
        mask = tl_lib.ids_to_mask32(local, state.tl.words)
        state, done = batch_lib.cancel_one(
            state, t_s, t_e, mask, require_pending=True,
            max_growths=0)
        eng.states = eng._put(
            ens_lib.set_member(eng.states, part, state))
        if done:
            eng._bump_load(part, -(t_e - t_s) * len(local))
            self._ledger_cancel(t_s, t_e, pe_ids)
        self.counters["cancelled"] += int(done)
        return done

    def _ledger_cancel(self, t_s, t_e, pe_ids) -> None:
        """Drop a cancelled reservation's ledger entry (if tracked)."""
        if self._accounts is None:
            return
        key = (t_e, t_s, tuple(pe_ids))
        for i, ent in enumerate(self._ledger):
            if (ent[0], ent[3], ent[4]) == key:
                self._accounts.release(ent[2])
                self._ledger.pop(i)
                heapq.heapify(self._ledger)
                return

    def snapshot(self):
        tenancy = None
        if self._accounts is not None:
            tenancy = (copy.deepcopy(self._accounts),
                       list(self._ledger), self._lseq)
        return (self.engine.states, list(self.engine.load),
                self.engine._rr, tenancy)

    def restore(self, payload):
        states, load, rr, tenancy = payload
        self.engine.states = states
        self.engine.load = list(load)
        self.engine._rr = rr
        if tenancy is not None:
            accounts, ledger, lseq = tenancy
            self._accounts = copy.deepcopy(accounts)
            self._ledger = list(ledger)
            self._lseq = lseq

    def metrics(self):
        cap, pend = ens_lib.lane_capacity(self.engine.states)
        out = dict(capacity=cap, pending_capacity=pend,
                   search_path=self._search_path(
                       cap, self.engine.chips_per_part),
                   chips_per_partition=self.engine.chips_per_part,
                   partition_load=list(self.engine.load),
                   dispatches=self.engine.dispatches,
                   match_rounds=self.engine.last_match_rounds)
        out.update({k: int(v) for k, v in jax.device_get(
            _work_counters(self.engine.states)).items()})
        if self.cfg.backfilling:
            s = self.engine.states
            out.update(
                # per-lane queue depth (park_capacity reads axis 0,
                # which is the lane axis on a stacked state)
                park_capacity=int(s.park_seq.shape[-1]),
                n_parked_now=int(np.asarray(
                    s.park_seq != T_INF).sum()),
                n_parked=int(np.asarray(s.n_parked).sum()),
                n_promoted=int(np.asarray(s.n_promoted).sum()),
                n_moved=int(np.asarray(s.n_moved).sum()))
        if self._accounts is not None:
            out["tenants"] = self._accounts.snapshot()
            out["ledger_depth"] = len(self._ledger)
        return out


class _HostBackend(_BackendBase):
    """Host/list engines behind the same verb set (reference path)."""

    def __init__(self, cfg, counters):
        super().__init__(cfg, counters)
        self.engine = _make_engine(cfg.n_pe, cfg.engine,
                                   **(cfg.engine_kwargs or {}))
        self._completions: list = []     # heap of (t_e, seq, t_s, ids)
        self._seq = 0
        self._last_ta = 0                # arrival-order watermark

    def _pes(self, ids):
        return set(ids) if self.cfg.engine == "list" else list(ids)

    def add_allocation(self, t_s, t_e, pes):
        self.engine.add_allocation(t_s, t_e, self._pes(pes))

    def delete_allocation(self, t_s, t_e, pes):
        self.engine.delete_allocation(t_s, t_e, self._pes(pes))

    def _release_due(self, t: int) -> int:
        n = 0
        while self._completions and self._completions[0][0] <= t:
            t_e, _, t_s, ids = heapq.heappop(self._completions)
            self.engine.delete_allocation(t_s, t_e, self._pes(ids))
            n += 1
        self.counters["released"] += n
        return n

    def offer(self, requests, *, policy, routing, flush) -> OfferResult:
        if routing is not None:
            raise ValueError("routing applies to partitioned sessions")
        if not flush:
            raise ValueError(
                "flush=False staging is a ring-buffer (device "
                "session) feature; host/list sessions decide every "
                "offer immediately")
        pol = self.resolve_policy(policy)
        reqs = list(requests)
        _check_demands(None, reqs)
        batch_lib.check_arrival_order(reqs, self._last_ta)
        self.counters["offered"] += len(reqs)
        if not reqs:
            return _empty_result()
        W = tl_lib.n_words(self.cfg.n_pe)
        rows: List[Tuple] = []
        allocs: List[Optional[Allocation]] = []
        for req in reqs:
            if self.cfg.auto_release:
                self._release_due(req.t_a)
            alloc = self.engine.find_allocation(req, pol,
                                                t_now=req.t_a)
            allocs.append(alloc)
            if alloc is None:
                rows.append((False, -1, -1, np.zeros(W, np.uint32),
                             0, 0, 0))
                continue
            self.engine.add_allocation(alloc.t_s, alloc.t_e,
                                       self._pes(alloc.pe_ids))
            if self.cfg.auto_release:
                heapq.heappush(
                    self._completions,
                    (alloc.t_e, self._seq, alloc.t_s,
                     tuple(alloc.pe_ids)))
                self._seq += 1
            r = alloc.rectangle
            rows.append((True, alloc.t_s, alloc.t_e,
                         _mask_np(alloc.pe_ids, W),
                         r.n_free, r.t_begin, r.t_end))
        self._last_ta = reqs[-1].t_a
        self.counters["accepted"] += \
            sum(a is not None for a in allocs)
        dec = Decision(
            accepted=np.asarray([r[0] for r in rows]),
            t_s=np.asarray([r[1] for r in rows], np.int32),
            t_e=np.asarray([r[2] for r in rows], np.int32),
            pe_mask=np.stack([r[3] for r in rows]),
            n_free=np.asarray([r[4] for r in rows], np.int32),
            t_begin=np.asarray([r[5] for r in rows], np.int32),
            t_end=np.asarray([r[6] for r in rows], np.int32),
            parked=np.zeros(len(rows), bool))
        return OfferResult(
            decision=dec, batch=None,
            valid=np.ones(len(reqs), bool), _allocations=allocs)

    def tick(self, t: int) -> int:
        if not self.cfg.auto_release:
            return 0
        return self._release_due(t)

    def cancel(self, t_s, t_e, pe_ids, lane: int = 0) -> bool:
        if lane != 0:
            raise ValueError("lane applies to ensemble sessions")
        key = (t_s, t_e, tuple(pe_ids))
        if self.cfg.auto_release:
            match = [c for c in self._completions
                     if (c[2], c[0], c[3]) == key]
            if not match:
                return False
            self._completions.remove(match[0])
            heapq.heapify(self._completions)
        self.engine.delete_allocation(t_s, t_e, self._pes(pe_ids))
        self.counters["cancelled"] += 1
        return True

    def snapshot(self):
        return (copy.deepcopy(self.engine),
                list(self._completions), self._seq, self._last_ta)

    def restore(self, payload):
        engine, completions, seq, last_ta = payload
        self.engine = copy.deepcopy(engine)
        self._completions = list(completions)
        self._seq = seq
        self._last_ta = last_ta

    def metrics(self):
        return dict(n_pending=len(self._completions))
