"""Fused device-resident admission: ``(state, request) -> (state, decision)``.

The per-request engine pays a host round-trip per job: ``find_allocation``
syncs ``found``/the PE mask back to Python, which then issues ``update``
as a second dispatch.  This module makes the scheduler core functional
(DESIGN.md §3): :class:`~repro.core.timeline.SchedulerState` carries the
dense timeline plus a pending-release buffer of committed reservations,
:func:`admit` is one pure jitted step that fuses ``deleteAllocation`` of
due completions, ``findAllocation`` (Algorithm 3) and ``addAllocation``,
and :func:`admit_stream` scans a struct-of-arrays request batch through
that step with ``jax.lax.scan`` — whole experiments admit on-device.

Capacity overflow (timeline records or pending slots) latches
``state.overflow``; every later step becomes a no-op so the truncated
state is never consulted, and the host wrappers
(:func:`admit_stream_grow`, :func:`admit_one`) grow the state and
deterministically re-run the stream from its pre-run snapshot.

Streaming arrivals stage through the fixed-capacity
:class:`RequestRing` and leave as constant-shape chunks, which is what
lets :class:`repro.api.Session` admit continuously with zero
re-padding and zero recompilation after warmup.
"""
from __future__ import annotations

import functools
import warnings
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_lib
from repro.core import timeline as tl_lib
from repro.core.policies import policy_index
from repro.core.timeline import SchedulerState
from repro.tenancy import table as tenancy_lib
from repro.core.types import (
    Allocation,
    ARRequest,
    BackfillMode,
    Rectangle,
    T_INF,
    backfill_index,
)

# Growth retries before the host wrappers give up (2**8 x the initial
# capacity is far beyond any stream the int32 timeline can describe).
MAX_DOUBLINGS = 8

# Traced backfill-mode ids (see repro.core.types.BackfillMode).
BF_NONE = backfill_index(BackfillMode.NONE)
BF_EASY = backfill_index(BackfillMode.EASY)
BF_CONSERVATIVE = backfill_index(BackfillMode.CONSERVATIVE)


def as_backfill_id(backfill) -> jax.Array:
    """Any backfill spelling -> its traced int32 id.

    Accepts a mode name / :class:`~repro.core.types.BackfillMode` /
    validated id, an already-traced array (passed through), or a
    1-tuple (the single-lane spelling of the per-lane config form).
    """
    if isinstance(backfill, jax.Array):
        return backfill
    if isinstance(backfill, (tuple, list)):
        if len(backfill) != 1:
            raise ValueError(
                f"{len(backfill)} backfill modes for a single lane "
                f"(per-lane tuples belong to ensemble callers)")
        backfill = backfill[0]
    return jnp.int32(backfill_index(backfill))


class RequestBatch(NamedTuple):
    """Struct-of-arrays AR request stream, sorted by arrival time.

    Each field is ``int32[N]``; a slice along the leading axis is a
    single request, which is exactly what ``lax.scan`` feeds to the
    fused step.  ``tenant`` is the optional ownership column of
    multi-tenant sessions (DESIGN.md §10): ``None`` — the default —
    contributes no pytree leaf, so zero-tenant batches keep their
    exact pre-tenancy structure (and compiled graphs).  ``demand`` is
    the optional multi-resource tail column (DESIGN.md §11):
    ``int32[N, R-1]`` secondary-plane demands (plane 0 *is* ``n_pe``);
    ``None`` for single-resource sessions, again leaf-free.
    """

    t_a: jax.Array
    t_r: jax.Array
    t_du: jax.Array
    t_dl: jax.Array
    n_pe: jax.Array
    tenant: Optional[jax.Array] = None
    demand: Optional[jax.Array] = None   # int32[N, R-1] tail demands


#: The paper's five request coordinates — the always-present subset of
#: :class:`RequestBatch` fields.  Staging/padding sites iterate this
#: (not ``RequestBatch._fields``) so the optional tenant column is
#: materialised only for multi-tenant sessions.
REQ_FIELDS: Tuple[str, ...] = ("t_a", "t_r", "t_du", "t_dl", "n_pe")


def _req_field(r: ARRequest, f: str):
    """Read one staging column off a host request.

    ``demand<k>`` columns (k >= 1) read plane ``k`` of the request's
    demand vector; requests without one stage zeros there (PEs only).
    Everything else is a plain attribute.
    """
    if f.startswith("demand"):
        k = int(f[len("demand"):])
        return 0 if r.demand is None else int(r.demand[k])
    return getattr(r, f)


def _demand_fields(extra_demand: int) -> Tuple[str, ...]:
    """Staging column names of the demand tail (planes 1..R-1)."""
    return tuple(f"demand{k}" for k in range(1, extra_demand + 1))


def _fields_to_batch(fields: dict) -> RequestBatch:
    """Column dict (possibly with demand<k> columns) -> RequestBatch.

    The per-plane demand columns are stacked into the single
    ``int32[..., R-1]`` tail array along a new trailing axis; without
    any such column ``demand`` stays ``None`` (leaf-free).
    """
    plain = {k: jnp.asarray(v) for k, v in fields.items()
             if not k.startswith("demand")}
    dcols = sorted((k for k in fields if k.startswith("demand")),
                   key=lambda k: int(k[len("demand"):]))
    if dcols:
        plain["demand"] = jnp.stack(
            [jnp.asarray(fields[k], jnp.int32) for k in dcols],
            axis=-1)
    return RequestBatch(**plain)


class Decision(NamedTuple):
    """Per-request admission outcome (scalar per step, ``[N]`` stacked)."""

    accepted: jax.Array   # bool
    t_s: jax.Array        # int32; -1 when rejected
    t_e: jax.Array        # int32; -1 when rejected
    pe_mask: jax.Array    # uint32[W]; 0 when rejected
    n_free: jax.Array     # int32 winning-rectangle free PEs
    t_begin: jax.Array    # int32 winning-rectangle begin
    t_end: jax.Array      # int32 winning-rectangle end
    parked: jax.Array     # bool: accepted into the deferral queue
    #                       (reservation may still move under EASY)


def requests_to_batch(jobs: Sequence[ARRequest],
                      with_tenant: bool = False,
                      extra_demand: int = 0) -> RequestBatch:
    """Pack host requests into the device struct-of-arrays layout.

    ``extra_demand`` (= R - 1) adds the multi-resource tail column;
    jobs without a demand vector contribute zero tail demand.
    """
    return RequestBatch(
        t_a=jnp.asarray([j.t_a for j in jobs], jnp.int32),
        t_r=jnp.asarray([j.t_r for j in jobs], jnp.int32),
        t_du=jnp.asarray([j.t_du for j in jobs], jnp.int32),
        t_dl=jnp.asarray([j.t_dl for j in jobs], jnp.int32),
        n_pe=jnp.asarray([j.n_pe for j in jobs], jnp.int32),
        tenant=jnp.asarray([j.tenant for j in jobs], jnp.int32)
        if with_tenant else None,
        demand=jnp.asarray(
            [[_req_field(j, f) for f in _demand_fields(extra_demand)]
             for j in jobs], jnp.int32) if extra_demand else None,
    )


def request_struct(req: ARRequest,
                   with_tenant: bool = False,
                   extra_demand: int = 0) -> RequestBatch:
    """A single request as a scalar struct (for :func:`admit`)."""
    return RequestBatch(
        t_a=jnp.int32(req.t_a), t_r=jnp.int32(req.t_r),
        t_du=jnp.int32(req.t_du), t_dl=jnp.int32(req.t_dl),
        n_pe=jnp.int32(req.n_pe),
        tenant=jnp.int32(req.tenant) if with_tenant else None,
        demand=jnp.asarray(
            [_req_field(req, f) for f in _demand_fields(extra_demand)],
            jnp.int32) if extra_demand else None)


def filler_request(n_pe: int, t_a: int) -> ARRequest:
    """A never-feasible padding request (asks for ``n_pe + 1`` PEs).

    Rejected without touching the timeline; it carries the arrival time
    of the last real request *already admitted* so it can never reorder
    releases (a filler stamped past a still-staged request would
    trigger its releases early).
    """
    return ARRequest(t_a=t_a, t_r=t_a, t_du=1, t_dl=t_a + 1,
                     n_pe=n_pe + 1)


def check_arrival_order(requests: Sequence[ARRequest],
                        last_t_a: int) -> None:
    """Validate t_a monotonicity of a whole slice before any mutation,
    so a rejected offer/push leaves the caller's state untouched."""
    last = last_t_a
    for r in requests:
        if r.t_a < last:
            raise ValueError(
                f"requests must be arrival-ordered across offers: "
                f"got t_a={r.t_a} after t_a={last}")
        last = r.t_a


def pad_streams(streams, n_pe: int, with_tenant: bool = False,
                extra_demand: int = 0
                ) -> Tuple[RequestBatch, np.ndarray]:
    """Stack variable-length request streams into ``[C, N]`` + mask.

    Padding requests (:func:`filler_request`) ask for ``n_pe + 1`` PEs
    — never feasible, so they are rejected without touching the
    timeline; they arrive after the stream's last real request, so they
    cannot reorder releases either.  Decisions at padded positions must
    be masked out with the returned ``valid`` array (the ensemble
    consumers do).  ``with_tenant`` adds the tenant ownership column
    (filler positions carry tenant 0, which the admit step never
    charges — filler is detected by its infeasible PE ask).
    """
    C = len(streams)
    N = max((len(s) for s in streams), default=0)
    N = max(N, 1)
    names = (REQ_FIELDS + (("tenant",) if with_tenant else ())
             + _demand_fields(extra_demand))
    fields = {f: np.zeros((C, N), np.int32) for f in names}
    valid = np.zeros((C, N), bool)
    for c, stream in enumerate(streams):
        last = stream[-1].t_a if stream else 0
        for i in range(N):
            if i < len(stream):
                r = stream[i]
                valid[c, i] = True
            else:
                r = filler_request(n_pe, last)
            for f in names:
                fields[f][c, i] = _req_field(r, f)
    return _fields_to_batch(fields), valid


def scatter_streams(requests: Sequence[ARRequest],
                    lanes: Sequence[int], n_lanes: int, n_pe: int,
                    extra_demand: int = 0
                    ) -> Tuple[RequestBatch, np.ndarray, list]:
    """Group routed requests into per-lane padded streams.

    ``lanes[i]`` is the lane assigned to ``requests[i]``; the return
    value is ``(batch, valid, slots)`` where ``batch``/``valid`` come
    from :func:`pad_streams` over ``n_lanes`` streams and ``slots[i] =
    (lane, pos)`` locates request i's decision in the ``[C, N]``
    layout.  Within a lane the arrival order of the input sequence is
    preserved — the grouped commit admits each lane's requests in the
    same order a sequential router would have.
    """
    streams: list = [[] for _ in range(n_lanes)]
    slots = []
    for req, lane in zip(requests, lanes):
        slots.append((int(lane), len(streams[lane])))
        streams[lane].append(req)
    batch, valid = pad_streams(streams, n_pe,
                               extra_demand=extra_demand)
    return batch, valid, slots


class RequestRing:
    """Fixed-capacity FIFO staging ring for streaming admission.

    The online path of :class:`repro.api.Session`: arriving requests
    are staged here (host-side numpy storage — arrivals come from the
    host anyway) and leave as *fixed-shape* device chunks via
    :meth:`pop_chunk`, so the jitted ``admit_stream`` sees constant
    shapes across calls no matter how the arrivals are grouped.  Slots
    are reused modulo ``capacity``; the ring never re-pads or
    reallocates, and a full ring rejects the push (callers drain first).
    """

    def __init__(self, capacity: int, with_tenant: bool = False,
                 extra_demand: int = 0):
        if capacity < 1:
            raise ValueError("ring capacity must be >= 1")
        self.capacity = capacity
        self._fields = (REQ_FIELDS + (("tenant",) if with_tenant
                                      else ())
                        + _demand_fields(extra_demand))
        self._buf = {f: np.zeros(capacity, np.int32)
                     for f in self._fields}
        self._head = 0          # index of the oldest staged request
        self.count = 0          # staged (not yet popped) requests
        self.pushed = 0         # lifetime pushes
        self.popped = 0         # lifetime pops (valid only)
        self.wrapped = False    # a slot has been reused (index wrapped)
        self.last_t_a = 0       # arrival time of the newest push
        self.last_popped_t_a = 0  # arrival time of the newest pop

    @property
    def free(self) -> int:
        return self.capacity - self.count

    def push(self, requests: Sequence[ARRequest]) -> None:
        """Stage arrival-ordered requests; raises when they don't fit.

        All-or-nothing: the whole slice is validated before any slot
        is written, so a rejected push leaves the ring untouched.
        """
        if len(requests) > self.free:
            raise OverflowError(
                f"ring full: {len(requests)} requests, "
                f"{self.free}/{self.capacity} slots free — pop a chunk "
                f"first or configure a larger ring_capacity")
        check_arrival_order(requests, self.last_t_a)
        for r in requests:
            i = (self._head + self.count) % self.capacity
            if self.pushed >= self.capacity:
                self.wrapped = True
            for f in self._fields:
                self._buf[f][i] = _req_field(r, f)
            self.count += 1
            self.pushed += 1
            self.last_t_a = r.t_a

    def _pop_chunk_host(self, chunk: int, n_pe: int,
                        n: Optional[int] = None):
        """As :meth:`pop_chunk` but numpy fields (for lane stacking).

        ``n`` caps how many staged requests to dequeue (default: up to
        ``chunk``); the remaining positions hold filler.
        """
        n = min(chunk, self.count) if n is None \
            else min(n, chunk, self.count)
        idx = (self._head + np.arange(chunk)) % self.capacity
        fields = {f: self._buf[f][idx].copy()
                  for f in self._fields}
        valid = np.arange(chunk) < n
        if n > 0:
            self.last_popped_t_a = int(fields["t_a"][n - 1])
        if n < chunk:
            # filler is stamped with the newest *popped* arrival, never
            # a still-staged one — stamping past staged requests would
            # release their predecessors early and change decisions
            pad = filler_request(n_pe, self.last_popped_t_a)
            for f in self._fields:
                fields[f][n:] = _req_field(pad, f)
        self._head = (self._head + n) % self.capacity
        self.count -= n
        self.popped += n
        return fields, valid

    def pop_chunk(self, chunk: int,
                  n_pe: int) -> Tuple[RequestBatch, np.ndarray]:
        """Dequeue up to ``chunk`` requests as one fixed-shape batch.

        Always returns arrays of length ``chunk``: missing tail
        positions hold :func:`filler_request` padding and are flagged
        ``False`` in the returned ``valid`` mask.
        """
        fields, valid = self._pop_chunk_host(chunk, n_pe)
        return _fields_to_batch(fields), valid

    def snapshot(self) -> dict:
        """Copy of the ring's mutable state (see :meth:`restore`)."""
        return {"buf": {f: v.copy() for f, v in self._buf.items()},
                "head": self._head, "count": self.count,
                "pushed": self.pushed, "popped": self.popped,
                "wrapped": self.wrapped, "last_t_a": self.last_t_a,
                "last_popped_t_a": self.last_popped_t_a}

    def restore(self, snap: dict) -> None:
        for f, v in snap["buf"].items():
            self._buf[f][:] = v
        self._head = snap["head"]
        self.count = snap["count"]
        self.pushed = snap["pushed"]
        self.popped = snap["popped"]
        self.wrapped = snap["wrapped"]
        self.last_t_a = snap["last_t_a"]
        self.last_popped_t_a = snap["last_popped_t_a"]


def pop_chunk_ensemble(rings: Sequence[RequestRing], chunk: int,
                       n_pe: int, full_only: bool = False
                       ) -> Tuple[RequestBatch, np.ndarray]:
    """Pop one fixed-shape chunk from every lane's ring, stacked.

    Returns an ``[E, chunk]`` :class:`RequestBatch` plus the matching
    ``valid`` mask; lanes with fewer than ``chunk`` staged requests are
    padded with :func:`filler_request`.  With ``full_only`` a lane
    below a full chunk keeps its requests staged and contributes only
    filler (the ``flush=False`` contract: partial remainders wait).
    """
    names = rings[0]._fields if rings else REQ_FIELDS
    fields = {f: np.zeros((len(rings), chunk), np.int32)
              for f in names}
    valid = np.zeros((len(rings), chunk), bool)
    for e, ring in enumerate(rings):
        n = 0 if full_only and ring.count < chunk else None
        lane_fields, lane_valid = ring._pop_chunk_host(chunk, n_pe,
                                                       n=n)
        for f in names:
            fields[f][e] = lane_fields[f]
        valid[e] = lane_valid
    return _fields_to_batch(fields), valid


def _where_tree(pred, if_true, if_false):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), if_true, if_false)


def _promote_due(state: SchedulerState,
                 t_now: jax.Array) -> SchedulerState:
    """Commit parked reservations whose start time has arrived.

    A deferral-queue entry with ``t_s <= t_now`` is running (or about
    to): its reservation becomes immovable and moves to the
    pending-release buffer, freeing the queue slot.  All due entries
    promote in one vectorised pass (DESIGN.md §7): the k-th due entry
    in promotion order takes the k-th free pending slot in index order
    — exactly the assignment the old one-at-a-time ``while_loop``
    produced, without threading the full state through a loop carry.
    The whole pass sits behind ``lax.cond`` on a due-entry predicate,
    so steps with an idle queue pay one ``any`` reduction.

    Promotion order is FCFS (sequence number); multi-tenant states
    rank by the weighted fair-share key instead — highest
    ``weight * wait`` first, seq breaking ties — which reduces
    *bit-identically* to FCFS under equal weights (DESIGN.md §10).
    """
    t_now = jnp.asarray(t_now, jnp.int32)
    K = state.pending_capacity

    def promote(s: SchedulerState) -> SchedulerState:
        due = (s.park_seq < T_INF) & (s.park_ts <= t_now)
        free = s.pend_te == T_INF
        n_free = jnp.sum(free).astype(jnp.int32)
        n_due = jnp.sum(due).astype(jnp.int32)
        seq = jnp.where(due, s.park_seq, T_INF)
        if s.tenants is not None:
            # weighted fair-share rank: count due entries strictly
            # ahead (higher key, or equal key and earlier seq)
            key = tenancy_lib.fair_key(s.tenants, t_now)
            ahead = due[None, :] & (
                (key[None, :] > key[:, None])
                | ((key[None, :] == key[:, None])
                   & (seq[None, :] < seq[:, None])))
            rank = jnp.sum(ahead, axis=1).astype(jnp.int32)
        else:
            # FCFS rank among due entries (sequence numbers are unique)
            rank = jnp.sum(
                (seq[None, :] < seq[:, None]) & due[None, :],
                axis=1).astype(jnp.int32)
        promoted = due & (rank < n_free)
        # k-th free pending slot (index order) for FCFS rank k
        frank = (jnp.cumsum(free) - 1).astype(jnp.int32)
        slot_of_rank = jnp.full((K + 1,), K, jnp.int32).at[
            jnp.where(free, frank, K)].set(
            jnp.arange(K, dtype=jnp.int32))
        dest = jnp.where(promoted,
                         slot_of_rank[jnp.clip(rank, 0, K)], K)

        def scat(pend, park, fill):
            ext = jnp.concatenate([pend, pend[:1]])
            return ext.at[dest].set(
                jnp.where(_bcast(promoted, park), park, fill))[:K]

        ovf = n_due > n_free
        n_prom = jnp.minimum(n_due, n_free)
        used0 = jnp.sum(~free).astype(jnp.int32)
        out = s._replace(
            pend_ts=scat(s.pend_ts, s.park_ts, jnp.int32(0)),
            pend_te=scat(s.pend_te, s.park_te, jnp.int32(0)),
            pend_mask=scat(s.pend_mask, s.park_mask, jnp.uint32(0)),
            park_ts=jnp.where(promoted, T_INF, s.park_ts),
            park_te=jnp.where(promoted, T_INF, s.park_te),
            park_mask=jnp.where(promoted[:, None], jnp.uint32(0),
                                s.park_mask),
            park_seq=jnp.where(promoted, T_INF, s.park_seq),
            n_promoted=s.n_promoted + n_prom,
            overflow=s.overflow | ovf,
            hw_pending=jnp.maximum(
                s.hw_pending,
                jnp.where(ovf, jnp.int32(K + 1), used0 + n_prom)),
        )
        if s.tenants is not None:
            # ownership follows the reservation: queue slot -> pending
            # slot (the scatter reuses `dest`); freed queue slots
            # return to unowned
            tn = s.tenants
            out = out._replace(tenants=tn._replace(
                pend_tenant=scat(tn.pend_tenant, tn.park_tenant,
                                 jnp.int32(-1)),
                park_tenant=jnp.where(promoted, -1, tn.park_tenant),
                park_ta=jnp.where(promoted, 0, tn.park_ta),
            ))
        return out

    pred = (jnp.any((state.park_seq < T_INF)
                    & (state.park_ts <= t_now)) & ~state.overflow)
    return jax.lax.cond(pred, promote, lambda s: s, state)


def _bcast(pred: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcast a [K] predicate against [K]- or [K, W]-shaped data."""
    return pred if like.ndim == 1 else pred[:, None]


# Static batch width of the fused multi-release: one `update_many`
# deletes up to this many due reservations per pass.  Typical steps
# have 0-2 due completions, so one pass nearly always suffices while
# the scratch rows stay at S + 2 * chunk.
RELEASE_CHUNK = 8


def release_due(state: SchedulerState, t_now: jax.Array) -> SchedulerState:
    """Delete every pending reservation with ``t_e <= t_now``.

    With a deferral queue (``park_capacity > 0``) parked reservations
    whose start has arrived are promoted into the pending-release
    buffer first, so a later due end is released in the same pass.
    This is the session ``tick`` entry; the fused admit step gates the
    promotion together with the retry sweep under one queue-work cond
    (see ``_admit_impl``).
    """
    if state.park_capacity:
        state = _promote_due(state, t_now)
    return _release_pending(state, t_now)


def _release_pending(state: SchedulerState, t_now: jax.Array, *,
                     count_reaped: bool = False) -> SchedulerState:
    """The release loop proper (no promotion).

    Reservations never share a PE over overlapping intervals, so the
    deletions commute and — the timeline being a canonical
    representation of its occupancy step function — one fused
    multi-interval delete is bit-identical to the old one-at-a-time
    loop (DESIGN.md §7).  Up to :data:`RELEASE_CHUNK` due reservations
    are deleted per ``update_many`` call; the ``while_loop`` only
    iterates when more completions than that fall due at once.

    Multi-tenant states return each freed slot's ownership and
    decrement the owner's live count; with ``count_reaped`` (the
    overdue-reaping entry, :func:`reap_until`) the deletion is also
    charged to the owner's ``n_reaped`` counter.
    """
    t_now = jnp.asarray(t_now, jnp.int32)
    CH = min(RELEASE_CHUNK, state.pending_capacity)
    W = state.pend_mask.shape[1]

    def pending_due(s: SchedulerState):
        return jnp.any(s.pend_te <= t_now) & ~s.overflow

    def release_chunk(s: SchedulerState) -> SchedulerState:
        due = s.pend_te <= t_now
        rank = jnp.cumsum(due) - 1
        chosen = due & (rank < CH)
        dest = jnp.where(chosen, rank, CH)
        sel_ts = jnp.zeros((CH + 1,), jnp.int32).at[dest].set(
            jnp.where(chosen, s.pend_ts, 0))[:CH]
        sel_te = jnp.zeros((CH + 1,), jnp.int32).at[dest].set(
            jnp.where(chosen, s.pend_te, 0))[:CH]
        sel_mk = jnp.zeros((CH + 1, W), jnp.uint32).at[dest].set(
            jnp.where(chosen[:, None], s.pend_mask,
                      jnp.uint32(0)))[:CH]
        act = jnp.zeros((CH + 1,), bool).at[dest].set(chosen)[:CH]
        new_tl, ovf, n_keep = tl_lib.update_many(
            s.tl, sel_ts, sel_te, sel_mk, act, is_add=False,
            with_count=True)
        # slots are freed even on overflow so the loop always makes
        # progress; an overflowed stream is re-run anyway.
        out = s._replace(
            tl=_where_tree(ovf, s.tl, new_tl),
            pend_ts=jnp.where(chosen, T_INF, s.pend_ts),
            pend_te=jnp.where(chosen, T_INF, s.pend_te),
            pend_mask=jnp.where(chosen[:, None], jnp.uint32(0),
                                s.pend_mask),
            n_released=s.n_released + jnp.where(
                ovf, 0, jnp.sum(chosen)).astype(jnp.int32),
            overflow=s.overflow | ovf,
            hw_records=jnp.maximum(s.hw_records, n_keep),
        )
        if s.tenants is not None:
            tn = s.tenants
            T = tn.n_tenants
            tid = jnp.clip(tn.pend_tenant, 0, T - 1)
            dec = jnp.where(chosen & (tn.pend_tenant >= 0), 1,
                            0).astype(jnp.int32)
            upd = dict(
                live=tn.live.at[tid].add(-dec),
                pend_tenant=jnp.where(chosen, -1, tn.pend_tenant))
            if count_reaped:
                upd["n_reaped"] = tn.n_reaped.at[tid].add(dec)
            out = out._replace(tenants=tn._replace(**upd))
        return out

    return jax.lax.while_loop(pending_due, release_chunk, state)


@jax.jit
def reap_step(state: SchedulerState, t_now: jax.Array,
              grace: jax.Array) -> SchedulerState:
    """Batch-delete reservations overdue past the tenant grace window.

    A reservation is overdue at ``t_now`` iff ``t_e + grace <=
    t_now``, i.e. ``t_e <= t_now - grace`` — so reaping *is* the
    fused release loop evaluated at the shifted cutoff, with the
    freed slots additionally charged to their owners' ``n_reaped``.
    Only meaningful for sessions that track completions themselves
    (``auto_release=False``): with auto-release every reservation is
    released at ``t_e``, before any grace window can elapse.
    """
    cutoff = (jnp.asarray(t_now, jnp.int32)
              - jnp.asarray(grace, jnp.int32))
    return _release_pending(state, cutoff, count_reaped=True)


def reap_until(state: SchedulerState, t_now: int, grace: int, *,
               max_growths: int = MAX_DOUBLINGS) -> SchedulerState:
    """Host wrapper of :func:`reap_step` with overflow growth.

    The tenancy half of ``Session.tick(t)`` (DESIGN.md §10): mirrors
    :func:`release_until`'s grow-and-rerun loop — a deletion can
    split a merged record and overflow the timeline.
    """
    start = state
    for attempt in range(max_growths + 1):
        out = reap_step(start, jnp.int32(t_now), jnp.int32(grace))
        if not bool(out.overflow):
            return out
        if attempt < max_growths:
            start = _grown(start, out)
    raise RuntimeError(
        f"reap_until still overflowing after {max_growths + 1} "
        f"attempts (last tried capacity {start.tl.capacity})")


def _retry_parked(state: SchedulerState, t_now: jax.Array,
                  bf: jax.Array, *, n_pe: int,
                  use_kernel: bool) -> SchedulerState:
    """EASY retry-on-release sweep: pull parked reservations earlier.

    In FCFS order each live queue entry is lifted off the timeline,
    re-searched with :func:`~repro.core.search.replacement_search`
    (earliest feasible start, the classic backfilling reservation), and
    moved only when the new start is *strictly earlier* — so the sweep
    can never delay anybody, the head included.  It runs only when the
    ``park_retry`` latch is set, i.e. after a cancellation freed
    *future* capacity: completions free only past records (durations
    are exact), and proactively compacting reservations toward ``now``
    crowds exactly the region where new arrivals' deadline windows
    live, hurting acceptance.  Conservative mode never sweeps: its
    reservations are frozen at admission, which keeps conservative
    decision-identical to ``none``.
    """
    Q = state.park_capacity
    t_now = jnp.asarray(t_now, jnp.int32)

    def sweep(s0: SchedulerState) -> SchedulerState:
        def body(_, carry):
            s, done = carry
            cand = (s.park_seq < T_INF) & ~done
            i = _select_next(s, cand, t_now)
            act = jnp.any(cand) & ~s.overflow
            t_du = s.park_te[i] - s.park_ts[i]
            tl1, ovf1, nk1 = tl_lib.update(
                s.tl, s.park_ts[i], s.park_te[i], s.park_mask[i],
                is_add=False, with_count=True)
            res = search_lib.replacement_search(
                tl1, s.park_tr[i], t_du, s.park_tdl[i],
                s.park_npe[i], jnp.int32(0), t_now, n_pe=n_pe,
                use_kernel=use_kernel, rspec=s.rspec,
                demand_tail=_park_demand(s, i),
                valid_mask=s.lane_valid)
            better = act & ~ovf1 & res.found & (res.t_s < s.park_ts[i])
            new_ts = jnp.where(better, res.t_s, s.park_ts[i])
            new_te = new_ts + t_du
            new_mk = jnp.where(better, res.pe_mask, s.park_mask[i])
            tl2, ovf2, nk2 = tl_lib.update(
                tl1, new_ts, new_te, new_mk, is_add=True,
                with_count=True)
            apply = act & ~ovf1 & ~ovf2
            s = s._replace(
                tl=_where_tree(apply, tl2, s.tl),
                park_ts=s.park_ts.at[i].set(
                    jnp.where(apply & better, new_ts, s.park_ts[i])),
                park_te=s.park_te.at[i].set(
                    jnp.where(apply & better, new_te, s.park_te[i])),
                park_mask=s.park_mask.at[i].set(
                    jnp.where(apply & better, new_mk, s.park_mask[i])),
                n_moved=s.n_moved
                + jnp.where(apply & better, 1, 0).astype(jnp.int32),
                overflow=s.overflow | (act & (ovf1 | ovf2)),
                hw_records=jnp.maximum(
                    s.hw_records,
                    jnp.where(act, jnp.maximum(nk1, nk2), 0)),
            )
            return (s, done.at[i].set(True))

        out, _ = jax.lax.fori_loop(
            0, Q, body, (s0, jnp.zeros((Q,), bool)))
        return out

    pred = ((bf == BF_EASY) & state.park_retry
            & jnp.any(state.park_seq < T_INF) & ~state.overflow)
    return jax.lax.cond(pred, sweep, lambda s: s, state)
    # NB: the caller (_admit_impl) consumes the park_retry latch per
    # admit step whether or not the sweep fired.


def _park_demand(s: SchedulerState, i: jax.Array):
    """Demand tail of queue entry ``i`` (``None`` on R=1 states)."""
    return None if s.park_dem is None else s.park_dem[i]


def _select_next(s: SchedulerState, cand: jax.Array,
                 t_now: jax.Array) -> jax.Array:
    """Index of the next queue entry to serve among ``cand`` slots.

    FCFS (minimum sequence number); multi-tenant states pick the
    maximum weighted fair-share key instead, seq breaking ties —
    bit-identical to FCFS under equal weights (DESIGN.md §10).  Safe
    when nothing is a candidate (callers gate on ``jnp.any(cand)``).
    """
    if s.tenants is None:
        return jnp.argmin(jnp.where(cand, s.park_seq, T_INF))
    key = tenancy_lib.fair_key(s.tenants, t_now)
    best = jnp.max(jnp.where(cand, key, -jnp.inf))
    return jnp.argmin(jnp.where(cand & (key == best), s.park_seq,
                                T_INF))


def _no_displace(state: SchedulerState, req: RequestBatch,
                 policy_id: jax.Array):
    zero = jnp.int32(0)
    return state, search_lib.SearchResult(
        found=jnp.asarray(False), t_s=zero, t_e=zero,
        pe_mask=jnp.zeros((state.tl.words,), jnp.uint32),
        n_free=zero, t_begin=zero, t_end=zero,
        early_reject=jnp.asarray(False), tiles=zero, tiles_run=zero,
        pe_blocks=zero)


def _displace(state: SchedulerState, req: RequestBatch,
              policy_id: jax.Array, *, n_pe: int, use_kernel: bool):
    """EASY displacement: admit ``req`` by moving non-head reservations.

    The transaction of DESIGN.md §6: lift every *non-head* deferral-
    queue reservation off the timeline, place the arriving request
    (its own policy, full deadline window) around the committed
    reservations plus the protected head, then re-place the lifted
    entries in FCFS order at their earliest feasible start inside their
    own deadline windows.  The request is admitted only if every lifted
    entry still fits — otherwise the whole transaction rolls back and
    the request is rejected, exactly as under ``none``.  The head-of-
    queue reservation and every committed start are untouched by
    construction (the EASY safety invariant).

    Returns the (possibly unchanged) state and a
    :class:`~repro.core.search.SearchResult` whose ``found`` flags the
    transaction outcome.  Any capacity overflow inside the transaction
    latches ``state.overflow`` regardless of the outcome, so the host
    grow-and-re-run protocol stays deterministic.
    """
    Q = state.park_capacity
    s = state
    active = s.park_seq < T_INF
    head = _select_next(s, active, req.t_a)
    nonhead = active & (jnp.arange(Q) != head)

    # batched lift: every non-head parked reservation comes off the
    # timeline in ONE fused multi-interval delete (DESIGN.md §7) —
    # the lifts commute, so this is bit-identical to the old
    # per-entry fori_loop of updates.
    tl, ovf, hw = tl_lib.update_many(
        s.tl, s.park_ts, s.park_te, s.park_mask, nonhead,
        is_add=False, with_count=True)
    tl = _where_tree(ovf, s.tl, tl)

    res_r = search_lib.search(
        tl, req.t_r, req.t_du, req.t_dl, req.n_pe, policy_id,
        req.t_a, n_pe=n_pe, use_kernel=use_kernel, rspec=s.rspec,
        demand_tail=req.demand, valid_mask=s.lane_valid)
    # a t_e at the horizon sentinel would commit as a no-op record
    # (timeline.update clamps it away) — reject it instead, matching
    # the admit step's guard
    ok = res_r.found & ~ovf & (res_r.t_e < jnp.int32(T_INF))
    tl2, o2, nk2 = tl_lib.update(
        tl, jnp.where(ok, res_r.t_s, 0), jnp.where(ok, res_r.t_e, 1),
        jnp.where(ok, res_r.pe_mask, jnp.uint32(0)), is_add=True,
        with_count=True)
    ovf = ovf | (ok & o2)
    tl = _where_tree(ok & ~o2, tl2, tl)
    hw = jnp.maximum(hw, jnp.where(ok, nk2, 0))

    def re_body(_, carry):
        tl, ovf, hw, ok, done, pts, pte, pmk, moved = carry
        cand = nonhead & ~done
        i = _select_next(s, cand, req.t_a)
        act = jnp.any(cand) & ok & ~ovf
        t_du = s.park_te[i] - s.park_ts[i]
        res = search_lib.replacement_search(
            tl, s.park_tr[i], t_du, s.park_tdl[i], s.park_npe[i],
            jnp.int32(0), req.t_a, n_pe=n_pe, use_kernel=use_kernel,
            rspec=s.rspec, demand_tail=_park_demand(s, i),
            valid_mask=s.lane_valid)
        okp = act & res.found
        tl2, o2, nk = tl_lib.update(
            tl, jnp.where(okp, res.t_s, 0),
            jnp.where(okp, res.t_s + t_du, 1),
            jnp.where(okp, res.pe_mask, jnp.uint32(0)), is_add=True,
            with_count=True)
        return (
            _where_tree(okp & ~o2, tl2, tl),
            ovf | (okp & o2),
            jnp.maximum(hw, jnp.where(okp, nk, 0)),
            ok & (res.found | ~act),
            done.at[i].set(True),
            pts.at[i].set(jnp.where(okp, res.t_s, pts[i])),
            pte.at[i].set(jnp.where(okp, res.t_s + t_du, pte[i])),
            pmk.at[i].set(jnp.where(okp, res.pe_mask, pmk[i])),
            moved + jnp.where(
                okp & (res.t_s != s.park_ts[i]), 1, 0
            ).astype(jnp.int32),
        )

    tl, ovf, hw, ok, _, pts, pte, pmk, moved = jax.lax.fori_loop(
        0, Q, re_body,
        (tl, ovf, hw, ok, jnp.zeros((Q,), bool), s.park_ts,
         s.park_te, s.park_mask, jnp.int32(0)))

    commit = ok & ~ovf
    out = s._replace(
        tl=_where_tree(commit, tl, s.tl),
        park_ts=jnp.where(commit, pts, s.park_ts),
        park_te=jnp.where(commit, pte, s.park_te),
        park_mask=jnp.where(commit, pmk, s.park_mask),
        n_moved=s.n_moved + jnp.where(commit, moved, 0),
        overflow=s.overflow | ovf,
        hw_records=jnp.maximum(s.hw_records, hw),
    )
    return out, res_r._replace(found=commit)


def _admit_release(state: SchedulerState, req: RequestBatch,
                   bf: jax.Array, *, n_pe: int, backfilling: bool,
                   auto_release: bool,
                   use_kernel: bool) -> SchedulerState:
    """The admit step's queue work: release due reservations, and on
    backfilling states promote and retry the deferral queue."""
    if backfilling:
        # promote-due + release + retry sweep under ONE queue-work
        # cond (DESIGN.md §7): a step whose queue holds nothing due
        # and whose retry latch is unarmed — every step on an
        # idle-queue stream — pays one predicate and the plain
        # release loop, i.e. mode-`none` cost.
        t_now = jnp.asarray(req.t_a, jnp.int32)
        live = state.park_seq < T_INF
        queue_pred = ((jnp.any(live & (state.park_ts <= t_now))
                       | ((bf == BF_EASY) & state.park_retry
                          & jnp.any(live)))
                      & ~state.overflow)

        def queue_work(s: SchedulerState) -> SchedulerState:
            s = _promote_due(s, t_now)
            s = _release_pending(s, t_now)
            return _retry_parked(s, t_now, bf, n_pe=n_pe,
                                 use_kernel=use_kernel)

        state = jax.lax.cond(
            queue_pred, queue_work,
            lambda s: _release_pending(s, t_now), state)
        # the retry latch is consumed per admit step either way
        return state._replace(park_retry=jnp.asarray(False))
    if auto_release:
        return release_due(state, req.t_a)
    return state


def _admit_impl(state: SchedulerState, req: RequestBatch,
                policy_id: jax.Array, backfill_id, *, n_pe: int,
                auto_release: bool,
                use_kernel: bool = False) -> Tuple[SchedulerState, Decision]:
    Q = state.park_capacity
    bf = jnp.asarray(backfill_id, jnp.int32)
    backfilling = bool(Q) and auto_release
    with jax.named_scope("admit.release"):
        state = _admit_release(state, req, bf, n_pe=n_pe,
                               backfilling=backfilling,
                               auto_release=auto_release,
                               use_kernel=use_kernel)
    tenancy = state.tenants is not None
    # tenancy needs the pending buffer as its reservation ledger even
    # without auto-release (overdue reaping batch-deletes from it;
    # client cancels clear it); zero-tenant callers keep their exact
    # pre-tenancy graphs.
    track_pending = auto_release or tenancy
    with jax.named_scope("admit.quota"):
        if tenancy:
            # ---- quota gate (DESIGN.md §10): after queue work — the
            # gate must see post-release live counts, like the host
            # oracle — but strictly *before* search.
            tn0 = state.tenants
            T = tn0.n_tenants
            tid = jnp.clip(
                jnp.asarray(0 if req.tenant is None else req.tenant,
                            jnp.int32), 0, T - 1)
            # filler padding (requests_to_batch rings/grids) asks for
            # n_pe + 1 PEs; it belongs to no tenant and must neither be
            # gated nor charged
            real = req.n_pe <= jnp.int32(n_pe)
            demand = (req.n_pe.astype(jnp.float32)
                      * req.t_du.astype(jnp.float32))
            orig_tr, orig_tdu = req.t_r, req.t_du
            occ_row = tl_lib.occupancy_at(
                state.tl, jnp.asarray(req.t_a, jnp.int32))
            if state.rspec is not None:
                # telemetry stays a PE-utilisation fraction: count only
                # the primary plane's words of the multi-resource row
                occ_row = occ_row[state.rspec.plane_slice(0)]
            occ_q = tenancy_lib.ratio_q16(
                jax.lax.population_count(occ_row).sum().astype(jnp.int32),
                n_pe)
            within = ((tn0.used[tid] + demand <= tn0.quota[tid])
                      & (tn0.live[tid] < tn0.max_live[tid]))
            blocked = real & ~within
            # an over-quota request is rewritten never-feasible (the
            # filler trick): search, displacement, commit and park all
            # no-op naturally, with zero extra branches in the hot path
            req = req._replace(
                t_r=jnp.where(blocked, req.t_a, req.t_r),
                t_du=jnp.where(blocked, jnp.int32(1), req.t_du),
                t_dl=jnp.where(blocked, req.t_a + jnp.int32(1),
                               req.t_dl),
                n_pe=jnp.where(blocked, jnp.int32(n_pe + 1), req.n_pe))
        else:
            blocked = jnp.asarray(False)
    # NB: searches at full capacity S — the per-request engine's
    # power-of-two bucketing needs the host-visible record count, which
    # does not exist inside a fixed-shape scan.  The fusion win (no
    # host round-trips) dominates; keep initial `capacity` modest and
    # let overflow growth size S to the workload.
    with jax.named_scope("admit.search"):
        res = search_lib.search(
            state.tl, req.t_r, req.t_du, req.t_dl, req.n_pe, policy_id,
            req.t_a, n_pe=n_pe, use_kernel=use_kernel, rspec=state.rspec,
            demand_tail=req.demand, valid_mask=state.lane_valid)
        # reject a win whose end clamps to the horizon sentinel: committing
        # it would be a silent no-op under timeline.update's T_INF guard,
        # leaving an "accepted" decision with no occupancy behind it
        found = (res.found & ~state.overflow
                 & (res.t_e < jnp.int32(T_INF)))
        t_s, t_e, pe_mask = res.t_s, res.t_e, res.pe_mask
        n_free, t_begin, t_end = res.n_free, res.t_begin, res.t_end
        # the index's and the kernel's work on real requests: filler
        # and over-quota rewrites ask for n_pe + 1 PEs, which the
        # index's capacity proof rejects every time
        counted = (req.n_pe <= jnp.int32(n_pe)) & ~state.overflow
        state = state._replace(
            n_early_rejects=state.n_early_rejects
            + (counted & res.early_reject).astype(jnp.int32),
            n_search_tiles=state.n_search_tiles
            + jnp.where(counted, res.tiles, 0),
            n_search_tiles_run=state.n_search_tiles_run
            + jnp.where(counted, res.tiles_run, 0),
            n_search_pe_blocks=state.n_search_pe_blocks
            + jnp.where(counted, res.pe_blocks, 0))
    need_add = jnp.asarray(True)
    with jax.named_scope("admit.displace"):
        if backfilling:
            # EASY fallback: an otherwise-rejected request may displace
            # non-head parked reservations (transactional; see _displace).
            # With fewer than two live entries there is nothing to lift —
            # the transaction would re-run the identical failed search —
            # so it is skipped (identical decisions, no wasted searches).
            # over-quota requests never displace: the transaction's lifts
            # could latch overflow for work the gate already rejected
            can_try = ((bf == BF_EASY) & ~res.found & ~state.overflow
                       & ~blocked
                       & (jnp.sum(state.park_seq < T_INF) >= 2))
            state, dres = jax.lax.cond(
                can_try,
                functools.partial(_displace, n_pe=n_pe,
                                  use_kernel=use_kernel),
                _no_displace, state, req, policy_id)
            found = jnp.where(can_try, dres.found, found)
            t_s = jnp.where(can_try, dres.t_s, t_s)
            t_e = jnp.where(can_try, dres.t_e, t_e)
            pe_mask = jnp.where(can_try, dres.pe_mask, pe_mask)
            n_free = jnp.where(can_try, dres.n_free, n_free)
            t_begin = jnp.where(can_try, dres.t_begin, t_begin)
            t_end = jnp.where(can_try, dres.t_end, t_end)
            # the displacement transaction already wrote r to the timeline
            need_add = ~can_try
            free_park = state.park_seq == jnp.int32(T_INF)
            parks = ((bf != BF_NONE) & (t_s > req.t_r)
                     & jnp.any(free_park))
        else:
            parks = jnp.asarray(False)

    def commit(s: SchedulerState) -> SchedulerState:
        new_tl, ovf, n_keep = tl_lib.update(
            s.tl, jnp.where(need_add, t_s, 0),
            jnp.where(need_add, t_e, 1),
            jnp.where(need_add, pe_mask, jnp.uint32(0)), is_add=True,
            with_count=True)
        ovf = ovf & need_add
        hw_pending = s.hw_pending
        if track_pending:
            free = s.pend_te == T_INF
            slot = jnp.argmax(free)
            n_used = jnp.sum(~free).astype(jnp.int32) + 1
            to_pend = ~parks
            hw_pending = jnp.maximum(
                hw_pending, jnp.where(to_pend, n_used, 0))
            ovf = ovf | (to_pend & ~jnp.any(free))
            wr = to_pend & ~ovf
            pend_ts = jnp.where(
                wr, s.pend_ts.at[slot].set(t_s), s.pend_ts)
            pend_te = jnp.where(
                wr, s.pend_te.at[slot].set(t_e), s.pend_te)
            pend_mask = jnp.where(
                wr, s.pend_mask.at[slot].set(pe_mask), s.pend_mask)
        else:
            pend_ts, pend_te, pend_mask = \
                s.pend_ts, s.pend_te, s.pend_mask
        out = s._replace(
            # an overflowing update returns a truncated timeline —
            # keep the pre-commit state so the retry starts from
            # consistent data.
            tl=_where_tree(ovf, s.tl, new_tl),
            pend_ts=pend_ts, pend_te=pend_te, pend_mask=pend_mask,
            n_accepted=s.n_accepted
            + jnp.where(ovf, 0, 1).astype(jnp.int32),
            overflow=s.overflow | ovf,
            hw_records=jnp.maximum(s.hw_records, n_keep),
            hw_pending=hw_pending,
        )
        if tenancy:
            # ownership of the new pending slot (queue slots are
            # owned by park_write below)
            tn = s.tenants
            out = out._replace(tenants=tn._replace(
                pend_tenant=jnp.where(
                    wr, tn.pend_tenant.at[slot].set(tid),
                    tn.pend_tenant)))
        if backfilling:
            # park bookkeeping sits behind its own cond: an accept
            # that starts at its ready time (the overwhelmingly
            # common case — always, on an idle-queue stream) pays one
            # predicate instead of seven queue-array scatters
            def park_write(o: SchedulerState) -> SchedulerState:
                pslot = jnp.argmax(free_park)
                live = jnp.sum(~free_park).astype(jnp.int32) + 1
                o = o._replace(
                    park_ts=o.park_ts.at[pslot].set(t_s),
                    park_te=o.park_te.at[pslot].set(t_e),
                    park_mask=o.park_mask.at[pslot].set(pe_mask),
                    park_tr=o.park_tr.at[pslot].set(req.t_r),
                    park_tdl=o.park_tdl.at[pslot].set(req.t_dl),
                    park_npe=o.park_npe.at[pslot].set(req.n_pe),
                    park_seq=o.park_seq.at[pslot].set(o.park_next_seq),
                    park_next_seq=o.park_next_seq + 1,
                    n_parked=o.n_parked + 1,
                    hw_parked=jnp.maximum(o.hw_parked, live),
                )
                if o.park_dem is not None:
                    # the queue entry keeps its demand tail so later
                    # re-placements (EASY sweep / displacement) search
                    # with the full vector
                    dem_row = (req.demand if req.demand is not None
                               else jnp.zeros_like(o.park_dem[0]))
                    o = o._replace(
                        park_dem=o.park_dem.at[pslot].set(dem_row))
                if tenancy:
                    tno = o.tenants
                    o = o._replace(tenants=tno._replace(
                        park_tenant=tno.park_tenant.at[pslot].set(
                            tid),
                        # the fair-share wait clock starts at arrival
                        park_ta=tno.park_ta.at[pslot].set(req.t_a),
                    ))
                return o

            out = jax.lax.cond(parks & ~ovf, park_write,
                               lambda o: o, out)
        return out

    with jax.named_scope("admit.commit"):
        state = jax.lax.cond(found, commit, lambda s: s, state)
        accepted = found & ~state.overflow
    with jax.named_scope("admit.quota"):
        if tenancy:
            # ---- per-tenant accounting and telemetry EWMAs: lazy
            # device-resident accumulators (one scatter block per step,
            # nothing read back).  Filler padding (real=False) and
            # overflowed steps (re-run from the pre-run snapshot anyway)
            # charge nothing, so the table matches the host oracle, which
            # sees neither.  `used` mirrors HostTenantAccounts.record
            # float32-for-float32; the EWMAs are int32 fixed point.
            tn = state.tenants
            ok_upd = real & ~state.overflow
            a = tn.alpha
            acc_i = jnp.where(ok_upd & accepted, 1, 0).astype(jnp.int32)
            rej_i = jnp.where(ok_upd & ~accepted, 1, 0).astype(jnp.int32)
            qrej_i = jnp.where(ok_upd & blocked, 1, 0).astype(jnp.int32)
            prk_i = jnp.where(ok_upd & accepted & parks, 1,
                              0).astype(jnp.int32)
            acc_x = jnp.where(accepted, tenancy_lib.EWMA_ONE, 0)
            new_acc = tenancy_lib.ewma_q16(tn.acc_ewma[tid], acc_x, a)
            slow_x = tenancy_lib.ratio_q16(t_e - orig_tr, orig_tdu)
            new_slow = tenancy_lib.ewma_q16(tn.slow_ewma[tid], slow_x, a)
            new_occ = tenancy_lib.ewma_q16(tn.occ_ewma, occ_q, a)
            state = state._replace(tenants=tn._replace(
                used=tn.used.at[tid].add(
                    jnp.where(ok_upd & accepted, demand,
                              jnp.float32(0.0))),
                live=tn.live.at[tid].add(acc_i),
                n_accepted=tn.n_accepted.at[tid].add(acc_i),
                n_rejected=tn.n_rejected.at[tid].add(rej_i),
                n_quota_rejected=tn.n_quota_rejected.at[tid].add(qrej_i),
                n_parked=tn.n_parked.at[tid].add(prk_i),
                acc_ewma=tn.acc_ewma.at[tid].set(
                    jnp.where(ok_upd, new_acc, tn.acc_ewma[tid])),
                slow_ewma=tn.slow_ewma.at[tid].set(
                    jnp.where(ok_upd & accepted, new_slow,
                              tn.slow_ewma[tid])),
                occ_ewma=jnp.where(ok_upd, new_occ, tn.occ_ewma),
            ))
    return state, Decision(
        accepted=accepted,
        t_s=jnp.where(accepted, t_s, jnp.int32(-1)),
        t_e=jnp.where(accepted, t_e, jnp.int32(-1)),
        pe_mask=jnp.where(accepted, pe_mask, jnp.uint32(0)),
        n_free=n_free,
        t_begin=t_begin,
        t_end=t_end,
        parked=accepted & parks,
    )


@functools.partial(
    jax.jit, static_argnames=("n_pe", "auto_release", "use_kernel"))
def admit(state: SchedulerState, req: RequestBatch,
          policy_id: jax.Array, backfill_id=BF_NONE, *, n_pe: int,
          auto_release: bool = True,
          use_kernel: bool = False) -> Tuple[SchedulerState, Decision]:
    """One fused admission step: release due -> retry -> search -> commit.

    ``auto_release=False`` skips the pending-release bookkeeping for
    callers (e.g. the fleet) that manage completions themselves.
    ``backfill_id`` is the traced deferral mode (none/easy/
    conservative); it only matters when the state carries a deferral
    queue (``park_capacity > 0``).
    """
    with jax.named_scope("admit"):
        return _admit_impl(state, req, policy_id, backfill_id,
                           n_pe=n_pe, auto_release=auto_release,
                           use_kernel=use_kernel)


@functools.partial(
    jax.jit, static_argnames=("n_pe", "auto_release", "use_kernel"))
def admit_stream(state: SchedulerState, batch: RequestBatch,
                 policy_id: jax.Array, backfill_id=BF_NONE, *,
                 n_pe: int, auto_release: bool = True,
                 use_kernel: bool = False
                 ) -> Tuple[SchedulerState, Decision]:
    """Scan a whole arrival-ordered request stream on-device."""
    bf = jnp.asarray(backfill_id, jnp.int32)

    def step(s, r):
        with jax.named_scope("admit"):
            return _admit_impl(s, r, policy_id, bf, n_pe=n_pe,
                               auto_release=auto_release,
                               use_kernel=use_kernel)

    return jax.lax.scan(step, state, batch)


@functools.partial(
    jax.jit, static_argnames=("n_pe", "auto_release", "use_kernel"),
    donate_argnums=(0,))
def admit_stream_donated(state: SchedulerState, batch: RequestBatch,
                         policy_id: jax.Array, backfill_id=BF_NONE, *,
                         n_pe: int, auto_release: bool = True,
                         use_kernel: bool = False
                         ) -> Tuple[SchedulerState, Decision]:
    """:func:`admit_stream` with the state buffers *donated*.

    Donation lets XLA reuse the input buffers for the output, so the
    steady-state step is allocation-free — but it consumes the
    caller's only copy, which collides with the grow-once protocol's
    "re-run the batch from the pre-run snapshot".  The resolution is
    rollback-on-overflow (DESIGN.md §8): when the overflow latch is
    (or becomes) set, this function returns the *pre-call* state —
    rolled back inside the dispatch — carrying the sticky latch and
    the run's high-water marks.  The host can then grow once
    (:func:`grow_rollback`) and re-run deterministically; the
    discarded run's decisions were going to be re-computed anyway,
    and the watermarks only size growth, never decisions.

    The latch is sticky *across* calls: a donated call entered with
    ``overflow`` already set returns its input state unchanged (its
    decisions are garbage and must be discarded) — this is what lets
    the service pipeline chunks without a per-chunk overflow read.
    """
    bf = jnp.asarray(backfill_id, jnp.int32)

    def step(s, r):
        with jax.named_scope("admit"):
            return _admit_impl(s, r, policy_id, bf, n_pe=n_pe,
                               auto_release=auto_release,
                               use_kernel=use_kernel)

    out, dec = jax.lax.scan(step, state, batch)
    ovf = state.overflow | out.overflow
    rolled = _where_tree(jnp.any(ovf), state, out)
    rolled = rolled._replace(
        overflow=ovf,
        hw_records=jnp.maximum(state.hw_records, out.hw_records),
        hw_pending=jnp.maximum(state.hw_pending, out.hw_pending))
    return rolled, dec


# ---------------------------------------------------------------------------
# host wrappers: overflow -> grow -> deterministic re-run
# ---------------------------------------------------------------------------


class GrowthError(RuntimeError):
    """Overflow with growth exhausted or forbidden.

    ``state``, when set, is the rolled-back pre-run state of a
    *donated* attempt: the caller's input buffers were consumed, so a
    donating caller must reinstall this state to stay usable (the
    service backends do).  Non-donated attempts leave the caller's
    state untouched and set ``state=None``.
    """

    def __init__(self, msg: str, state: Optional[SchedulerState] = None):
        super().__init__(msg)
        self.state = state


def grown_capacities(state: SchedulerState, need_records: int,
                     need_pending: int) -> Tuple[int, int]:
    """New (capacity, pending_capacity) sized by the high-water marks.

    ``need_records`` / ``need_pending`` are the max watermarks observed
    in the overflowing run (across the whole ensemble for the vmapped
    wrappers).  A structure whose watermark fits keeps its size; one
    that overflowed jumps straight to the next power of two covering
    the need (at least doubling, so the retry loop always progresses
    even when the watermark stalled at the first-overflow step).
    """
    cap, pend = state.tl.capacity, state.pending_capacity
    new_cap = cap if need_records <= cap \
        else max(2 * cap, tl_lib.next_pow2(need_records))
    new_pend = pend if need_pending <= pend \
        else max(2 * pend, tl_lib.next_pow2(need_pending))
    if (new_cap, new_pend) == (cap, pend):
        # overflow latched without a usable watermark: double both.
        new_cap, new_pend = 2 * cap, 2 * pend
    return new_cap, new_pend


def _grown(state: SchedulerState, run: SchedulerState) -> SchedulerState:
    """Grow the pre-run snapshot to what the failed ``run`` needed."""
    new_cap, new_pend = grown_capacities(
        state, int(run.hw_records), int(run.hw_pending))
    return tl_lib.grow_state(
        state, new_capacity=new_cap, new_pending_capacity=new_pend)


def grow_rollback(state: SchedulerState) -> SchedulerState:
    """Grow a rolled-back (latched) state and clear its latch.

    The donated-path counterpart of :func:`_grown`: a
    :func:`admit_stream_donated` overflow returns the pre-run state
    carrying the failed run's watermarks, so the rollback state *is*
    its own growth reference.  ``grow_state`` copies the latch
    verbatim, which would keep every retry a no-op — clear it.
    """
    out = _grown(state, state)
    return out._replace(overflow=jnp.zeros_like(out.overflow))


def admit_stream_grow(state: SchedulerState, batch: RequestBatch,
                      policy, *, n_pe: int, backfill=BF_NONE,
                      auto_release: bool = True,
                      use_kernel: bool = False,
                      max_growths: int = MAX_DOUBLINGS,
                      donate: bool = False
                      ) -> Tuple[SchedulerState, Decision]:
    """Run :func:`admit_stream`, growing capacity on overflow.

    Each retry re-runs the *full* batch from the original (grown)
    pre-run state; padding never changes decisions, so the result is
    identical to a run that started with enough capacity.  This is the
    growth step behind :meth:`repro.api.Session.offer`, which feeds it
    fixed-shape ring-buffer chunks so steady-state streaming never
    recompiles.  ``max_growths=0`` forbids growth entirely: the first
    overflow raises before any state mutation (the service's
    ``auto_grow=False`` mode).

    ``donate=True`` dispatches :func:`admit_stream_donated` instead —
    the caller's state buffers are consumed and must not be reused
    (the overflow retry re-materializes via :func:`grow_rollback`; a
    terminal overflow raises :class:`GrowthError` carrying the
    rolled-back state so the caller can reinstall it).  Decisions are
    bit-identical to the non-donated path.
    """
    pid = jnp.int32(
        policy if isinstance(policy, (int, np.integer))
        else policy_index(policy))
    bfid = as_backfill_id(backfill)
    fn = admit_stream_donated if donate else admit_stream
    start = state
    for attempt in range(max_growths + 1):
        out, dec = fn(start, batch, pid, bfid, n_pe=n_pe,
                      auto_release=auto_release,
                      use_kernel=use_kernel)
        if not bool(out.overflow):
            return out, dec
        if attempt < max_growths:
            # donated: `out` IS the rolled-back pre-run state (fresh
            # buffers), so growth re-materializes outside the donated
            # dispatch and the retry owns its input exclusively again
            start = grow_rollback(out) if donate else _grown(start, out)
    raise GrowthError(
        f"admit_stream still overflowing after {max_growths + 1} "
        f"attempts (last tried capacity "
        f"{(out if donate else start).tl.capacity}, "
        f"pending {(out if donate else start).pending_capacity}; "
        f"needed records {int(out.hw_records)}, "
        f"pending {int(out.hw_pending)})",
        state=out if donate else None)


def admit_stream_auto(state: SchedulerState, batch: RequestBatch,
                      policy, *, n_pe: int, auto_release: bool = True,
                      use_kernel: bool = False
                      ) -> Tuple[SchedulerState, Decision]:
    """Deprecated alias of :func:`admit_stream_grow`.

    .. deprecated:: PR 3
       Use :class:`repro.api.ReservationService` — a
       :meth:`~repro.api.Session.offer` session streams fixed-shape
       chunks with zero recompilation — or call
       :func:`admit_stream_grow` directly for one-shot batches.
    """
    warnings.warn(
        "admit_stream_auto is deprecated: open a repro.api."
        "ReservationService session and use Session.offer(requests) "
        "(or admit_stream_grow for a one-shot batch)",
        DeprecationWarning, stacklevel=2)
    return admit_stream_grow(state, batch, policy, n_pe=n_pe,
                             auto_release=auto_release,
                             use_kernel=use_kernel)


def admit_one(state: SchedulerState, req: ARRequest, policy, *,
              n_pe: int, backfill=BF_NONE, auto_release: bool = True,
              use_kernel: bool = False
              ) -> Tuple[SchedulerState, Optional[Allocation]]:
    """Single fused admission with growth retry; host-typed result."""
    pid = jnp.int32(policy_index(policy))
    bfid = as_backfill_id(backfill)
    xd = 0 if state.rspec is None else state.rspec.R - 1
    start = state
    for attempt in range(MAX_DOUBLINGS + 1):
        out, dec = admit(start, request_struct(req, extra_demand=xd),
                         pid, bfid,
                         n_pe=n_pe, auto_release=auto_release,
                         use_kernel=use_kernel)
        if not bool(out.overflow):
            return out, decision_to_allocation(dec)
        if attempt < MAX_DOUBLINGS:
            start = _grown(start, out)
    raise RuntimeError(
        f"admit still overflowing after {MAX_DOUBLINGS + 1} attempts "
        f"(last tried capacity {start.tl.capacity}, "
        f"pending {start.pending_capacity})")


# ---------------------------------------------------------------------------
# session verbs: release-due advancement and cancellation
# ---------------------------------------------------------------------------


release_due_step = jax.jit(release_due)


def release_until(state: SchedulerState, t_now: int, *,
                  max_growths: int = MAX_DOUBLINGS) -> SchedulerState:
    """Host wrapper of :func:`release_due` with overflow growth.

    The service's ``tick(t)``: deletes every pending reservation ending
    by ``t_now``.  A deletion can split a merged record and overflow
    the timeline; the retry re-runs from the pre-tick snapshot on a
    grown state, which is deterministic.  ``max_growths=0`` raises on
    the first overflow instead (before any state mutation).
    """
    start = state
    for attempt in range(max_growths + 1):
        out = release_due_step(start, jnp.int32(t_now))
        if not bool(out.overflow):
            return out
        if attempt < max_growths:
            start = _grown(start, out)
    raise RuntimeError(
        f"release_until still overflowing after {max_growths + 1} "
        f"attempts (last tried capacity {start.tl.capacity})")


@functools.partial(jax.jit, static_argnames=("require_pending",))
def cancel_step(state: SchedulerState, t_s: jax.Array, t_e: jax.Array,
                mask: jax.Array, *, require_pending: bool = True
                ) -> Tuple[SchedulerState, jax.Array]:
    """Withdraw one committed reservation in a single fused dispatch.

    Deletes ``[t_s, t_e) x mask`` from the timeline and clears the
    matching pending-release slot.  With ``require_pending`` (the
    auto-release sessions) a reservation that is not pending — already
    released, cancelled, or never admitted — is a no-op returning
    ``False``, so cancel is idempotent and can never corrupt the
    timeline.  Overflow latches as in :func:`admit`; host callers grow
    and retry (:func:`cancel_one`).
    """
    match = (state.pend_ts == t_s) & (state.pend_te == t_e) & \
        jnp.all(state.pend_mask == mask[None, :], axis=1)
    found = jnp.any(match)
    if state.park_capacity:
        # a parked (deferral-queue) reservation is cancellable too
        pmatch = (state.park_ts == t_s) & (state.park_te == t_e) & \
            jnp.all(state.park_mask == mask[None, :], axis=1) & \
            (state.park_seq < T_INF)
        pfound = jnp.any(pmatch)
        found = found | pfound
    ok = found if require_pending else jnp.asarray(True)
    ok = ok & ~state.overflow
    new_tl, ovf, n_keep = tl_lib.update(
        state.tl, t_s, t_e, mask, is_add=False, with_count=True)
    ovf = ovf & ok
    do = ok & ~ovf
    slot = jnp.argmax(match)
    clear = jnp.any(match) & do
    cleared_ts = state.pend_ts.at[slot].set(T_INF)
    cleared_te = state.pend_te.at[slot].set(T_INF)
    cleared_mask = state.pend_mask.at[slot].set(jnp.uint32(0))
    out = state._replace(
        tl=_where_tree(do, new_tl, state.tl),
        pend_ts=jnp.where(clear, cleared_ts, state.pend_ts),
        pend_te=jnp.where(clear, cleared_te, state.pend_te),
        pend_mask=jnp.where(clear, cleared_mask, state.pend_mask),
        overflow=state.overflow | ovf,
        hw_records=jnp.maximum(state.hw_records,
                               jnp.where(ok, n_keep, 0)),
    )
    if state.park_capacity:
        pslot = jnp.argmax(pmatch)
        pclear = pfound & do
        out = out._replace(
            park_ts=jnp.where(
                pclear, out.park_ts.at[pslot].set(T_INF), out.park_ts),
            park_te=jnp.where(
                pclear, out.park_te.at[pslot].set(T_INF), out.park_te),
            park_mask=jnp.where(
                pclear, out.park_mask.at[pslot].set(jnp.uint32(0)),
                out.park_mask),
            park_seq=jnp.where(
                pclear, out.park_seq.at[pslot].set(T_INF),
                out.park_seq),
            # a successful withdrawal frees future capacity: arm the
            # EASY retry-on-release sweep for the next admit step
            park_retry=out.park_retry | do,
        )
    if state.tenants is not None:
        tn = state.tenants
        T = tn.n_tenants
        ctid = jnp.clip(tn.pend_tenant[slot], 0, T - 1)
        dec = jnp.where(clear & (tn.pend_tenant[slot] >= 0), 1,
                        0).astype(jnp.int32)
        upd = dict(
            live=tn.live.at[ctid].add(-dec),
            pend_tenant=jnp.where(
                clear, tn.pend_tenant.at[slot].set(-1),
                tn.pend_tenant))
        if state.park_capacity:
            ptid = jnp.clip(tn.park_tenant[pslot], 0, T - 1)
            pdec = jnp.where(pclear & (tn.park_tenant[pslot] >= 0),
                             1, 0).astype(jnp.int32)
            upd["live"] = upd["live"].at[ptid].add(-pdec)
            upd["park_tenant"] = jnp.where(
                pclear, tn.park_tenant.at[pslot].set(-1),
                tn.park_tenant)
            upd["park_ta"] = jnp.where(
                pclear, tn.park_ta.at[pslot].set(0), tn.park_ta)
        out = out._replace(tenants=tn._replace(**upd))
    return out, do


def cancel_one(state: SchedulerState, t_s: int, t_e: int,
               mask: jax.Array, *, require_pending: bool = True,
               max_growths: int = MAX_DOUBLINGS
               ) -> Tuple[SchedulerState, bool]:
    """Host wrapper of :func:`cancel_step` with overflow growth."""
    start = state
    for attempt in range(max_growths + 1):
        out, done = cancel_step(
            start, jnp.int32(t_s), jnp.int32(t_e), mask,
            require_pending=require_pending)
        if not bool(out.overflow):
            return out, bool(done)
        if attempt < max_growths:
            start = _grown(start, out)
    raise RuntimeError(
        f"cancel still overflowing after {max_growths + 1} "
        f"attempts (last tried capacity {start.tl.capacity})")


@functools.partial(jax.jit, static_argnames=("require_pending",))
def cancel_many_step(state: SchedulerState, t_s: jax.Array,
                     t_e: jax.Array, masks: jax.Array,
                     active: jax.Array, *,
                     require_pending: bool = True
                     ) -> Tuple[SchedulerState, jax.Array]:
    """Withdraw up to K committed reservations in one fused dispatch.

    The batched sibling of :func:`cancel_step`, built on
    ``timeline.update_many``: all matched reservations are deleted in
    one boundary-union + merge pass and their pending (or parked)
    slots cleared together.  Cancellations of distinct reservations
    commute, so this is decision-identical to K sequential cancels
    (callers must not repeat a reservation within one batch — the
    host wrapper deduplicates).  Returns the new state and a bool[K]
    of per-entry outcomes (``require_pending`` semantics as in
    :func:`cancel_step`).
    """
    K = t_s.shape[0]
    active = jnp.asarray(active, bool)
    pmatch = (state.pend_ts[None, :] == t_s[:, None]) & \
        (state.pend_te[None, :] == t_e[:, None]) & \
        jnp.all(state.pend_mask[None, :, :] == masks[:, None, :],
                axis=2)                                       # [K, P]
    found = jnp.any(pmatch, axis=1)
    if state.park_capacity:
        kmatch = (state.park_ts[None, :] == t_s[:, None]) & \
            (state.park_te[None, :] == t_e[:, None]) & \
            jnp.all(state.park_mask[None, :, :] == masks[:, None, :],
                    axis=2) & (state.park_seq[None, :] < T_INF)
        kfound = jnp.any(kmatch, axis=1)
        found = found | kfound
    ok = (found if require_pending else jnp.ones((K,), bool))
    ok = ok & active & ~state.overflow
    new_tl, ovf, n_keep = tl_lib.update_many(
        state.tl, t_s, t_e, masks, ok, is_add=False, with_count=True)
    do = ok & ~ovf
    P = state.pending_capacity
    slot = jnp.argmax(pmatch, axis=1)
    clear = jnp.zeros((P + 1,), bool).at[
        jnp.where(do & jnp.any(pmatch, axis=1), slot, P)].set(
        True)[:P]
    out = state._replace(
        tl=_where_tree(ovf, state.tl, new_tl),
        pend_ts=jnp.where(clear, T_INF, state.pend_ts),
        pend_te=jnp.where(clear, T_INF, state.pend_te),
        pend_mask=jnp.where(clear[:, None], jnp.uint32(0),
                            state.pend_mask),
        overflow=state.overflow | ovf,
        hw_records=jnp.maximum(state.hw_records,
                               jnp.where(jnp.any(ok), n_keep, 0)),
    )
    if state.park_capacity:
        Q = state.park_capacity
        pslot = jnp.argmax(kmatch, axis=1)
        pclear = jnp.zeros((Q + 1,), bool).at[
            jnp.where(do & kfound, pslot, Q)].set(True)[:Q]
        out = out._replace(
            park_ts=jnp.where(pclear, T_INF, out.park_ts),
            park_te=jnp.where(pclear, T_INF, out.park_te),
            park_mask=jnp.where(pclear[:, None], jnp.uint32(0),
                                out.park_mask),
            park_seq=jnp.where(pclear, T_INF, out.park_seq),
            # a successful withdrawal frees future capacity: arm the
            # EASY retry-on-release sweep for the next admit step
            park_retry=out.park_retry | jnp.any(do),
        )
    if state.tenants is not None:
        tn = state.tenants
        T = tn.n_tenants
        ctid = jnp.clip(tn.pend_tenant, 0, T - 1)
        dec = jnp.where(clear & (tn.pend_tenant >= 0), 1,
                        0).astype(jnp.int32)
        upd = dict(
            live=tn.live.at[ctid].add(-dec),
            pend_tenant=jnp.where(clear, -1, tn.pend_tenant))
        if state.park_capacity:
            ptid = jnp.clip(tn.park_tenant, 0, T - 1)
            pdec = jnp.where(pclear & (tn.park_tenant >= 0), 1,
                             0).astype(jnp.int32)
            upd["live"] = upd["live"].at[ptid].add(-pdec)
            upd["park_tenant"] = jnp.where(pclear, -1,
                                           tn.park_tenant)
            upd["park_ta"] = jnp.where(pclear, 0, tn.park_ta)
        out = out._replace(tenants=tn._replace(**upd))
    return out, do


def cancel_many(state: SchedulerState, entries, *,
                require_pending: bool = True,
                max_growths: int = MAX_DOUBLINGS
                ) -> Tuple[SchedulerState, List[bool]]:
    """Host wrapper of :func:`cancel_many_step` with overflow growth.

    ``entries`` is a sequence of ``(t_s, t_e, mask)`` triples.
    Under ``require_pending`` repeated triples within one batch are
    deduplicated on the host: the first occurrence cancels, later
    duplicates report ``False`` — exactly what sequential
    :func:`cancel_one` calls return, since the first cancel clears
    the matching slot.  With ``require_pending=False`` sequential
    cancels are blind deletes that report ``True`` every time, so
    duplicates stay active (the batched AND-NOT union is idempotent
    on occupancy) and report ``True`` as well.
    """
    entries = list(entries)
    if not entries:
        return state, []
    W = state.tl.words
    if require_pending:
        seen: dict = {}
        dup = np.zeros(len(entries), bool)
        for i, (ts, te, mk) in enumerate(entries):
            key = (int(ts), int(te), bytes(np.asarray(mk)))
            if key in seen:
                dup[i] = True
            seen[key] = i
        act = jnp.asarray(~dup)
    else:
        act = jnp.ones((len(entries),), bool)
    # pad K to the next power of two (inactive rows) so varying batch
    # sizes share O(log K) compiled shapes instead of one per size
    K_pad = tl_lib.next_pow2(len(entries)) \
        if len(entries) > 1 else 1
    pad = K_pad - len(entries)
    act = jnp.concatenate([act, jnp.zeros((pad,), bool)])
    t_s = jnp.asarray([e[0] for e in entries] + [0] * pad, jnp.int32)
    t_e = jnp.asarray([e[1] for e in entries] + [0] * pad, jnp.int32)
    masks = jnp.asarray(np.stack(
        [np.asarray(e[2], np.uint32).reshape(W) for e in entries]
        + [np.zeros(W, np.uint32)] * pad))
    start = state
    for attempt in range(max_growths + 1):
        out, done = cancel_many_step(
            start, t_s, t_e, masks, act,
            require_pending=require_pending)
        if not bool(out.overflow):
            return out, [bool(d) for d in
                         np.asarray(done)[:len(entries)]]
        if attempt < max_growths:
            start = _grown(start, out)
    raise RuntimeError(
        f"cancel_many still overflowing after {max_growths + 1} "
        f"attempts (last tried capacity {start.tl.capacity})")


# ---------------------------------------------------------------------------
# host-side decision unpacking
# ---------------------------------------------------------------------------


def parked_entries(state: SchedulerState) -> List[dict]:
    """Host view of the deferral queue in FCFS order.

    One dict per live entry: the reservation mark (``t_s``/``t_e``/
    ``pe_ids``), the request window it can still be re-placed in
    (``t_r``/``t_dl``/``n_pe``) and its arrival sequence number.  The
    first entry is the head of queue (protected under EASY).
    """
    seq = np.asarray(state.park_seq)
    ts = np.asarray(state.park_ts)
    te = np.asarray(state.park_te)
    tr = np.asarray(state.park_tr)
    tdl = np.asarray(state.park_tdl)
    npe = np.asarray(state.park_npe)
    masks = np.asarray(state.park_mask)
    dem = (np.asarray(state.park_dem)
           if state.park_dem is not None else None)
    tenant = (np.asarray(state.tenants.park_tenant)
              if state.tenants is not None else None)
    t_a = (np.asarray(state.tenants.park_ta)
           if state.tenants is not None else None)
    out = []
    for i in np.argsort(seq, kind="stable"):
        if seq[i] >= T_INF:
            continue
        entry = dict(
            seq=int(seq[i]), t_s=int(ts[i]), t_e=int(te[i]),
            t_r=int(tr[i]), t_dl=int(tdl[i]), n_pe=int(npe[i]),
            pe_ids=mask32_to_ids(masks[i]))
        if dem is not None:
            entry["demand"] = ((int(npe[i]),)
                               + tuple(int(x) for x in dem[i]))
        if tenant is not None:
            entry["tenant"] = int(tenant[i])
            entry["t_a"] = int(t_a[i])
        out.append(entry)
    return out


def mask32_to_ids(mask32: np.ndarray) -> Tuple[int, ...]:
    """uint32[W] bitmask -> sorted tuple of PE ids."""
    bits = np.unpackbits(
        np.ascontiguousarray(mask32, dtype="<u4").view(np.uint8),
        bitorder="little")
    return tuple(int(i) for i in np.nonzero(bits)[0])


def decision_to_allocation(dec: Decision) -> Optional[Allocation]:
    """One scalar :class:`Decision` -> host :class:`Allocation`."""
    if not bool(dec.accepted):
        return None
    return Allocation(
        t_s=int(dec.t_s), t_e=int(dec.t_e),
        pe_ids=mask32_to_ids(np.asarray(dec.pe_mask)),
        rectangle=Rectangle(
            t_s=int(dec.t_s), t_begin=int(dec.t_begin),
            t_end=int(dec.t_end), n_free=int(dec.n_free)),
    )


def search_result_to_allocation(res) -> Optional[Allocation]:
    """One scalar ``SearchResult`` -> host :class:`Allocation`."""
    if not bool(res.found):
        return None
    return Allocation(
        t_s=int(res.t_s), t_e=int(res.t_e),
        pe_ids=mask32_to_ids(np.asarray(res.pe_mask)),
        rectangle=Rectangle(
            t_s=int(res.t_s), t_begin=int(res.t_begin),
            t_end=int(res.t_end), n_free=int(res.n_free)),
    )


def decisions_to_allocations(dec: Decision) -> List[Optional[Allocation]]:
    """Stacked decisions -> one host allocation (or None) per request."""
    accepted = np.asarray(dec.accepted)
    t_s = np.asarray(dec.t_s)
    t_e = np.asarray(dec.t_e)
    masks = np.asarray(dec.pe_mask)
    n_free = np.asarray(dec.n_free)
    t_begin = np.asarray(dec.t_begin)
    t_end = np.asarray(dec.t_end)
    out: List[Optional[Allocation]] = []
    for i in range(accepted.shape[0]):
        if not accepted[i]:
            out.append(None)
            continue
        out.append(Allocation(
            t_s=int(t_s[i]), t_e=int(t_e[i]),
            pe_ids=mask32_to_ids(masks[i]),
            rectangle=Rectangle(
                t_s=int(t_s[i]), t_begin=int(t_begin[i]),
                t_end=int(t_end[i]), n_free=int(n_free[i]))))
    return out
