"""JAX device engine: the availability timeline as a dense tensor.

TPU adaptation of the paper's ``AvailRectList`` (see DESIGN.md §2): the
linked list of ``{time, busy-PE-set}`` records becomes a fixed-capacity
struct-of-arrays pytree.  All operations are functional, jit-compatible,
and use ``jax.lax`` control flow only — no host round-trips.

Layout
------
``times : int32[S]``      sorted boundaries; ``T_INF`` marks padding
``occ   : uint32[S, W]``  busy-unit bitmask during ``[times[i], times[i+1])``

``W`` packs one bitplane per resource, concatenated on the word axis
(DESIGN.md §11): plane ``r`` of a
:class:`~repro.core.resources.ResourceSpec` owns the word range
``rspec.plane_slice(r)`` and bit ``u`` of that plane is unit ``u`` of
resource ``r``.  The default scalar configuration (``rspec=None``) is
the single PE plane ``W == n_words(n_pe)`` — the paper's layout — and
every operation below is word-count agnostic, so both configurations
run the same code.

Invariants (asserted in tests, preserved by ``update``):
  * valid entries are strictly sorted and precede all padding;
  * consecutive valid rows differ (merged records, paper's "clean");
  * the first valid row is non-empty; occupancy after the last valid
    boundary is empty (all free), as is before the first;
  * bits past each plane's unit count (and outside a lane's valid
    mask) are never set.
"""
from __future__ import annotations

import functools
import operator
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import availindex as idx_lib
from repro.core.types import T_INF

_WORD = 32


def n_words(n_pe: int) -> int:
    return (n_pe + _WORD - 1) // _WORD


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n`` (and >= 2) — growth sizing."""
    return 1 << max(int(n) - 1, 1).bit_length()


class Timeline(NamedTuple):
    """Fixed-capacity availability timeline (a JAX pytree).

    The optional hierarchical availability index (DESIGN.md §12) rides
    along as three summary arrays plus the static zero-leaf
    :class:`~repro.core.availindex.IndexSpec`; all default ``None``,
    so index-free timelines keep their legacy leaf set and compiled
    graphs.  When present, every update refreshes the summaries from
    the post-update rows, so they always equal
    :func:`~repro.core.availindex.build_summaries` of the current
    timeline (see :func:`_reindex` for why the refresh is a plain
    recompute rather than a dirty-tile select).
    """

    times: jax.Array  # int32[S]
    occ: jax.Array    # uint32[S, W]
    idx_occ: Optional[jax.Array] = None      # uint32[S/T, W]
    idx_minfree: Optional[jax.Array] = None  # int32[S/T, R]
    idx_maxfree: Optional[jax.Array] = None  # int32[S/T, R]
    ispec: Optional[Any] = None              # static IndexSpec

    @property
    def capacity(self) -> int:
        return self.times.shape[0]

    @property
    def words(self) -> int:
        return self.occ.shape[1]

    def n_valid(self) -> jax.Array:
        return jnp.sum(self.times < T_INF).astype(jnp.int32)


def empty(capacity: int, n_pe: int,
          words: Optional[int] = None,
          ispec: Optional[Any] = None) -> Timeline:
    """All-free timeline; ``words`` overrides the single-plane width
    (multi-resource layouts pass ``rspec.total_words``).  ``ispec``
    attaches the hierarchical availability index (DESIGN.md §12)."""
    W = n_words(n_pe) if words is None else int(words)
    out = Timeline(
        times=jnp.full((capacity,), T_INF, dtype=jnp.int32),
        occ=jnp.zeros((capacity, W), dtype=jnp.uint32),
    )
    if ispec is not None:
        if ispec.total_words != W:
            raise ValueError(
                f"ispec covers {ispec.total_words} words, timeline "
                f"has {W}")
        i_occ, i_min, i_max = idx_lib.empty_summaries(capacity, ispec)
        out = out._replace(idx_occ=i_occ, idx_minfree=i_min,
                           idx_maxfree=i_max, ispec=ispec)
    return out


def _reindex(new_tl: Timeline, ispec) -> Timeline:
    """Index maintenance after an update (DESIGN.md §12).

    Recomputes the tile summaries from the post-update rows.  An
    earlier incremental variant kept the old summaries for tiles
    wholly before the first changed row via a dirty-from where-select;
    the select chain (searchsorted + iota + three broadcast selects)
    measured *slower* on CPU than the handful of fused popcount/reduce
    ops it reuses, and the recompute is bit-identical on clean tiles
    anyway (their rows are unchanged and the summaries are
    deterministic), so the simple form is canonical — the property
    suite pins it against :func:`~repro.core.availindex.build_summaries`
    either way.
    """
    f_occ, f_min, f_max = idx_lib.build_summaries(
        new_tl.times, new_tl.occ, ispec)
    return new_tl._replace(
        idx_occ=f_occ, idx_minfree=f_min, idx_maxfree=f_max,
        ispec=ispec,
    )


class SchedulerState(NamedTuple):
    """Complete functional scheduler state (a JAX pytree, DESIGN.md §3).

    The timeline plus the device-side pending-release buffer of
    committed reservations (``pend_te == T_INF`` marks a free slot) and
    run counters.  ``overflow`` latches when either the timeline or the
    pending buffer ran out of capacity: from then on every further
    fused-admission step is a no-op and the host wrapper must grow the
    state and re-run (see :mod:`repro.core.batch`).

    ``hw_records`` / ``hw_pending`` are high-water marks: the most
    timeline records (including the overflowing count, which may exceed
    the capacity) and pending slots any step needed so far.  The host
    wrappers read them to grow once to the max needed capacity —
    across a whole ensemble when the leading axis is vmapped
    (DESIGN.md §4) — instead of doubling blindly per retry.

    The ``park_*`` arrays are the bounded backfilling deferral queue
    (DESIGN.md §6): accepted-but-delayed requests hold their
    reservation mark here (start / end / PE mask occupy the timeline
    like any committed reservation) together with the request window
    needed to re-place them (``park_tr`` / ``park_tdl`` / ``park_npe``)
    and an FCFS sequence number (``park_seq``; ``T_INF`` marks a free
    slot, the minimum live value is the head of queue).  The queue
    capacity ``Q`` is a *static* shape: ``Q == 0`` (the default)
    compiles every backfill branch away, so pre-backfill callers keep
    their exact graphs.
    """

    tl: Timeline
    pend_ts: jax.Array    # int32[K] reservation starts
    pend_te: jax.Array    # int32[K] reservation ends; T_INF = free slot
    pend_mask: jax.Array  # uint32[K, W] reserved-PE bitmasks
    n_accepted: jax.Array  # int32 scalar
    n_released: jax.Array  # int32 scalar
    n_early_rejects: jax.Array  # int32 scalar: real requests the
    #                             index's summary_reject rejected
    n_search_tiles: jax.Array   # int32 scalar: candidate tiles the
    #                             admit searches' kernel covered
    n_search_tiles_run: jax.Array  # int32 scalar: of those, tiles
    #                                with a live candidate (the rest
    #                                were skipped)
    n_search_pe_blocks: jax.Array  # int32 scalar: (tile, PE block)
    #                                steps the kernel ran
    overflow: jax.Array    # bool scalar
    hw_records: jax.Array  # int32 scalar: max records any update needed
    hw_pending: jax.Array  # int32 scalar: max pending slots needed
    park_ts: jax.Array    # int32[Q] parked reservation starts
    park_te: jax.Array    # int32[Q] parked reservation ends
    park_mask: jax.Array  # uint32[Q, W] parked reserved-PE bitmasks
    park_tr: jax.Array    # int32[Q] ready times (re-place window lo)
    park_tdl: jax.Array   # int32[Q] deadlines (re-place window hi)
    park_npe: jax.Array   # int32[Q] PEs requested
    park_seq: jax.Array   # int32[Q] FCFS sequence; T_INF = free slot
    park_retry: jax.Array  # bool scalar: a cancel freed future
    #                        capacity; the next EASY admit step runs
    #                        the retry-on-release sweep once
    park_next_seq: jax.Array  # int32 scalar: next sequence to assign
    n_parked: jax.Array    # int32 scalar: lifetime parks
    n_promoted: jax.Array  # int32 scalar: lifetime promotions
    n_moved: jax.Array     # int32 scalar: lifetime reservation moves
    hw_parked: jax.Array   # int32 scalar: max live queue entries
    #: Optional multi-tenant table (``repro.tenancy.TenantTable``,
    #: DESIGN.md §10).  ``None`` — the default — contributes no pytree
    #: leaves, so zero-tenant sessions trace, donate, and shard the
    #: byte-identical graphs they had before tenancy existed.
    tenants: Optional[Any] = None
    #: Multi-resource extension (DESIGN.md §11), all ``None`` by
    #: default so scalar states keep their exact treedef and graphs:
    #: ``park_dem`` holds the secondary-plane demand vectors of parked
    #: requests (plane 0 stays in ``park_npe``); ``lane_valid`` is the
    #: packed valid-unit mask of this lane (heterogeneous machine
    #: sizes shrink it below the spec's padded word layout); ``rspec``
    #: is the static :class:`~repro.core.resources.ResourceSpec` —
    #: a zero-leaf pytree node, so it lives in the treedef, not in
    #: the buffers.
    park_dem: Optional[jax.Array] = None   # int32[Q, R-1]
    lane_valid: Optional[jax.Array] = None  # uint32[W]
    rspec: Optional[Any] = None

    @property
    def pending_capacity(self) -> int:
        return self.pend_te.shape[0]

    @property
    def park_capacity(self) -> int:
        return self.park_seq.shape[0]


def init_state(capacity: int, n_pe: int,
               pending_capacity: int = 256,
               park_capacity: int = 0,
               tenants: Optional[Any] = None,
               rspec: Optional[Any] = None,
               live_units=None,
               index_tile: Optional[int] = None) -> SchedulerState:
    """Fresh all-free scheduler state.

    ``park_capacity`` sizes the backfilling deferral queue; the default
    0 statically disables every backfill code path (identical compiled
    graphs to the pre-backfill core).  ``tenants`` optionally attaches
    a ``repro.tenancy.TenantTable`` (its buffer columns must match
    ``pending_capacity`` / ``park_capacity``).

    ``rspec`` (a :class:`~repro.core.resources.ResourceSpec` with
    ``units[0] == n_pe``) switches the state to the multi-resource
    layout: the occupancy and every reservation mask widen to
    ``rspec.total_words`` words, secondary-plane demands of parked
    requests persist in ``park_dem``, and ``live_units`` optionally
    shrinks this lane's schedulable units per plane (heterogeneous
    machine sizes; ``live_units[0] <= n_pe``).

    ``index_tile`` (a power of two dividing ``capacity``) attaches the
    hierarchical availability index (DESIGN.md §12): per-tile timeline
    summaries refreshed by every update, consumed for conservative
    candidate pruning and early-reject admission.  The
    default ``None`` keeps the index-free legacy treedef and graphs.
    """
    if rspec is not None and rspec.n_pe != n_pe:
        raise ValueError(
            f"rspec.units[0]={rspec.n_pe} must equal n_pe={n_pe}")
    if live_units is not None and rspec is None:
        raise ValueError("live_units requires rspec")
    ispec = None
    if index_tile is not None:
        ispec = idx_lib.make_index_spec(index_tile, n_pe, rspec)
        ispec.n_tiles(capacity)   # validates divisibility
    words = n_words(n_pe) if rspec is None else rspec.total_words
    park_dem = None
    if rspec is not None and rspec.R > 1 and park_capacity > 0:
        park_dem = jnp.zeros((park_capacity, rspec.R - 1), jnp.int32)
    lane_valid = None
    if rspec is not None:
        lane_valid = jnp.asarray(rspec.valid_mask_np(live_units))
    return SchedulerState(
        tl=empty(capacity, n_pe, words=words, ispec=ispec),
        pend_ts=jnp.full((pending_capacity,), T_INF, jnp.int32),
        pend_te=jnp.full((pending_capacity,), T_INF, jnp.int32),
        pend_mask=jnp.zeros((pending_capacity, words),
                            jnp.uint32),
        n_accepted=jnp.int32(0),
        n_released=jnp.int32(0),
        n_early_rejects=jnp.int32(0),
        n_search_tiles=jnp.int32(0),
        n_search_tiles_run=jnp.int32(0),
        n_search_pe_blocks=jnp.int32(0),
        overflow=jnp.asarray(False),
        hw_records=jnp.int32(0),
        hw_pending=jnp.int32(0),
        park_ts=jnp.full((park_capacity,), T_INF, jnp.int32),
        park_te=jnp.full((park_capacity,), T_INF, jnp.int32),
        park_mask=jnp.zeros((park_capacity, words),
                            jnp.uint32),
        park_tr=jnp.zeros((park_capacity,), jnp.int32),
        park_tdl=jnp.zeros((park_capacity,), jnp.int32),
        park_npe=jnp.zeros((park_capacity,), jnp.int32),
        park_seq=jnp.full((park_capacity,), T_INF, jnp.int32),
        park_retry=jnp.asarray(False),
        park_next_seq=jnp.int32(0),
        n_parked=jnp.int32(0),
        n_promoted=jnp.int32(0),
        n_moved=jnp.int32(0),
        hw_parked=jnp.int32(0),
        tenants=tenants,
        park_dem=park_dem,
        lane_valid=lane_valid,
        rspec=rspec,
    )


def grow_state(state: SchedulerState,
               new_capacity: int | None = None,
               new_pending_capacity: int | None = None) -> SchedulerState:
    """Host-side capacity growth of timeline and/or pending buffer.

    Padding rows never change decisions, so re-running a request stream
    on a grown copy of the pre-stream state is deterministic.
    """
    out = state
    if new_capacity is not None:
        out = out._replace(tl=grow(out.tl, new_capacity))
    if new_pending_capacity is not None:
        K = out.pending_capacity
        assert new_pending_capacity >= K
        pad = new_pending_capacity - K
        out = out._replace(
            pend_ts=jnp.concatenate(
                [out.pend_ts, jnp.full((pad,), T_INF, jnp.int32)]),
            pend_te=jnp.concatenate(
                [out.pend_te, jnp.full((pad,), T_INF, jnp.int32)]),
            pend_mask=jnp.concatenate(
                [out.pend_mask,
                 jnp.zeros((pad, out.pend_mask.shape[1]), jnp.uint32)]),
        )
        if out.tenants is not None:
            out = out._replace(tenants=out.tenants._replace(
                pend_tenant=jnp.concatenate(
                    [out.tenants.pend_tenant,
                     jnp.full((pad,), -1, jnp.int32)])))
    return out


def pe_valid_mask(n_pe: int) -> np.ndarray:
    """uint32[W] with exactly the first ``n_pe`` bits set."""
    W = n_words(n_pe)
    bits = np.zeros(W * _WORD, dtype=np.uint32)
    bits[:n_pe] = 1
    return pack_bits(bits[None, :])[0]


def ids_to_mask32(pe_ids, words: int,
                  n_pe: Optional[int] = None) -> jax.Array:
    """Sorted-or-not PE id sequence -> uint32[words] bitmask.

    Host-side only: ids must be concrete non-negative integers below
    ``n_pe`` (below ``words * 32`` when ``n_pe`` is ``None``), with no
    duplicates.  Traced values are rejected with a ``TypeError`` — a
    tracer cannot be scattered into a host numpy buffer, and silently
    mis-building a mask would corrupt the timeline invariants.
    """
    if isinstance(pe_ids, jax.core.Tracer):
        raise TypeError(
            "ids_to_mask32 is host-side: got a traced id sequence; "
            "build masks inside jit with pack_bits instead")
    limit = words * _WORD if n_pe is None else int(n_pe)
    bits = np.zeros(words * _WORD, dtype=np.uint32)
    for i in pe_ids:
        if isinstance(i, jax.core.Tracer):
            raise TypeError(
                f"ids_to_mask32 is host-side: got traced id {i!r}")
        try:
            idx = int(operator.index(
                i.item() if isinstance(i, (jax.Array, np.ndarray))
                else i))
        except TypeError as e:
            raise TypeError(
                f"PE id {i!r} is not an integer") from e
        if not 0 <= idx < limit:
            raise ValueError(
                f"PE id {idx} out of range [0, {limit})")
        if bits[idx]:
            raise ValueError(f"duplicate PE id {idx}")
        bits[idx] = 1
    return jnp.asarray(pack_bits(bits[None, :])[0])


def pack_bits(bits: np.ndarray | jax.Array) -> jax.Array:
    """[..., W*32] 0/1 -> uint32 [..., W] little-endian within words."""
    xp = jnp if isinstance(bits, jax.Array) else np
    *lead, nbits = bits.shape
    assert nbits % _WORD == 0
    b = bits.reshape(*lead, nbits // _WORD, _WORD).astype(xp.uint32)
    shifts = xp.arange(_WORD, dtype=xp.uint32)
    return (b << shifts).sum(axis=-1).astype(xp.uint32)


def unpack_bits(words: jax.Array, n_pe: int) -> jax.Array:
    """uint32 [..., W] -> 0/1 int8 [..., n_pe]."""
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * _WORD)[
        ..., :n_pe].astype(jnp.int8)


def occupancy_at(tl: Timeline, t: jax.Array) -> jax.Array:
    """Busy bitmask in effect at instant ``t`` (zeros outside records)."""
    idx = jnp.searchsorted(tl.times, t, side="right") - 1
    in_range = (idx >= 0) & (jnp.take(tl.times, jnp.maximum(idx, 0)) < T_INF)
    row = jnp.take(tl.occ, jnp.clip(idx, 0, tl.capacity - 1), axis=0)
    return jnp.where(in_range, row, jnp.uint32(0))


def next_times(tl: Timeline) -> jax.Array:
    """End of each slot's interval; padding rows get ``T_INF``."""
    return jnp.concatenate(
        [tl.times[1:], jnp.array([T_INF], dtype=jnp.int32)])


def _merge_compact(ext_t: jax.Array, ext_o: jax.Array, S: int,
                   words: int) -> Tuple[Timeline, jax.Array, jax.Array]:
    """Shared epilogue of every update: merge + scatter-compact.

    ``ext_t``/``ext_o`` are the time-sorted extended rows (originals
    plus inserted boundaries, already range-updated).  Keeps rows whose
    occupancy differs from the previous kept row — duplicates carry
    identical occupancy after the range update, so comparing against
    the immediate predecessor suffices — then scatter-compacts the
    survivors back into capacity ``S``.
    """
    R = ext_t.shape[0]
    prev = jnp.concatenate(
        [jnp.zeros((1, words), jnp.uint32), ext_o[:-1]])
    keep = (ext_t < T_INF) & jnp.any(ext_o != prev, axis=1)
    pos = jnp.cumsum(keep) - 1
    dest = jnp.where(keep, pos, R - 1)
    out_t = jnp.full((R,), T_INF, jnp.int32).at[dest].set(
        jnp.where(keep, ext_t, T_INF))
    out_o = jnp.zeros((R, words), jnp.uint32).at[dest].set(
        jnp.where(keep[:, None], ext_o, jnp.uint32(0)))
    n_keep = jnp.sum(keep).astype(jnp.int32)
    overflow = n_keep > S
    return Timeline(times=out_t[:S], occ=out_o[:S]), overflow, n_keep


@functools.partial(jax.jit, static_argnames=("is_add", "with_count"))
def update(tl: Timeline, t_s: jax.Array, t_e: jax.Array,
           mask: jax.Array, *, is_add: bool,
           with_count: bool = False
           ) -> Union[Tuple[Timeline, jax.Array],
                      Tuple[Timeline, jax.Array, jax.Array]]:
    """Functional ``addAllocation`` / ``deleteAllocation`` (Algorithms 1-2).

    Inserts the two boundary records, ORs (or AND-NOTs) ``mask`` into
    every record in ``[t_s, t_e)``, merges redundant records, and
    re-compacts into the same capacity.  Returns ``(new_tl, overflow)``
    where ``overflow`` flags that the compacted timeline needed more
    than ``S`` records (callers must grow and retry — see scheduler).
    With ``with_count=True`` returns ``(new_tl, overflow, n_keep)``
    where ``n_keep`` is the record count the result *needed* (it may
    exceed the capacity ``S``) — the growth wrappers use it to size
    the retry in one step.

    Sort-free (DESIGN.md §7): the timeline is sorted by invariant, so
    the two boundary records are placed with ``searchsorted`` and a
    shift-gather instead of re-lexsorting all ``S + 2`` rows on every
    insert.  Bit-identical to :func:`update_lexsort` (the retained
    oracle, asserted by ``tests/test_timeline_fast.py``).
    """
    S = tl.capacity
    t_s = jnp.asarray(t_s, jnp.int32)
    t_e = jnp.asarray(t_e, jnp.int32)
    # 0. clamp malformed intervals to a provable no-op.  A ``t_e`` at
    #    or past the ``T_INF`` sentinel would make the half-open range
    #    update ``t < t_e`` cover the padding tail forever (occupancy
    #    that can never be released — a silently corrupted invariant);
    #    map such intervals to the empty ``[T_INF, T_INF) x 0`` update,
    #    whose inserted boundary rows the merge pass drops.
    valid_iv = (t_s < t_e) & (t_e < T_INF)
    t_s = jnp.where(valid_iv, t_s, T_INF)
    t_e = jnp.where(valid_iv, t_e, T_INF)
    mask = jnp.where(valid_iv, mask, jnp.zeros_like(mask))
    # 1. merged positions of the two inserted boundary records: after
    #    all originals of equal time ('right'), and — matching the
    #    retained lexsort oracle's stable tie-break — the t_s record
    #    before the t_e record when the two coincide.
    i_s = jnp.searchsorted(tl.times, t_s, side="right").astype(jnp.int32)
    i_e = jnp.searchsorted(tl.times, t_e, side="right").astype(jnp.int32)
    pos_s = i_s + (t_e < t_s).astype(jnp.int32)
    pos_e = i_e + (t_s <= t_e).astype(jnp.int32)
    # 2. shift-gather the originals around the two insertion points;
    #    inserted records inherit the occupancy in effect at their
    #    instant.
    idx = jnp.arange(S + 2, dtype=jnp.int32)
    src = idx - (idx > pos_s).astype(jnp.int32) \
        - (idx > pos_e).astype(jnp.int32)
    src = jnp.clip(src, 0, S - 1)
    ext_t = jnp.where(
        idx == pos_s, t_s,
        jnp.where(idx == pos_e, t_e, tl.times[src]))
    ext_o = jnp.where(
        (idx == pos_s)[:, None], occupancy_at(tl, t_s)[None, :],
        jnp.where((idx == pos_e)[:, None],
                  occupancy_at(tl, t_e)[None, :], tl.occ[src]))
    # 3. apply the range update.
    in_range = (ext_t >= t_s) & (ext_t < t_e)
    if is_add:
        upd = ext_o | mask[None, :]
    else:
        upd = ext_o & ~mask[None, :]
    ext_o = jnp.where(in_range[:, None], upd, ext_o)
    # 4.-5. merge + scatter-compact back to capacity S.
    out, overflow, n_keep = _merge_compact(ext_t, ext_o, S, tl.words)
    if tl.ispec is not None:
        out = _reindex(out, tl.ispec)
    if with_count:
        return out, overflow, n_keep
    return out, overflow


@functools.partial(jax.jit, static_argnames=("is_add", "with_count"))
def update_lexsort(tl: Timeline, t_s: jax.Array, t_e: jax.Array,
                   mask: jax.Array, *, is_add: bool,
                   with_count: bool = False
                   ) -> Union[Tuple[Timeline, jax.Array],
                              Tuple[Timeline, jax.Array, jax.Array]]:
    """The original lexsort-based :func:`update` (the PR 1-4 hot path).

    Retained as the bit-exactness oracle for the sort-free
    implementations: ``tests/test_timeline_fast.py`` fuzzes
    :func:`update` and :func:`update_many` against it.  Not used on
    any hot path.
    """
    S = tl.capacity
    t_s = jnp.asarray(t_s, jnp.int32)
    t_e = jnp.asarray(t_e, jnp.int32)
    # 1. extend with the two (possibly duplicate) boundary records,
    #    inheriting the occupancy in effect at each instant.
    ext_t = jnp.concatenate([tl.times, jnp.stack([t_s, t_e])])
    ext_o = jnp.concatenate(
        [tl.occ, jnp.stack([occupancy_at(tl, t_s), occupancy_at(tl, t_e)])])
    is_new = jnp.zeros(S + 2, jnp.int32).at[S:].set(1)
    # 2. stable order: by time, originals before inserted duplicates so
    #    that the merge pass removes the duplicate.
    perm = jnp.lexsort((is_new, ext_t))
    ext_t, ext_o = ext_t[perm], ext_o[perm]
    # 3. apply the range update.
    in_range = (ext_t >= t_s) & (ext_t < t_e)
    if is_add:
        upd = ext_o | mask[None, :]
    else:
        upd = ext_o & ~mask[None, :]
    ext_o = jnp.where(in_range[:, None], upd, ext_o)
    # 4.-5. merge + scatter-compact back to capacity S.
    out, overflow, n_keep = _merge_compact(ext_t, ext_o, S, tl.words)
    if tl.ispec is not None:
        out = _reindex(out, tl.ispec)
    if with_count:
        return out, overflow, n_keep
    return out, overflow


@functools.partial(jax.jit, static_argnames=("is_add", "with_count"))
def update_many(tl: Timeline, t_s: jax.Array, t_e: jax.Array,
                masks: jax.Array, active: jax.Array, *, is_add: bool,
                with_count: bool = False
                ) -> Union[Tuple[Timeline, jax.Array],
                           Tuple[Timeline, jax.Array, jax.Array]]:
    """Batched ``update``: K same-direction intervals, one merge pass.

    Applies every interval ``[t_s[k], t_e[k]) x masks[k]`` with
    ``active[k]`` set — all adds or all deletes (``is_add`` is
    static).  Same-direction interval updates commute (a segment's
    occupancy is the OR / AND-NOT of the union of covering masks) and
    the merged compacted timeline is a *canonical* representation of
    the occupancy step function, so one batched pass is bit-identical
    to applying the K intervals through :func:`update` sequentially —
    the decision-safety argument of DESIGN.md §7 — while paying one
    boundary union + one segment-mask pass + one merge/compact
    instead of K.

    ``overflow`` flags that the final compacted result needed more
    than ``S`` records (``n_keep`` with ``with_count=True``); unlike
    a sequential chain there are no intermediate states, so a batch
    whose *end state* fits never overflows even if some sequential
    order would have spiked past ``S`` transiently.
    """
    S, W = tl.capacity, tl.words
    K = t_s.shape[0]
    t_s = jnp.asarray(t_s, jnp.int32)
    t_e = jnp.asarray(t_e, jnp.int32)
    # malformed intervals (t_e at/past the T_INF sentinel) would smear
    # their mask over the padding tail; deactivate them — the same
    # no-op clamp as :func:`update`.
    active = jnp.asarray(active, bool) & (t_s < t_e) & (t_e < T_INF)
    R = S + 2 * K
    # 1. boundary records: both endpoints of every active interval;
    #    inactive intervals contribute T_INF rows, which the merge
    #    drops.  Inserted records go after originals of equal time;
    #    ties among boundaries break by position (t_s block first).
    b_t = jnp.where(jnp.concatenate([active, active]),
                    jnp.concatenate([t_s, t_e]), T_INF)
    base = jnp.searchsorted(tl.times, b_t, side="right").astype(jnp.int32)
    lt = b_t[None, :] < b_t[:, None]
    tie = (b_t[None, :] == b_t[:, None]) & \
        (jnp.arange(2 * K)[None, :] < jnp.arange(2 * K)[:, None])
    rank = jnp.sum(lt | tie, axis=1).astype(jnp.int32)
    pos_b = base + rank
    # originals shift right past every boundary strictly below them
    pos_o = jnp.arange(S, dtype=jnp.int32) + jnp.sum(
        b_t[None, :] < tl.times[:, None], axis=1).astype(jnp.int32)
    # 2. scatter originals + boundaries into the merged order (the
    #    positions are pairwise distinct and cover [0, R) exactly).
    occ_b = jax.vmap(occupancy_at, in_axes=(None, 0))(tl, b_t)
    ext_t = jnp.zeros((R,), jnp.int32).at[pos_o].set(
        tl.times).at[pos_b].set(b_t)
    ext_o = jnp.zeros((R, W), jnp.uint32).at[pos_o].set(
        tl.occ).at[pos_b].set(occ_b)
    # 3. segment-mask union: OR (add) / AND-NOT (delete) of every
    #    active interval covering each record's instant.
    cover = active[None, :] & (t_s[None, :] <= ext_t[:, None]) & \
        (ext_t[:, None] < t_e[None, :])                        # [R, K]
    union = jax.lax.reduce(
        jnp.where(cover[:, :, None], masks[None, :, :], jnp.uint32(0)),
        np.uint32(0), jax.lax.bitwise_or, (1,))                # [R, W]
    if is_add:
        ext_o = ext_o | union
    else:
        ext_o = ext_o & ~union
    # 4.-5. merge + scatter-compact back to capacity S.
    out, overflow, n_keep = _merge_compact(ext_t, ext_o, S, W)
    if tl.ispec is not None:
        out = _reindex(out, tl.ispec)
    if with_count:
        return out, overflow, n_keep
    return out, overflow


@jax.jit
def window_busy(tl: Timeline, a: jax.Array, b: jax.Array) -> jax.Array:
    """Union of busy masks over records intersecting ``[a, b)``."""
    nxt = next_times(tl)
    ov = (tl.times < b) & (nxt > a)
    masked = jnp.where(ov[:, None], tl.occ, jnp.uint32(0))
    return jax.lax.reduce(masked, np.uint32(0), jax.lax.bitwise_or, (0,))


def grow(tl: Timeline, new_capacity: int) -> Timeline:
    """Host-side capacity growth (static shape change; not jitted).

    An attached index is re-materialised at the new tile count (the
    old tiles' values are unchanged — padding rows summarise to the
    all-free sentinel — but the arrays change shape, so a fresh build
    is the simplest bit-exact form).
    """
    assert new_capacity >= tl.capacity
    pad = new_capacity - tl.capacity
    out = Timeline(
        times=jnp.concatenate(
            [tl.times, jnp.full((pad,), T_INF, jnp.int32)]),
        occ=jnp.concatenate(
            [tl.occ, jnp.zeros((pad, tl.words), jnp.uint32)]),
    )
    if tl.ispec is not None:
        i_occ, i_min, i_max = idx_lib.build_summaries(
            out.times, out.occ, tl.ispec)
        out = out._replace(idx_occ=i_occ, idx_minfree=i_min,
                           idx_maxfree=i_max, ispec=tl.ispec)
    return out


def from_host(times: np.ndarray, occ64: np.ndarray, n_pe: int,
              capacity: int) -> Timeline:
    """Build a device timeline from the host engine's uint64 rows."""
    S = times.shape[0]
    assert S <= capacity, "host timeline exceeds device capacity"
    bits = np.zeros((S, n_words(n_pe) * _WORD), dtype=np.uint32)
    for w in range(occ64.shape[1]):
        lo = (occ64[:, w] & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (occ64[:, w] >> np.uint64(32)).astype(np.uint32)
        if 2 * w * _WORD < bits.shape[1]:
            bits[:, 2 * w * _WORD:(2 * w + 1) * _WORD] = _expand32(lo)
        if (2 * w + 1) * _WORD < bits.shape[1]:
            bits[:, (2 * w + 1) * _WORD:(2 * w + 2) * _WORD] = _expand32(hi)
    tl = empty(capacity, n_pe)
    return Timeline(
        times=tl.times.at[:S].set(jnp.asarray(times, jnp.int32)),
        occ=tl.occ.at[:S].set(pack_bits(bits)),
    )


def _expand32(words: np.ndarray) -> np.ndarray:
    shifts = np.arange(_WORD, dtype=np.uint32)
    return ((words[:, None] >> shifts) & np.uint32(1)).astype(np.uint32)
