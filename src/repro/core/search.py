"""Device-side ``findAllocation`` (Algorithm 3), fully vectorised.

The paper's per-candidate scan — "for every optional start time, get the
free PEs in the window, then expand to the maximum availability
rectangle" — is reformulated as two dense matrix products over the
bit-expanded occupancy (DESIGN.md §2):

    busy[P, pe]     = (overlap[P, S] @ occ_bits[S, pe]) > 0
    blocking[P, S]  = (free[P, pe]   @ occ_bits[S, pe]^T) > 0

so the whole search maps onto the MXU.  The rectangle bounds are then
masked min/max reductions over the slot axis.  ``kernels/availscan``
implements the same contraction as a Pallas kernel; this module is the
pure-jnp path (and the oracle the kernel is tested against).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import availindex as idx_lib
from repro.core import policies as policies_lib
from repro.core import timeline as tl_lib
from repro.core.timeline import Timeline
from repro.core.types import T_INF


class SearchResult(NamedTuple):
    found: jax.Array      # bool
    t_s: jax.Array        # int32 chosen start
    t_e: jax.Array        # int32 chosen end
    pe_mask: jax.Array    # uint32[W] chosen PEs
    n_free: jax.Array     # int32 free PEs in the winning rectangle
    t_begin: jax.Array    # int32 rectangle begin
    t_end: jax.Array      # int32 rectangle end
    # work counts the admit step folds into its state counters
    early_reject: jax.Array  # bool: the index's summary_reject fired
    tiles: jax.Array      # int32 candidate tiles the kernel covered
    tiles_run: jax.Array  # int32 of those, tiles with a live candidate
    pe_blocks: jax.Array  # int32 (tile, PE block) steps the kernel ran

class Rectangles(NamedTuple):
    """Per-candidate maximum availability rectangles.

    ``n_free`` counts plane-0 (PE) units; under a multi-resource
    layout ``n_free_tail`` carries the free-unit counts of planes
    1..R-1 (``None`` on the scalar path — the field defaults keep the
    legacy pytree structure unchanged).
    """

    starts: jax.Array    # int32[P]
    n_free: jax.Array    # int32[P]
    t_begin: jax.Array   # int32[P]
    t_end: jax.Array     # int32[P]
    valid: jax.Array     # bool[P]
    n_free_tail: Optional[jax.Array] = None  # int32[P, R-1]


def candidate_starts(tl: Timeline, t_r: jax.Array, t_du: jax.Array,
                     t_dl: jax.Array) -> jax.Array:
    """int32[2S+2] candidates; infeasible slots padded with T_INF.

    Candidates are the ready time, the latest start, every boundary in
    range, and every boundary shifted left by the duration (end-aligned
    placements) — the paper's Section 4.2 enumeration.

    The sorted array is *deduplicated and compacted* (DESIGN.md §7):
    distinct live candidates ascending at the front, all duplicates
    and out-of-window slots collapsed into the ``T_INF`` tail.
    Duplicates share their first occurrence's start value, hence its
    rectangle and policy score, so dropping them never changes the
    selected start; compaction makes the effective candidate count
    track *live* boundaries instead of static capacity, which is what
    lets the availscan kernel skip all-padding tiles.
    """
    lo = t_r
    hi = t_dl - t_du

    def in_range(x):
        return (x >= lo) & (x <= hi) & (x < T_INF)

    c_bound = jnp.where(in_range(tl.times), tl.times, T_INF)
    shifted = jnp.where(tl.times < T_INF, tl.times - t_du, T_INF)
    c_shift = jnp.where(in_range(shifted), shifted, T_INF)
    ends = jnp.stack([lo, hi]).astype(jnp.int32)
    cand = jnp.sort(jnp.concatenate([ends, c_bound, c_shift]))
    # dedupe + compact: keep the first occurrence of each distinct
    # live value, scatter the survivors to the front in order.
    P = cand.shape[0]
    keep = (cand < T_INF) & jnp.concatenate(
        [jnp.ones((1,), bool), cand[1:] != cand[:-1]])
    dest = jnp.where(keep, jnp.cumsum(keep) - 1, P)
    return jnp.full((P + 1,), T_INF, jnp.int32).at[dest].set(
        jnp.where(keep, cand, T_INF))[:P]


def _index_demand(ispec, n_req: jax.Array,
                  demand_tail: Optional[jax.Array]) -> jax.Array:
    """int32[R] full per-plane demand vector for index bounds."""
    head = jnp.asarray(n_req, jnp.int32)[None]
    if ispec.R == 1 or demand_tail is None:
        return jnp.concatenate(
            [head, jnp.zeros((ispec.R - 1,), jnp.int32)])
    return jnp.concatenate(
        [head, jnp.asarray(demand_tail, jnp.int32)])


def summary_reject(tl: Timeline, t_r: jax.Array, t_du: jax.Array,
                   t_dl: jax.Array, demand: jax.Array,
                   deficit: jax.Array) -> jax.Array:
    """Conservative whole-request infeasibility proof (DESIGN.md §12).

    True only when *no* window ``[s, s + t_du)`` with ``s`` in
    ``[t_r, t_dl - t_du]`` can be feasible, so the caller may skip the
    full search and emit the exact rejected result.  Two proofs:

    1. capacity: some plane demands more units than the lane has;
    2. tile max-free: with ``t_r >= times[0]``, every window start
       lands inside some record's interval, that record's tile
       intersects the span ``[t_r, t_dl)``, and a window's free count
       never exceeds a covering row's — so if *every* tile
       intersecting the span proves ``maxfree - deficit < demand`` on
       some plane, every window is infeasible.

    An empty timeline (``times[0] == T_INF``) or a window reaching
    past the last record (whose all-free row summarises to
    ``maxfree == units``) never rejects — conservativeness needs no
    special cases.
    """
    ispec = tl.ispec
    S, T = tl.capacity, ispec.tile
    NT = S // T
    units = jnp.asarray(ispec.units, jnp.int32)
    lo = jnp.asarray(t_r, jnp.int32)
    hi = jnp.asarray(t_dl, jnp.int32) - jnp.asarray(t_du, jnp.int32)
    cap_reject = jnp.any(demand > units - deficit)
    tile_t0 = tl.times.reshape(NT, T)[:, 0]
    tile_end = jnp.concatenate(
        [tile_t0[1:], jnp.array([T_INF], jnp.int32)])
    intersect = (tile_t0 < jnp.asarray(t_dl, jnp.int32)) \
        & (tile_end > lo)
    bad = jnp.any(tl.idx_maxfree - deficit[None, :]
                  < demand[None, :], axis=1)              # [NT]
    guard = (hi >= lo) & (tl.times[0] <= lo)
    tile_reject = (guard & jnp.any(intersect)
                   & jnp.all(~intersect | bad))
    return cap_reject | tile_reject


def prune_candidates(tl: Timeline, starts: jax.Array, t_du: jax.Array,
                     demand: jax.Array,
                     deficit: jax.Array) -> jax.Array:
    """Mask summary-infeasible candidates to the ``T_INF`` sentinel.

    A candidate window fully containing tile ``k`` unions at least
    ``idx_occ[k]`` into its busy mask, so its free count is bounded by
    ``idx_minfree[k] - deficit`` per plane; any contained tile proving
    ``< demand`` makes the candidate truly infeasible.  Conservative:
    pruned candidates could never win selection, so decisions are
    bit-identical — and candidate 0 (the all-infeasible fallback the
    rejected-decision fields report) is never pruned.
    """
    ispec = tl.ispec
    S, T = tl.capacity, ispec.tile
    NT = S // T
    a = jnp.minimum(starts, T_INF - t_du)
    b = a + t_du
    tile_last = tl.times.reshape(NT, T)[:, -1]
    tile_nxt0 = tl_lib.next_times(tl).reshape(NT, T)[:, 0]
    contained = (tile_last[None, :] < b[:, None]) \
        & (tile_nxt0[None, :] > a[:, None])               # [P, NT]
    bad = jnp.any(tl.idx_minfree - deficit[None, :]
                  < demand[None, :], axis=1)              # [NT]
    prune = jnp.any(contained & bad[None, :], axis=1)
    keep0 = jnp.arange(starts.shape[0]) > 0
    return jnp.where(prune & keep0, T_INF, starts)


def availability_rectangles(
    tl: Timeline, starts: jax.Array, t_du: jax.Array, t_now: jax.Array,
    n_pe: int, *, rspec=None, valid_mask: Optional[jax.Array] = None,
) -> Rectangles:
    """Maximum availability rectangle per candidate (Algorithm 3 l.6-9).

    The pure-jnp reference path computes both contractions directly on
    the *packed* uint32 occupancy words (bitwise OR / AND + popcount)
    instead of bit-expanding to a ``[S, n_pe]`` float matrix: the
    booleans are identical to the MXU formulation of DESIGN.md §2
    (which the Pallas kernel keeps), but each uint32 op covers 32 PEs,
    so the hot contraction shrinks ~32x on CPU/VPU hardware.

    Invalid candidates (``T_INF`` padding) are masked to fixed
    sentinels (``n_free = t_begin = t_end = 0``) so the kernel path
    can skip all-padding tiles and still match this reference
    element-for-element; sentinels can never win selection (invalid
    candidates are never feasible) and the all-infeasible fallback
    index 0 is always a live candidate.

    Multi-resource layouts (DESIGN.md §11) pass ``rspec``: the free
    union is masked with the lane's ``valid_mask`` (defaulting to the
    spec's full padded layout) and popcounted *per bitplane*, yielding
    the plane-0 ``n_free`` the policies score plus ``n_free_tail`` for
    the vector fit test.  With ``R == 1`` and a full valid mask the
    counts — and the blocking booleans, since occupancy bits only ever
    appear on valid units — are identical to the scalar path.
    """
    nxt = tl_lib.next_times(tl)
    valid = starts < T_INF
    a = jnp.minimum(starts, T_INF - t_du)       # avoid int32 overflow
    b = a + t_du
    # window overlap and busy-unit union (bitwise OR over packed words)
    ov = ((tl.times[None, :] < b[:, None]) &
          (nxt[None, :] > a[:, None]))                          # [P, S]
    busy_w = jax.lax.reduce(
        jnp.where(ov[:, :, None], tl.occ[None, :, :], jnp.uint32(0)),
        np.uint32(0), jax.lax.bitwise_or, (1,))                 # [P, W]
    n_free_tail = None
    if rspec is None:
        # occupancy words never set bits past n_pe (timeline
        # invariant), so the popcount of the busy union counts real
        # PEs only
        n_free = (n_pe - jnp.sum(
            jax.lax.population_count(busy_w), axis=1).astype(jnp.int32))
        free_w = ~busy_w                                        # [P, W]
    else:
        if valid_mask is None:
            valid_mask = jnp.asarray(rspec.valid_mask_np())
        free_w = ~busy_w & valid_mask[None, :]                  # [P, W]
        counts = jax.lax.population_count(free_w)
        plane_free = [
            jnp.sum(counts[:, rspec.plane_slice(r)],
                    axis=1).astype(jnp.int32)
            for r in range(rspec.R)]
        n_free = plane_free[0]
        if rspec.R > 1:
            n_free_tail = jnp.stack(plane_free[1:], axis=1)
        else:
            n_free_tail = jnp.zeros((starts.shape[0], 0), jnp.int32)
    # blocking slots: a slot blocks iff it occupies any free unit
    # (bitwise AND against the free-word union; junk free bits past
    # n_pe never match because occupancy words are clean there)
    blocking = jnp.any(
        (free_w[:, None, :] & tl.occ[None, :, :]) != 0, axis=2)  # [P, S]
    left = blocking & (nxt[None, :] <= a[:, None])
    t_begin = jnp.max(jnp.where(left, nxt[None, :], -T_INF), axis=1)
    t_begin = jnp.minimum(jnp.maximum(t_begin, t_now), a)
    right = blocking & (tl.times[None, :] >= b[:, None])
    t_end = jnp.min(jnp.where(right, tl.times[None, :], T_INF), axis=1)
    zero = jnp.int32(0)
    return Rectangles(
        starts=starts,
        n_free=jnp.where(valid, n_free, zero),
        t_begin=jnp.where(valid, t_begin, zero),
        t_end=jnp.where(valid, t_end, zero),
        valid=valid,
        n_free_tail=(None if n_free_tail is None
                     else jnp.where(valid[:, None], n_free_tail, zero)))


def _winning_pe_mask(tl: Timeline, t_s: jax.Array, t_du: jax.Array,
                     n_req: jax.Array, n_pe: int) -> jax.Array:
    """Lowest-index ``n_req`` free PEs over the winning window."""
    a = jnp.minimum(t_s, T_INF - t_du)
    busy = tl_lib.window_busy(tl, a, a + t_du)          # uint32[W]
    free_bits = (1 - tl_lib.unpack_bits(busy[None, :], n_pe)[0]
                 ).astype(jnp.int32)                    # [n_pe]
    csum = jnp.cumsum(free_bits)
    sel = (free_bits == 1) & (csum <= n_req)
    W = tl.words
    sel_padded = jnp.zeros((W * 32,), jnp.uint32).at[:n_pe].set(
        sel.astype(jnp.uint32))
    return tl_lib.pack_bits(sel_padded[None, :])[0]


def _winning_mask_mr(tl: Timeline, t_s: jax.Array, t_du: jax.Array,
                     n_req: jax.Array, demand_tail: jax.Array,
                     rspec, valid_mask: jax.Array) -> jax.Array:
    """Lowest-index free *valid* units per plane over the window.

    The plane-0 pick matches :func:`_winning_pe_mask` bit-for-bit on
    a full-width lane (invalid bits are never free, so the cumsum
    walks the same unit order); secondary planes allocate their
    ``demand_tail[r-1]`` units the same way in their own bit range.
    """
    a = jnp.minimum(t_s, T_INF - t_du)
    busy = tl_lib.window_busy(tl, a, a + t_du)      # uint32[W]
    free_w = ~busy & valid_mask
    out = []
    for r in range(rspec.R):
        wr = rspec.words_per[r]
        fb = tl_lib.unpack_bits(
            free_w[None, rspec.plane_slice(r)],
            wr * 32)[0].astype(jnp.int32)           # [wr*32]
        need = n_req if r == 0 else demand_tail[r - 1]
        sel = (fb == 1) & (jnp.cumsum(fb) <= need)
        out.append(tl_lib.pack_bits(
            sel.astype(jnp.uint32)[None, :])[0])
    return jnp.concatenate(out)


def search(
    tl: Timeline,
    t_r: jax.Array,
    t_du: jax.Array,
    t_dl: jax.Array,
    n_req: jax.Array,
    policy_id: jax.Array,
    t_now: jax.Array,
    *,
    n_pe: int,
    use_kernel: bool = False,
    rspec=None,
    demand_tail: Optional[jax.Array] = None,
    valid_mask: Optional[jax.Array] = None,
) -> SearchResult:
    """Full Algorithm 3: candidates -> rectangles -> policy -> PE pick.

    Trace-time body, deliberately not jitted: :func:`find_allocation`
    wraps it for standalone use, :mod:`repro.core.batch` inlines it
    into the fused ``admit`` step so find+commit compile as one
    program, and :mod:`repro.core.ensemble` vmaps it over stacked
    timelines (all inputs tolerate a leading ensemble axis — the
    kernel path included).

    ``rspec`` switches to the multi-resource vector fit (DESIGN.md
    §11): a candidate is feasible iff plane 0 fits ``n_req`` *and*
    every secondary plane fits its ``demand_tail`` entry, policies
    keep scoring the plane-0 ``n_free``, and the winning mask spans
    all planes.  ``valid_mask`` (default: the spec's full layout)
    carries per-lane machine sizes.

    An indexed timeline (``tl.ispec`` set, DESIGN.md §12) adds two
    conservative fast paths: a whole-search early-reject ``lax.cond``
    that proves no feasible window exists and emits the exact
    rejected result without enumerating candidates (the dominant win
    on saturated streams — and, vmapped, the fleet probe's lane
    prefilter), and — on the kernel path only — summary pruning that
    masks provably-infeasible candidates to the ``T_INF`` sentinel so
    the availscan kernels' data-driven tile skip drops their tiles
    (the jnp reference path evaluates every candidate slot at fixed
    shape, so pruning there saves nothing).  Both are conservative
    (summary-infeasible implies truly infeasible), so every result
    stays bit-identical to the index-free search.

    The result also carries the search's work counts: whether the
    early reject fired, and on the kernel path the candidate tiles the
    kernel covered and those it ran (0 on the jnp path).  Its phases
    carry the ``admit.search.*`` named scopes (DESIGN.md §13).
    """
    if rspec is not None:
        if valid_mask is None:
            valid_mask = jnp.asarray(rspec.valid_mask_np())
        if demand_tail is None:
            demand_tail = jnp.zeros((rspec.R - 1,), jnp.int32)
        demand_tail = jnp.asarray(demand_tail, jnp.int32)
    if tl.ispec is not None:
        with jax.named_scope("admit.search.reject"):
            demand_vec = _index_demand(tl.ispec, n_req, demand_tail)
            deficit = idx_lib.plane_deficit(tl.ispec, valid_mask)
            reject = summary_reject(tl, t_r, t_du, t_dl, demand_vec,
                                    deficit)

        def _rejected(_):
            # bit-exact cheap branch: selection over an all-infeasible
            # candidate set falls back to index 0, whose start is the
            # minimum live candidate — min(t_r, t_dl - t_du) — and the
            # rejected Decision reports that candidate's rectangle
            with jax.named_scope("admit.search.reject"):
                starts0 = jnp.minimum(
                    jnp.asarray(t_r, jnp.int32),
                    jnp.asarray(t_dl, jnp.int32)
                    - jnp.asarray(t_du, jnp.int32))[None]
                rects = availability_rectangles(
                    tl, starts0, t_du, t_now, n_pe, rspec=rspec,
                    valid_mask=valid_mask)
                return SearchResult(
                    found=jnp.asarray(False),
                    t_s=starts0[0],
                    t_e=starts0[0] + jnp.asarray(t_du, jnp.int32),
                    pe_mask=jnp.zeros((tl.words,), jnp.uint32),
                    n_free=rects.n_free[0],
                    t_begin=rects.t_begin[0],
                    t_end=rects.t_end[0],
                    early_reject=jnp.asarray(True), tiles=jnp.int32(0),
                    tiles_run=jnp.int32(0), pe_blocks=jnp.int32(0),
                )

        def _full(_):
            return _search_full(
                tl, t_r, t_du, t_dl, n_req, policy_id, t_now,
                n_pe=n_pe, use_kernel=use_kernel, rspec=rspec,
                demand_tail=demand_tail, valid_mask=valid_mask,
                demand_vec=demand_vec, deficit=deficit)

        return jax.lax.cond(reject, _rejected, _full, 0)
    return _search_full(
        tl, t_r, t_du, t_dl, n_req, policy_id, t_now, n_pe=n_pe,
        use_kernel=use_kernel, rspec=rspec, demand_tail=demand_tail,
        valid_mask=valid_mask, demand_vec=None, deficit=None)


def _search_full(
    tl: Timeline,
    t_r: jax.Array,
    t_du: jax.Array,
    t_dl: jax.Array,
    n_req: jax.Array,
    policy_id: jax.Array,
    t_now: jax.Array,
    *,
    n_pe: int,
    use_kernel: bool,
    rspec,
    demand_tail: Optional[jax.Array],
    valid_mask: Optional[jax.Array],
    demand_vec: Optional[jax.Array],
    deficit: Optional[jax.Array],
) -> SearchResult:
    """The candidate enumeration half of :func:`search` (see there)."""
    with jax.named_scope("admit.search.candidates"):
        starts = candidate_starts(tl, t_r, t_du, t_dl)
        if tl.ispec is not None and use_kernel:
            # summary pruning feeds the availscan kernels' data-driven
            # tile skip: a pruned start becomes T_INF padding, so its
            # tile never loads.  The jnp reference path evaluates
            # every candidate slot at fixed shape regardless, so
            # pruning there is pure per-request cost — the mask
            # changes nothing the where-select downstream wouldn't
            # (pruned candidates are truly infeasible and could never
            # win selection either way).
            starts = prune_candidates(tl, starts, t_du, demand_vec,
                                      deficit)
    sel = None
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        # fused path: rectangles + policy selection in one kernel —
        # the per-candidate vectors never round-trip through HBM
        with jax.named_scope("admit.search.rects"):
            sel = kernel_ops.search_select(
                tl, starts, t_du, t_now, n_req, policy_id, n_pe=n_pe,
                rspec=rspec, demand_tail=demand_tail,
                valid_mask=valid_mask)
    if sel is not None:
        found = sel["found"]
        t_s = starts[sel["best"]]
        n_free, t_begin, t_end = sel["n_free"], sel["t_begin"], \
            sel["t_end"]
        work = dict(early_reject=jnp.asarray(False), tiles=sel["tiles"],
                    tiles_run=sel["tiles_run"], pe_blocks=sel["pe_blocks"])
    else:
        # jnp reference path — also the fallback when search_select
        # returned None (shape beyond the kernel VMEM budget; the
        # unfused kernel entry exists for the element-wise oracle
        # tests)
        with jax.named_scope("admit.search.rects"):
            rects = availability_rectangles(
                tl, starts, t_du, t_now, n_pe, rspec=rspec,
                valid_mask=valid_mask)
            feasible = rects.valid & (rects.n_free >= n_req)
            if rspec is not None and rspec.R > 1:
                feasible = feasible & jnp.all(
                    rects.n_free_tail >= demand_tail[None, :], axis=1)
            duration = rects.t_end - rects.t_begin
            best, found = policies_lib.select(
                policy_id, rects.n_free, duration, rects.starts,
                feasible)
            t_s = rects.starts[best]
            n_free, t_begin, t_end = rects.n_free[best], \
                rects.t_begin[best], rects.t_end[best]
        work = dict(early_reject=jnp.asarray(False),
                    tiles=jnp.int32(0), tiles_run=jnp.int32(0),
                    pe_blocks=jnp.int32(0))
    with jax.named_scope("admit.search.mask"):
        if rspec is None:
            pe_mask = _winning_pe_mask(tl, t_s, t_du, n_req, n_pe)
        else:
            pe_mask = _winning_mask_mr(
                tl, t_s, t_du, n_req, demand_tail, rspec, valid_mask)
        pe_mask = jnp.where(found, pe_mask, jnp.uint32(0))
    return SearchResult(
        found=found,
        t_s=t_s,
        t_e=t_s + t_du,
        pe_mask=pe_mask,
        n_free=n_free,
        t_begin=t_begin,
        t_end=t_end,
        **work,
    )


find_allocation = functools.partial(
    jax.jit, static_argnames=("n_pe", "use_kernel", "rspec"))(search)


def replacement_search(
    tl: Timeline,
    t_r: jax.Array,
    t_du: jax.Array,
    t_dl: jax.Array,
    n_req: jax.Array,
    policy_id: jax.Array,
    t_now: jax.Array,
    *,
    n_pe: int,
    use_kernel: bool = False,
    rspec=None,
    demand_tail: Optional[jax.Array] = None,
    valid_mask: Optional[jax.Array] = None,
) -> SearchResult:
    """The backfill feasibility check: re-place a parked reservation.

    Identical to :func:`search` except the window is clamped to what is
    still reachable — candidates start at ``max(t_r, t_now)`` — so a
    deferral-queue entry can only be re-placed at a start it could
    really make.  Because a live parked reservation always satisfies
    ``t_now < t_s <= t_dl - t_du``, the clamped window is never empty.
    Used by the retry-on-release sweep (earliest-start re-placement)
    and the EASY displacement transaction (:mod:`repro.core.batch`).
    """
    return search(tl, jnp.maximum(t_r, t_now), t_du, t_dl, n_req,
                  policy_id, t_now, n_pe=n_pe, use_kernel=use_kernel,
                  rspec=rspec, demand_tail=demand_tail,
                  valid_mask=valid_mask)
