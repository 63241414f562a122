"""Pallas TPU kernels for the availability-rectangle scan.

This is the paper's computational hot spot: ``findAllocation`` spends
``O(p * u * v)`` testing every candidate start against every slot
(Section 4.2 complexity analysis).  The TPU formulation turns the scan
into two MXU contractions per candidate tile (DESIGN.md §2):

    busy[Pt, pe]    = overlap[Pt, S] @ occ_bits[S, pe]      (window union)
    blocking[Pt, S] = free[Pt, pe]   @ occ_bits[S, pe]^T    (rect expansion)

Grid (scalar kernels): one program per (tile of ``Pt`` candidate start
times, PE block).  The occupancy arrives packed, uint32 ``[S, W]``, one
block of words per step (:func:`pe_blocks`: a machine of up to 4096
PEs is a single block, DMA'd from HBM once and expanded once for every
candidate tile — the TPU analogue of the paper's "organise
availability for efficient search"; a wider one takes blocks of 4096
PEs, the last one partial).  Each step expands its block's bits in
VMEM; both contractions separate over blocks (the free counts sum, a
slot blocks if it occupies a free PE of any block), so a tile
accumulates them and finishes on its last block.  All comparisons stay
in exact int32; only the 0/1 contraction operands are f32 (counts <
2**24, exact).

Occupancy awareness (DESIGN.md §7, §12): the candidate array arrives
deduplicated and compacted (live starts first, ``T_INF`` tail — see
``search.candidate_starts``), and *per-tile live candidate counts*
ride in as a scalar-prefetch operand.  Tiles whose count is zero are
skipped with ``pl.when``: they write sentinel outputs without touching
the MXU.  The counts are data-driven rather than prefix-driven: the
hierarchical availability index prunes summary-infeasible candidates
to ``T_INF`` *holes* mid-array (``search.prune_candidates``), and a
tile is skippable exactly when every one of its candidates is padding
or pruned — on an unpruned compacted array this degenerates to the
PR 5 live-prefix skip bit-for-bit.

:func:`availscan_select` additionally fuses the policy selection
(``policies.select``) into the kernel epilogue: each tile reduces its
candidates to a lexicographic best and folds it into a running-best
accumulator across the sequential grid, so only one 8-lane result row
leaves the kernel — the per-candidate ``[P]`` vectors (and the
``[Pt, S]`` blocking matrix) never round-trip through HBM.

VMEM budget per program (defaults Pt=128): one PE block's expanded
records, ``S * 32 * block_words <= 2**21`` elements (``ops.fits``): a
4096-PE block at up to S=512 records, a 1024-PE machine up to S=2048.
The ``_mr`` kernels keep the f32 bit planes of the whole bit axis in one
block, ``S * bits <= 2**21``.  Beyond the budget ops.py runs the
pure-jnp path instead, and sessions report it as their
``search_path``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import T_INF

# Tile of candidate start times evaluated by one program instance.
DEFAULT_PT = 128
# TPU lane width; S (and the _mr kernels' n_pe) are padded to
# multiples of this.
_LANE = 128
_WORD = 32
# The widest PE block of the scalar kernels, in packed words (4096
# PEs); a wider machine is tiled over several blocks.
BLOCK_WORDS = 128
_BIG = jnp.iinfo(jnp.int32).max


def _interpret_mode() -> bool:
    """``interpret=None`` resolved by platform: the kernels compile on a
    TPU and run in the Pallas interpreter on the CPU backend (tests).
    Any other backend has no kernel for them, which is an error rather
    than a silent interpreter run."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise NotImplementedError(
        f"the availscan kernels are written for TPU; there is no "
        f"compiled kernel for platform {platform!r}")


def pe_blocks(words: int) -> Tuple[int, int]:
    """``(block_words, n_blocks)``: how the scalar kernels tile a
    timeline of ``words`` packed occupancy words over its PEs.

    A machine of at most :data:`BLOCK_WORDS` words is one block of its
    word count rounded up to a power of two (at least 4, so that the
    expanded block fills whole 128-lane rows); a wider one takes
    blocks of ``BLOCK_WORDS`` words, the last one partial.
    """
    if words <= BLOCK_WORDS:
        return max(4, 1 << (words - 1).bit_length()), 1
    return BLOCK_WORDS, -(-words // BLOCK_WORDS)


def _expand_bits(w, j, *, block_words, n_words):
    """f32 0/1 ``[S, 32 * block_words]``: the bits of PE block ``j``'s
    packed words ``w`` (int32 ``[S, block_words]``).

    The order of PEs inside a block is free (counts and "any" do not
    depend on it), so the expansion is bit-major and lane-dense: the
    words are repeated across one 128-lane row, ``rep`` times, and
    group ``g`` of 128 lanes holds bit ``g * rep + lane // block_words``
    of word ``lane % block_words``.  Words past the timeline's last
    (a partial last block) read as empty.
    """
    rep = _LANE // block_words
    x = w if rep == 1 else jnp.concatenate([w] * rep, axis=1)  # [S, 128]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    word = lane % block_words
    if n_words % block_words:
        x = jnp.where(j * block_words + word < n_words, x, 0)
    shift = lane // block_words
    planes = [jax.lax.shift_right_logical(x, shift + g * rep) & 1
              for g in range(_WORD // rep)]
    bits = planes[0] if len(planes) == 1 else jnp.concatenate(planes, 1)
    return bits.astype(jnp.float32)


def _bounds(a, b, times, nxt, blocking):
    """Rectangle bounds of a tile from its blocking slots ``[Pt, S]``."""
    left = blocking & (nxt[None, :] <= a[:, None])
    tb = jnp.max(jnp.where(left, nxt[None, :], -T_INF), axis=1)
    right = blocking & (times[None, :] >= b[:, None])
    te = jnp.min(jnp.where(right, times[None, :], T_INF), axis=1)
    return tb, te


def _blocked_rects(live, a_ref, b_ref, times_ref, nxt_ref, occ_ref,
                   bits_ref, acc_refs, finish, *, n_blocks, block_words,
                   n_words):
    """One (candidate tile, PE block) step of the scalar kernels: the
    two MXU contractions of DESIGN.md §2 on this block's PEs.

    Both separate over PE blocks: ``n_free`` sums, and a slot blocks
    the tile's rectangles iff it occupies a free PE of any block, so
    the blocking counts sum too.  On the tile's last block
    ``finish(n_free_raw, t_begin_raw, t_end_raw)`` runs with the
    tile's totals.  The 0/1 operands and the counts are f32 (counts <
    2**24, exact).
    """
    i, j = pl.program_id(0), pl.program_id(1)

    # a single block is the same for every tile: expand it once
    @pl.when((i == 0) if n_blocks == 1 else live)
    def _():
        bits_ref[...] = _expand_bits(occ_ref[...], j,
                                     block_words=block_words,
                                     n_words=n_words)

    @pl.when(live)
    def _():
        a, b = a_ref[:, 0], b_ref[:, 0]
        times, nxt = times_ref[0, :], nxt_ref[0, :]
        bits = bits_ref[...]
        ov = ((times[None, :] < b[:, None]) &
              (nxt[None, :] > a[:, None])).astype(jnp.float32)  # [Pt, S]
        busy = jax.lax.dot(ov, bits,
                           preferred_element_type=jnp.float32)  # [Pt, pe]
        free = busy < 0.5
        nfree = jnp.sum(free.astype(jnp.int32), axis=1)
        blocking = jax.lax.dot_general(
            free.astype(jnp.float32), bits,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [Pt, S]
        if n_blocks == 1:
            finish(nfree, *_bounds(a, b, times, nxt, blocking > 0.5))
            return
        nfree_acc, blocking_acc = acc_refs

        @pl.when(j == 0)
        def _():
            nfree_acc[:, 0] = nfree
            blocking_acc[...] = blocking

        @pl.when(j > 0)
        def _():
            nfree_acc[:, 0] += nfree
            blocking_acc[...] += blocking

        @pl.when(j == n_blocks - 1)
        def _():
            finish(nfree_acc[:, 0], *_bounds(
                a, b, times, nxt, blocking_acc[...] > 0.5))


def _blocked_call(kernel, prefetch, per_tile, times, nxt, occ, *, pt,
                  out_specs, out_shape, interpret):
    """A scalar kernel over the grid (candidate tiles, PE blocks).

    ``occ`` is the packed timeline, uint32 ``[S, W]``, read one PE
    block per step and expanded to bits in VMEM; ``per_tile`` are the
    ``[P_pad, 1]`` candidate operands; the live counts per tile are the
    last scalar-prefetch operand.  A dead tile keeps the PE block of
    the step before it, so its steps load nothing.
    """
    S, W = occ.shape
    assert S % _LANE == 0, S
    block_words, n_blocks = pe_blocks(W)
    if n_blocks == 1 and W < block_words:
        occ = jnp.pad(occ, ((0, 0), (0, block_words - W)))
    occ = jax.lax.bitcast_convert_type(occ, jnp.int32)
    n_tiles = per_tile[0].shape[0] // pt

    def occ_block(i, j, *prefetch):
        if n_blocks == 1:
            return 0, 0
        return 0, jnp.where(prefetch[-1][i] > 0, j, n_blocks - 1)

    scratch = [pltpu.VMEM((S, _WORD * block_words), jnp.float32)]
    if n_blocks > 1:
        scratch += [pltpu.VMEM((pt, 1), jnp.int32),
                    pltpu.VMEM((pt, S), jnp.float32)]
    tile_spec = pl.BlockSpec((pt, 1), lambda i, j, *_: (i, 0))
    row_spec = pl.BlockSpec((1, S), lambda i, j, *_: (0, 0))
    return pl.pallas_call(
        functools.partial(kernel, pt=pt, n_blocks=n_blocks,
                          block_words=block_words,
                          n_words=occ.shape[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_tiles, n_blocks),
            in_specs=[tile_spec] * len(per_tile) + [
                row_spec, row_spec,
                pl.BlockSpec((S, block_words), occ_block)],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=_interpret_mode() if interpret is None else interpret,
    )(*prefetch, *per_tile, times[None, :], nxt[None, :], occ)


def _tile_rects_mr(a, b, times, nxt, occ, psel):
    """Multi-resource tile (DESIGN.md §11): a third MXU dot against the
    plane-selector matrix ``psel[bit, r]`` (1 iff the global bit is a
    *valid* unit of resource plane ``r``) yields per-plane free-unit
    counts in one contraction — column 0 is the policy-scored PE count,
    columns 1..R-1 feed the vector fit test.  The blocking contraction
    is unchanged: occupancy bits only exist on valid units, so the
    unmasked free operand ANDs to the same booleans."""
    ov = ((times[None, :] < b[:, None]) &
          (nxt[None, :] > a[:, None])).astype(jnp.float32)     # [Pt, S]
    busy = jax.lax.dot(ov, occ,
                       preferred_element_type=jnp.float32)     # [Pt, bit]
    free = (busy < 0.5).astype(jnp.float32)
    nfree_planes = jax.lax.dot(
        free, psel,
        preferred_element_type=jnp.float32).astype(jnp.int32)  # [Pt, 128]
    blocking = jax.lax.dot_general(
        free, occ,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) > 0.5              # [Pt, S]
    left = blocking & (nxt[None, :] <= a[:, None])
    tb = jnp.max(jnp.where(left, nxt[None, :], -T_INF), axis=1)
    right = blocking & (times[None, :] >= b[:, None])
    te = jnp.min(jnp.where(right, times[None, :], T_INF), axis=1)
    return nfree_planes, tb, te


def _tile_live(live: jax.Array, P_pad: int, pt: int) -> jax.Array:
    """i32[P_pad/pt] live-candidate count per tile (0 = skippable)."""
    lv = _pad_to(live.astype(jnp.int32), P_pad, 0)
    return jnp.sum(lv.reshape(P_pad // pt, pt), axis=1)


def tile_counts(live: jax.Array, pt: int = DEFAULT_PT,
                n_blocks: int = 1) -> dict:
    """``tiles``: the candidate tiles a kernel grid over ``live``
    covers; ``tiles_run``: those with a live candidate (the others
    skip their MXU work); ``pe_blocks``: the (tile, PE block) steps
    that ran, ``n_blocks`` per live tile."""
    P_pad = -(-live.shape[-1] // pt) * pt
    tlive = _tile_live(live, P_pad, pt)
    run = jnp.sum(tlive > 0, dtype=jnp.int32)
    return dict(tiles=jnp.int32(P_pad // pt), tiles_run=run,
                pe_blocks=run * n_blocks)


def _availscan_kernel(tlive_ref, a_ref, b_ref, times_ref, nxt_ref,
                      occ_ref, nfree_ref, tb_ref, te_ref, bits_ref,
                      *acc_refs, pt, **blocks):
    live = tlive_ref[pl.program_id(0)] > 0

    def finish(nfree, tb, te):
        nfree_ref[:, 0] = nfree
        tb_ref[:, 0] = tb
        te_ref[:, 0] = te

    _blocked_rects(live, a_ref, b_ref, times_ref, nxt_ref, occ_ref,
                   bits_ref, acc_refs, finish, **blocks)

    @pl.when(~live)
    def _():
        # all-padding tile: sentinel outputs, no MXU work.  The ops.py
        # wrapper masks every invalid candidate to the reference
        # sentinels afterwards, so these values are never observed.
        finish(jnp.zeros((pt,), jnp.int32),
               jnp.full((pt,), -T_INF, jnp.int32),
               jnp.full((pt,), T_INF, jnp.int32))


def _pad_to(x: jax.Array, size: int, fill) -> jax.Array:
    pad = size - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(
    jax.jit, static_argnames=("pt", "interpret"))
def availscan(
    occ: jax.Array,        # uint32[S, W] packed occupancy words
    times: jax.Array,      # i32[S]
    nxt: jax.Array,        # i32[S]
    a: jax.Array,          # i32[P] window starts (overflow-clamped)
    b: jax.Array,          # i32[P] window ends
    live: jax.Array,       # bool/i32[P]: candidate is live (not
    #                        T_INF padding, not summary-pruned)
    *,
    pt: int = DEFAULT_PT,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Tiled scan over candidates and PE blocks, skipping dead tiles.

    Returns raw ``(n_free, t_begin_raw, t_end_raw)`` — ``n_free`` still
    counts the PE blocks' padding bits (``32 * block_words * n_blocks
    - n_pe``, caller subtracts) and the bounds carry ``-T_INF`` /
    ``T_INF`` sentinels when unblocked (caller clamps).  ``live``
    reduces to per-tile counts in the scalar-prefetch operand: tiles
    with no live candidate (all padding or all summary-pruned) skip
    both contractions.
    """
    P = a.shape[0]
    P_pad = -(-P // pt) * pt
    tlive = _tile_live(live, P_pad, pt)
    col = jax.ShapeDtypeStruct((P_pad, 1), jnp.int32)
    out_spec = pl.BlockSpec((pt, 1), lambda i, j, t: (i, 0))
    nfree, tb, te = _blocked_call(
        _availscan_kernel, [tlive],
        [_pad_to(a, P_pad, T_INF - 1)[:, None],
         _pad_to(b, P_pad, T_INF)[:, None]],
        times, nxt, occ, pt=pt, out_specs=[out_spec] * 3,
        out_shape=[col] * 3, interpret=interpret)
    return nfree[:P, 0], tb[:P, 0], te[:P, 0]


def _availscan_kernel_mr(tlive_ref, a_ref, b_ref, times_ref, nxt_ref,
                         occ_ref, psel_ref, nfp_ref, tb_ref, te_ref,
                         *, pt):
    i = pl.program_id(0)
    live = tlive_ref[i] > 0

    @pl.when(live)
    def _():
        nfp, tb, te = _tile_rects_mr(
            a_ref[:, 0], b_ref[:, 0], times_ref[0, :], nxt_ref[0, :],
            occ_ref[...], psel_ref[...])
        nfp_ref[...] = nfp
        tb_ref[:, 0] = tb
        te_ref[:, 0] = te

    @pl.when(~live)
    def _():
        nfp_ref[...] = jnp.zeros((pt, _LANE), jnp.int32)
        tb_ref[:, 0] = jnp.full((pt,), -T_INF, jnp.int32)
        te_ref[:, 0] = jnp.full((pt,), T_INF, jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("pt", "interpret"))
def availscan_mr(
    occ_bits: jax.Array,   # f32[S, n_bits_padded] 0/1 occupancy
    psel: jax.Array,       # f32[n_bits_padded, 128] plane selector
    times: jax.Array,      # i32[S]
    nxt: jax.Array,        # i32[S]
    a: jax.Array,          # i32[P] window starts (overflow-clamped)
    b: jax.Array,          # i32[P] window ends
    live: jax.Array,       # bool/i32[P]: candidate is live
    *,
    pt: int = DEFAULT_PT,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-resource :func:`availscan`: same tile-skip scan, but the
    free counts come back per plane (``n_free_planes[P, 128]``, column
    ``r`` = valid free units of resource ``r``) and need no padding
    correction — the plane selector already excludes padding and
    masked-out units."""
    S, n_bits_p = occ_bits.shape
    assert S % _LANE == 0 and n_bits_p % _LANE == 0, (S, n_bits_p)
    P = a.shape[0]
    P_pad = -(-P // pt) * pt
    a_p = _pad_to(a, P_pad, T_INF - 1)[:, None]
    b_p = _pad_to(b, P_pad, T_INF)[:, None]
    tlive = _tile_live(live, P_pad, pt)
    grid = (P_pad // pt,)
    nfp, tb, te = pl.pallas_call(
        functools.partial(_availscan_kernel_mr, pt=pt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((pt, 1), lambda i, s: (i, 0)),      # a
                pl.BlockSpec((pt, 1), lambda i, s: (i, 0)),      # b
                pl.BlockSpec((1, S), lambda i, s: (0, 0)),       # times
                pl.BlockSpec((1, S), lambda i, s: (0, 0)),       # nxt
                pl.BlockSpec((S, n_bits_p), lambda i, s: (0, 0)),  # occ
                pl.BlockSpec((n_bits_p, _LANE),
                             lambda i, s: (0, 0)),               # psel
            ],
            out_specs=[
                pl.BlockSpec((pt, _LANE), lambda i, s: (i, 0)),
                pl.BlockSpec((pt, 1), lambda i, s: (i, 0)),
                pl.BlockSpec((pt, 1), lambda i, s: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((P_pad, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((P_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((P_pad, 1), jnp.int32),
        ],
        interpret=_interpret_mode() if interpret is None else interpret,
    )(tlive, a_p, b_p,
      times[None, :], nxt[None, :], occ_bits, psel)
    return nfp[:P, :], tb[:P, 0], te[:P, 0]


def _integer_keys_tile(policy_id, n_free, duration):
    """In-kernel mirror of ``policies.integer_keys`` (where-chain)."""
    nf = n_free.astype(jnp.int32)
    du = duration.astype(jnp.int32)
    du_hi = du >> 16
    du_lo = du & 0xFFFF
    p_lo_raw = nf * du_lo
    p_hi = nf * du_hi + (p_lo_raw >> 16)
    p_lo = p_lo_raw & 0xFFFF
    zero = jnp.zeros_like(nf)
    key1 = jnp.where(
        policy_id == 1, nf, jnp.where(
            policy_id == 2, -nf, jnp.where(
                policy_id == 3, du, jnp.where(
                    policy_id == 4, -du, jnp.where(
                        policy_id == 5, p_hi, jnp.where(
                            policy_id == 6, -p_hi, zero))))))
    key2 = jnp.where(policy_id == 5, p_lo,
                     jnp.where(policy_id == 6, -p_lo, zero))
    return key1, key2


def _availscan_select_kernel(scal_ref, tlive_ref, starts_ref, a_ref,
                             b_ref, times_ref, nxt_ref, occ_ref,
                             acc_ref, bits_ref, *acc_refs, pt, **blocks):
    i = pl.program_id(0)
    policy_id = scal_ref[0]
    n_req = scal_ref[1]
    t_now = scal_ref[2]
    pad_corr = scal_ref[3]

    @pl.when((i == 0) & (pl.program_id(1) == 0))
    def _():
        # lexicographic +inf on the four comparison lanes: no tile
        # has contributed yet (built from iota — pallas kernels may
        # not capture constant arrays)
        lane = jax.lax.iota(jnp.int32, 8)
        acc_ref[0, :] = jnp.where(lane < 4, _BIG, 0)

    def finish(nfree_raw, tb_raw, te_raw):
        starts = starts_ref[:, 0]
        a = a_ref[:, 0]
        valid = starts < T_INF
        # the exact post-processing of the ops.py wrapper / jnp ref
        zero = jnp.zeros((pt,), jnp.int32)
        n_free = jnp.where(valid, nfree_raw - pad_corr, zero)
        t_begin = jnp.where(
            valid, jnp.minimum(jnp.maximum(tb_raw, t_now), a), zero)
        t_end = jnp.where(valid, te_raw, zero)
        # the exact scoring of policies.select
        feasible = valid & (n_free >= n_req)
        key1, key2 = _integer_keys_tile(policy_id, n_free,
                                        t_end - t_begin)
        key1 = jnp.where(feasible, key1, _BIG)
        key2 = jnp.where(feasible, key2, _BIG)
        tb = jnp.where(feasible, starts, _BIG)
        # tile-local lexicographic min of (key1, key2, tb, index)
        idx = i * pt + jax.lax.iota(jnp.int32, pt)
        m1 = jnp.min(key1)
        e1 = key1 == m1
        m2 = jnp.min(jnp.where(e1, key2, _BIG))
        e2 = e1 & (key2 == m2)
        m3 = jnp.min(jnp.where(e2, tb, _BIG))
        e3 = e2 & (tb == m3)
        m4 = jnp.min(jnp.where(e3, idx, _BIG))
        win = e3 & (idx == m4)

        def pick(v):
            return jnp.sum(jnp.where(win, v, 0).astype(jnp.int32))

        row = jnp.stack([m1, m2, m3, m4, pick(n_free), pick(t_begin),
                         pick(t_end), pick(feasible.astype(jnp.int32))])
        # fold into the running best: strict lexicographic less on
        # (key1, key2, tb, index) — index is unique, so ties cannot
        # occur and "first tile wins" falls out of the index key.
        acc = acc_ref[0, :]
        less = (row[0] < acc[0]) | (
            (row[0] == acc[0]) & ((row[1] < acc[1]) | (
                (row[1] == acc[1]) & ((row[2] < acc[2]) | (
                    (row[2] == acc[2]) & (row[3] < acc[3]))))))
        acc_ref[0, :] = jnp.where(less, row, acc)

    _blocked_rects(tlive_ref[i] > 0, a_ref, b_ref, times_ref, nxt_ref,
                   occ_ref, bits_ref, acc_refs, finish, **blocks)


@functools.partial(
    jax.jit, static_argnames=("pt", "interpret"))
def availscan_select(
    occ: jax.Array,        # uint32[S, W] packed occupancy words
    times: jax.Array,      # i32[S]
    nxt: jax.Array,        # i32[S]
    starts: jax.Array,     # i32[P] candidate starts (T_INF padded)
    a: jax.Array,          # i32[P] window starts (overflow-clamped)
    b: jax.Array,          # i32[P] window ends
    scalars: jax.Array,    # i32[4]: policy, n_req, t_now, pad
    live: jax.Array,       # bool[P] live (unpruned) candidate mask
    *,
    pt: int = DEFAULT_PT,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused availscan + policy selection (one int32[8] result row).

    Row layout: ``key1, key2, start_key, best_index, n_free, t_begin,
    t_end, feasible`` of the winning candidate — post-processed values
    (pad-corrected ``n_free``, clamped ``t_begin``), bit-identical to
    the jnp ``availability_rectangles`` + ``policies.select`` chain.
    The grid runs every PE block (:func:`pe_blocks`) of each candidate
    tile and selects on the tile's last block.  Tiles whose per-tile
    live count is zero are skipped entirely; on compacted
    (prefix-live) inputs this degenerates to the old ``i*pt < n_live``
    prefix skip, and index pruning punches holes without ever skipping
    a tile that still holds a live candidate.
    """
    P = a.shape[0]
    P_pad = -(-P // pt) * pt
    tlive = _tile_live(live, P_pad, pt)
    acc = _blocked_call(
        _availscan_select_kernel, [scalars.astype(jnp.int32), tlive],
        [_pad_to(starts, P_pad, T_INF)[:, None],
         _pad_to(a, P_pad, T_INF - 1)[:, None],
         _pad_to(b, P_pad, T_INF)[:, None]],
        times, nxt, occ, pt=pt,
        out_specs=pl.BlockSpec((1, 8), lambda i, j, s, t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 8), jnp.int32),
        interpret=interpret)
    return acc[0]


def _availscan_select_kernel_mr(scal_ref, tlive_ref, starts_ref, a_ref,
                                b_ref, times_ref, nxt_ref, occ_ref,
                                psel_ref, acc_ref, *, pt, n_res):
    i = pl.program_id(0)
    policy_id = scal_ref[0]
    n_req = scal_ref[1]
    t_now = scal_ref[2]

    @pl.when(i == 0)
    def _():
        lane = jax.lax.iota(jnp.int32, 8)
        acc_ref[0, :] = jnp.where(lane < 4, _BIG, 0)

    @pl.when(tlive_ref[i] > 0)
    def _():
        starts = starts_ref[:, 0]
        a = a_ref[:, 0]
        nfp_raw, tb_raw, te_raw = _tile_rects_mr(
            a, b_ref[:, 0], times_ref[0, :], nxt_ref[0, :],
            occ_ref[...], psel_ref[...])
        valid = starts < T_INF
        zero = jnp.zeros((pt,), jnp.int32)
        # plane-0 counts are already valid-masked by the selector —
        # no pad correction; otherwise the exact post-processing of
        # the ops.py wrapper / jnp reference
        n_free = jnp.where(valid, nfp_raw[:, 0], zero)
        t_begin = jnp.where(
            valid, jnp.minimum(jnp.maximum(tb_raw, t_now), a), zero)
        t_end = jnp.where(valid, te_raw, zero)
        # vector fit: AND-reduce the per-plane demand tests (the
        # demand tail rides in the scalar-prefetch operand; n_res is
        # static, so this loop unrolls at trace time)
        feasible = valid & (n_free >= n_req)
        for r in range(1, n_res):
            feasible = feasible & (nfp_raw[:, r] >= scal_ref[2 + r])
        key1, key2 = _integer_keys_tile(policy_id, n_free,
                                        t_end - t_begin)
        key1 = jnp.where(feasible, key1, _BIG)
        key2 = jnp.where(feasible, key2, _BIG)
        tb = jnp.where(feasible, starts, _BIG)
        idx = i * pt + jax.lax.iota(jnp.int32, pt)
        m1 = jnp.min(key1)
        e1 = key1 == m1
        m2 = jnp.min(jnp.where(e1, key2, _BIG))
        e2 = e1 & (key2 == m2)
        m3 = jnp.min(jnp.where(e2, tb, _BIG))
        e3 = e2 & (tb == m3)
        m4 = jnp.min(jnp.where(e3, idx, _BIG))
        win = e3 & (idx == m4)

        def pick(v):
            return jnp.sum(jnp.where(win, v, 0).astype(jnp.int32))

        row = jnp.stack([m1, m2, m3, m4, pick(n_free), pick(t_begin),
                         pick(t_end), pick(feasible.astype(jnp.int32))])
        acc = acc_ref[0, :]
        less = (row[0] < acc[0]) | (
            (row[0] == acc[0]) & ((row[1] < acc[1]) | (
                (row[1] == acc[1]) & ((row[2] < acc[2]) | (
                    (row[2] == acc[2]) & (row[3] < acc[3]))))))
        acc_ref[0, :] = jnp.where(less, row, acc)


@functools.partial(
    jax.jit, static_argnames=("pt", "n_res", "interpret"))
def availscan_select_mr(
    occ_bits: jax.Array,   # f32[S, n_bits_padded] 0/1 occupancy
    psel: jax.Array,       # f32[n_bits_padded, 128] plane selector
    times: jax.Array,      # i32[S]
    nxt: jax.Array,        # i32[S]
    starts: jax.Array,     # i32[P] candidate starts (T_INF padded)
    a: jax.Array,          # i32[P] window starts (overflow-clamped)
    b: jax.Array,          # i32[P] window ends
    scalars: jax.Array,    # i32[2+n_res]: policy, n_req, t_now,
    #                        demand[1..n_res-1]
    live: jax.Array,       # bool[P] live (unpruned) candidate mask
    *,
    pt: int = DEFAULT_PT,
    n_res: int = 1,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Multi-resource :func:`availscan_select` (DESIGN.md §11).

    Same one-row fused epilogue, but feasibility AND-reduces the
    per-plane fit tests against the demand tail carried in the
    scalar-prefetch operand, and ``n_free`` comes valid-masked from
    the plane-selector contraction (no pad correction).  A separate
    kernel so the scalar layout of the R=1 legacy kernel — and its
    compiled graph — stays untouched.
    """
    S, n_bits_p = occ_bits.shape
    assert S % _LANE == 0 and n_bits_p % _LANE == 0, (S, n_bits_p)
    assert scalars.shape[0] == 2 + n_res, (scalars.shape, n_res)
    P = a.shape[0]
    P_pad = -(-P // pt) * pt
    tlive = _tile_live(live, P_pad, pt)
    starts_p = _pad_to(starts, P_pad, T_INF)[:, None]
    a_p = _pad_to(a, P_pad, T_INF - 1)[:, None]
    b_p = _pad_to(b, P_pad, T_INF)[:, None]
    grid = (P_pad // pt,)
    acc = pl.pallas_call(
        functools.partial(_availscan_select_kernel_mr, pt=pt,
                          n_res=n_res),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((pt, 1), lambda i, s, t: (i, 0)),   # starts
                pl.BlockSpec((pt, 1), lambda i, s, t: (i, 0)),   # a
                pl.BlockSpec((pt, 1), lambda i, s, t: (i, 0)),   # b
                pl.BlockSpec((1, S), lambda i, s, t: (0, 0)),    # times
                pl.BlockSpec((1, S), lambda i, s, t: (0, 0)),    # nxt
                pl.BlockSpec((S, n_bits_p),
                             lambda i, s, t: (0, 0)),            # occ
                pl.BlockSpec((n_bits_p, _LANE),
                             lambda i, s, t: (0, 0)),            # psel
            ],
            out_specs=pl.BlockSpec((1, 8), lambda i, s, t: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, 8), jnp.int32),
        interpret=_interpret_mode() if interpret is None else interpret,
    )(scalars.astype(jnp.int32), tlive, starts_p, a_p, b_p,
      times[None, :], nxt[None, :], occ_bits, psel)
    return acc[0]
