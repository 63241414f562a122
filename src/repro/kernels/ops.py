"""Jit'd public wrappers around the availscan Pallas kernels.

Prepares the operands from a :class:`~repro.core.timeline.Timeline`
(the packed occupancy words for the scalar kernels, which expand them
in VMEM one PE block at a time; bit-expanded planes for the
multi-resource kernels; lane padding), invokes the kernel, and
post-processes the raw tile outputs back into the exact semantics of
the pure-jnp reference
(:func:`repro.core.search.availability_rectangles`).

Occupancy awareness (DESIGN.md §7, §12): the live candidate mask — the
non-``T_INF`` entries of the deduplicated, compacted (and possibly
index-pruned) candidate array — is reduced to per-tile live counts and
threaded into the kernel as a scalar-prefetch operand so dead tiles
are skipped wherever they sit (prefix padding or pruned holes), and
the invalid tail is masked to the same sentinels the reference
produces, keeping the two paths element-identical.

:func:`search_select` exposes the fused availscan + policy-selection
kernel (the per-candidate vectors never leave the kernel); the
``search`` hot path uses it on the kernel path.

On shapes beyond the kernels' per-program VMEM budget (:func:`fits`)
the wrappers run the reference path instead (``search_select`` returns
``None`` and the caller runs the jnp chain); sessions report which path
their shape takes as the ``search_path`` metric.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_lib
from repro.core import timeline as tl_lib
from repro.core.timeline import Timeline
from repro.core.types import T_INF
from repro.kernels import availscan as _k

# VMEM budget of one program: the S records of one PE block (the
# scalar kernels) or of the whole bit axis (the _mr kernels) as 0/1
# elements, S * bits <= 2**21.
_MAX_OCC_ELEMS = 2 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def fits(capacity: int, n_pe: int, rspec=None) -> bool:
    """Whether a timeline of this shape is within the kernels' budget.

    The scalar kernels tile the PE axis, so only one PE block's records
    count (``kernels/availscan.pe_blocks``); the multi-resource kernels
    hold the whole bit axis in one block.  Beyond the budget the
    wrappers below run the jnp reference instead; the choice is static
    (shape only), so the service reports it as the session's
    ``search_path`` metric.
    """
    s_pad = _round_up(max(capacity, _k._LANE), _k._LANE)
    if rspec is None:
        block_words = _k.pe_blocks(tl_lib.n_words(n_pe))[0]
        return s_pad * 32 * block_words <= _MAX_OCC_ELEMS
    if rspec.R > _k._LANE:
        return False
    return (s_pad * _round_up(max(rspec.total_bits, _k._LANE), _k._LANE)
            <= _MAX_OCC_ELEMS)


def _padded_operands(tl: Timeline, n_pe: int):
    """The scalar kernels' operands: the packed occupancy words, times
    and next times, with the records padded to the lane width, and the
    padding bits of the PE blocks (``32 * block_words * n_blocks -
    n_pe``), which the kernels count as free.  No bit is expanded
    here: the kernels expand one PE block at a time in VMEM."""
    if not fits(tl.capacity, n_pe):
        return None
    S = tl.capacity
    S_pad = _round_up(max(S, _k._LANE), _k._LANE)
    occ = tl.occ
    if S_pad > S:
        occ = jnp.pad(occ, ((0, S_pad - S), (0, 0)))
    times = jnp.pad(tl.times, (0, S_pad - S), constant_values=T_INF)
    nxt = jnp.pad(tl_lib.next_times(tl), (0, S_pad - S),
                  constant_values=T_INF)
    block_words, n_blocks = _k.pe_blocks(tl.words)
    pad_bits = 32 * block_words * n_blocks - n_pe
    return occ, times, nxt, pad_bits, n_blocks


def _padded_operands_mr(tl: Timeline, rspec,
                        valid_mask: Optional[jax.Array]):
    """Multi-resource operands: the bit axis spans every plane's word
    range, and the plane-selector matrix ``psel[bit, r]`` (1 iff the
    bit is a valid unit of plane ``r``) both excludes padding/masked
    units from the free counts and routes each plane to its own output
    lane — so no pad correction exists on this path."""
    if not fits(tl.capacity, 0, rspec):
        return None
    S = tl.capacity
    n_bits = rspec.total_bits
    S_pad = _round_up(max(S, _k._LANE), _k._LANE)
    n_bits_pad = _round_up(max(n_bits, _k._LANE), _k._LANE)
    occ_bits = tl_lib.unpack_bits(tl.occ, n_bits).astype(jnp.float32)
    occ_bits = jnp.pad(
        occ_bits, ((0, S_pad - S), (0, n_bits_pad - n_bits)))
    times = jnp.pad(tl.times, (0, S_pad - S), constant_values=T_INF)
    nxt = jnp.pad(tl_lib.next_times(tl), (0, S_pad - S),
                  constant_values=T_INF)
    if valid_mask is None:
        valid_mask = jnp.asarray(rspec.valid_mask_np())
    plane_id = np.full(n_bits_pad, -1, np.int32)
    for r in range(rspec.R):
        o = rspec.bit_offset(r)
        plane_id[o:o + rspec.words_per[r] * 32] = r
    vb = tl_lib.unpack_bits(
        valid_mask[None, :], n_bits)[0].astype(jnp.float32)
    vb = jnp.pad(vb, (0, n_bits_pad - n_bits))
    psel = (jnp.asarray(plane_id)[:, None] ==
            jnp.arange(_k._LANE, dtype=jnp.int32)[None, :]
            ).astype(jnp.float32) * vb[:, None]
    return occ_bits, times, nxt, psel


def availability_rectangles(
    tl: Timeline, starts: jax.Array, t_du: jax.Array, t_now: jax.Array,
    n_pe: int, *, rspec=None, valid_mask: Optional[jax.Array] = None,
) -> search_lib.Rectangles:
    """Kernel-backed drop-in for ``search.availability_rectangles``."""
    if rspec is not None:
        ops = _padded_operands_mr(tl, rspec, valid_mask)
        if ops is None:
            return search_lib.availability_rectangles(
                tl, starts, t_du, t_now, n_pe, rspec=rspec,
                valid_mask=valid_mask)
        occ_bits, times, nxt, psel = ops
        valid = starts < T_INF
        a = jnp.minimum(starts, T_INF - t_du)
        b = a + t_du
        nfp_raw, tb_raw, te_raw = _k.availscan_mr(
            occ_bits, psel, times, nxt, a, b, valid)
        zero = jnp.int32(0)
        t_begin = jnp.minimum(jnp.maximum(tb_raw, t_now), a)
        return search_lib.Rectangles(
            starts=starts,
            n_free=jnp.where(valid, nfp_raw[:, 0], zero),
            t_begin=jnp.where(valid, t_begin, zero),
            t_end=jnp.where(valid, te_raw, zero),
            valid=valid,
            n_free_tail=jnp.where(
                valid[:, None], nfp_raw[:, 1:rspec.R], zero))
    ops = _padded_operands(tl, n_pe)
    if ops is None:
        return search_lib.availability_rectangles(
            tl, starts, t_du, t_now, n_pe)
    occ, times, nxt, pad_bits, _ = ops

    valid = starts < T_INF
    a = jnp.minimum(starts, T_INF - t_du)   # avoid int32 overflow
    b = a + t_du

    nfree_raw, tb_raw, te_raw = _k.availscan(occ, times, nxt, a, b, valid)

    zero = jnp.int32(0)
    n_free = nfree_raw - pad_bits   # padding PE bits never busy
    t_begin = jnp.minimum(jnp.maximum(tb_raw, t_now), a)
    # invalid candidates (skipped tiles included) take the reference
    # sentinels, keeping kernel and jnp paths element-identical
    return search_lib.Rectangles(
        starts=starts,
        n_free=jnp.where(valid, n_free, zero),
        t_begin=jnp.where(valid, t_begin, zero),
        t_end=jnp.where(valid, te_raw, zero),
        valid=valid)


def search_select(
    tl: Timeline, starts: jax.Array, t_du: jax.Array, t_now: jax.Array,
    n_req: jax.Array, policy_id: jax.Array, n_pe: int, *,
    rspec=None, demand_tail: Optional[jax.Array] = None,
    valid_mask: Optional[jax.Array] = None,
) -> Optional[dict]:
    """Fused availscan + policy selection on the kernel path.

    Returns ``None`` when the shape exceeds the kernel budget (caller
    falls back to the jnp chain); otherwise a dict with the winning
    candidate: ``found``, ``best`` (index into ``starts``) and its
    post-processed ``n_free`` / ``t_begin`` / ``t_end`` — bit-identical
    to ``availability_rectangles`` + ``policies.select`` — plus
    ``tiles`` (candidate tiles the kernel's grid covers) and
    ``tiles_run`` (those holding a live candidate; the rest skip).

    ``rspec`` dispatches to the multi-resource kernel: the demand tail
    joins the scalar-prefetch row and feasibility AND-reduces across
    planes (DESIGN.md §11).
    """
    if rspec is not None:
        ops = _padded_operands_mr(tl, rspec, valid_mask)
        if ops is None:
            return None
        occ_bits, times, nxt, psel = ops
        live = starts < T_INF
        a = jnp.minimum(starts, T_INF - t_du)
        b = a + t_du
        if demand_tail is None:
            demand_tail = jnp.zeros((rspec.R - 1,), jnp.int32)
        scalars = jnp.concatenate([
            jnp.stack([jnp.asarray(policy_id, jnp.int32),
                       jnp.asarray(n_req, jnp.int32),
                       jnp.asarray(t_now, jnp.int32)]),
            jnp.asarray(demand_tail, jnp.int32)])
        acc = _k.availscan_select_mr(
            occ_bits, psel, times, nxt, starts, a, b, scalars, live,
            n_res=rspec.R)
        return dict(found=acc[7] > 0, best=acc[3], n_free=acc[4],
                    t_begin=acc[5], t_end=acc[6], **_k.tile_counts(live))
    ops = _padded_operands(tl, n_pe)
    if ops is None:
        return None
    occ, times, nxt, pad_bits, n_blocks = ops
    live = starts < T_INF
    a = jnp.minimum(starts, T_INF - t_du)
    b = a + t_du
    scalars = jnp.stack([
        jnp.asarray(policy_id, jnp.int32),
        jnp.asarray(n_req, jnp.int32), jnp.asarray(t_now, jnp.int32),
        jnp.int32(pad_bits)])
    acc = _k.availscan_select(
        occ, times, nxt, starts, a, b, scalars, live)
    return dict(found=acc[7] > 0, best=acc[3], n_free=acc[4],
                t_begin=acc[5], t_end=acc[6],
                **_k.tile_counts(live, n_blocks=n_blocks))
