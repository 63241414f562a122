"""Plain host reference of the admission semantics the cells run.

Independent of the program under test (it imports nothing from it):
a numpy availability timeline of packed PE bitmasks, after the paper's
data structure (arXiv:1203.0740, sections 4-5), and the event loop of
its section 6 experiments.  For each arrival, in order:

1. release every accepted reservation whose end ``t_e <= t_a``;
2. enumerate the candidate starts: the ready time ``t_r``, the latest
   start ``t_dl - t_du``, and every time in ``[t_r, t_dl - t_du]`` at
   which the occupancy changes, as is or shifted left by ``t_du``;
3. a candidate is feasible when at least ``n_pe`` PEs are free over
   the whole window ``[s, s + t_du)``;
4. the policy picks among the feasible: FF the earliest start, PE_W
   the most free PEs (earliest start on ties);
5. the reservation takes the lowest-numbered free PEs of its window.

The search is vectorised over candidates with a segmented OR
(``np.bitwise_or.reduceat``), as the program's ``core/hostsched.py``
does; the rest is written for reading.

``start_only=True`` is the control: it tests the PEs free at the start
of each window only, the shortcut that would break the guarantee of
exclusive reservations.  The correctness check must fail it.
"""
from __future__ import annotations

import heapq
from typing import Dict, Tuple

import numpy as np

T_INF = 2**31 - 1
POLICIES = ("FF", "PE_W")


class Timeline:
    """Sorted change points ``times[S]`` and the busy PEs ``occ[S, W]``
    (uint64 words) on ``[times[i], times[i+1])``; all PEs are free
    before ``times[0]`` and from ``times[-1]`` on."""

    def __init__(self, n_pe: int):
        self.n_pe = n_pe
        self.words = -(-n_pe // 64)
        bits = np.zeros(self.words * 64, np.uint8)
        bits[:n_pe] = 1
        self.all_pes = np.packbits(bits, bitorder="little").view(np.uint64)
        self.times = np.zeros(0, np.int64)
        self.occ = np.zeros((0, self.words), np.uint64)

    def _row_at(self, t: int) -> np.ndarray:
        i = int(np.searchsorted(self.times, t, side="right")) - 1
        if i < 0:
            return np.zeros(self.words, np.uint64)
        return self.occ[i].copy()

    def _update(self, t_s: int, t_e: int, mask: np.ndarray, add: bool,
                exclusive: bool = True):
        for t in (t_s, t_e):
            i = int(np.searchsorted(self.times, t))
            if i == len(self.times) or self.times[i] != t:
                row = self._row_at(t)
                self.times = np.insert(self.times, i, t)
                self.occ = np.insert(self.occ, i, row, axis=0)
        lo = int(np.searchsorted(self.times, t_s))
        hi = int(np.searchsorted(self.times, t_e))
        seg = self.occ[lo:hi]
        if add:
            if exclusive and np.any(seg & mask):
                raise ValueError("double booking")
            seg |= mask
        else:
            seg &= ~mask
        # keep change points only
        keep = np.ones(len(self.times), bool)
        keep[0] = self.occ[0].any()
        keep[1:] = np.any(self.occ[1:] != self.occ[:-1], axis=1)
        self.times, self.occ = self.times[keep], self.occ[keep]

    def add(self, t_s: int, t_e: int, mask: np.ndarray,
            exclusive: bool = True) -> None:
        self._update(t_s, t_e, mask, add=True, exclusive=exclusive)

    def delete(self, t_s: int, t_e: int, mask: np.ndarray) -> None:
        self._update(t_s, t_e, mask, add=False)

    def candidates(self, t_r: int, t_du: int, t_dl: int) -> np.ndarray:
        lo, hi = t_r, t_dl - t_du
        t = self.times
        shifted = t - t_du
        return np.unique(np.concatenate([
            np.array([lo, hi], np.int64),
            t[(t >= lo) & (t <= hi)],
            shifted[(shifted >= lo) & (shifted <= hi)]]))

    def free_masks(self, starts: np.ndarray, t_du: int,
                   start_only: bool = False) -> np.ndarray:
        """Free PEs ``[P, W]`` over each window ``[s, s + t_du)``."""
        P, S = len(starts), len(self.times)
        busy = np.zeros((P, self.words), np.uint64)
        if S:
            nxt = np.append(self.times[1:], T_INF)
            # rows overlapping [s, s + t_du) are lo..hi-1
            lo = np.searchsorted(nxt, starts, side="right")
            hi = np.searchsorted(self.times, starts + t_du, side="left")
            if start_only:
                hi = np.minimum(hi, lo + 1)
            lo = np.minimum(lo, hi)
            some = hi > lo
            if some.any():
                idx = np.empty(2 * int(some.sum()), np.int64)
                idx[0::2], idx[1::2] = lo[some], hi[some]
                seg = np.bitwise_or.reduceat(
                    self.occ, np.minimum(idx, S - 1), axis=0)
                busy[some] = seg[0::2]
        return ~busy & self.all_pes


def _popcount(masks: np.ndarray) -> np.ndarray:
    return np.bitwise_count(masks).sum(axis=-1).astype(np.int64)


def _lowest(mask: np.ndarray, k: int) -> np.ndarray:
    bits = np.unpackbits(mask.view(np.uint8), bitorder="little")
    take = np.zeros_like(bits)
    take[np.flatnonzero(bits)[:k]] = 1
    return np.packbits(take, bitorder="little").view(np.uint64)


def decide(stream: Dict[str, np.ndarray], n_pe: int, policy: str, *,
           start_only: bool = False) -> Tuple[np.ndarray, ...]:
    """Decisions for an arrival-ordered stream on an empty machine.

    Returns ``(accepted bool[N], t_s int64[N], pe_mask uint32[N, W32])``
    with ``t_s = -1`` and an empty mask for a rejection.  PE ``i`` is
    bit ``i % 32`` of 32-bit word ``i // 32``.
    """
    if policy not in POLICIES:
        raise ValueError(f"policy {policy!r} not in {POLICIES}")
    tl = Timeline(n_pe)
    n = len(stream["t_a"])
    acc = np.zeros(n, bool)
    t_s = np.full(n, -1, np.int64)
    masks = np.zeros((n, tl.words), np.uint64)
    held: list = []          # heap of (t_e, seq, t_s)
    cols = [np.asarray(stream[f]).tolist()
            for f in ("t_a", "t_r", "t_du", "t_dl", "n_pe")]
    for j, (t_a, t_r, t_du, t_dl, k) in enumerate(zip(*cols)):
        while held and held[0][0] <= t_a:
            t_e, i, s = heapq.heappop(held)
            tl.delete(s, t_e, masks[i])
        starts = tl.candidates(t_r, t_du, t_dl)
        free = tl.free_masks(starts, t_du, start_only)
        n_free = _popcount(free)
        feas = n_free >= k
        if not feas.any():
            continue
        if policy == "FF":
            best = int(np.flatnonzero(feas)[0])   # starts ascend
        else:   # PE_W: most free PEs, earliest start on ties
            best = int(np.argmax(np.where(feas, n_free, -1)))
        acc[j], t_s[j] = True, starts[best]
        masks[j] = _lowest(free[best], k)
        # the control books blind: its shortcut cannot see the clash
        tl.add(t_s[j], t_s[j] + t_du, masks[j], exclusive=not start_only)
        heapq.heappush(held, (t_s[j] + t_du, j, t_s[j]))
    return acc, t_s, masks.view(np.uint32)
