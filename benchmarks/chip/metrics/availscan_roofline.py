"""``availscan_select``'s share of its roofline, in percent.

The least bytes one search must read, whatever implements it: every
timeline record at the session's capacity, as a packed PE bitmask
(``n_pe / 8`` bytes) with its time and the next record's time (8
bytes), plus the ``2 * capacity + 2`` candidate starts at 4 bytes each.
The search does a few integer operations per byte, so the bound is
the bytes over the chip's HBM bandwidth; the share is that bound over
the measured time per call.  Moves ``admits_per_s``.
"""
KERNEL = "availscan_select"


def least_bytes(capacity: int, n_pe: int) -> int:
    return capacity * (n_pe // 8 + 8) + (2 * capacity + 2) * 4


def read(r):
    hits = [e - s for name, s, e in r.ops if name.startswith(KERNEL)]
    ns, calls = sum(hits), len(hits)
    if not calls or not ns:
        return None
    bound_s = least_bytes(r.shapes["capacity"], r.shapes["n_pe"]) / \
        r.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / (ns / 1e9 / calls)
