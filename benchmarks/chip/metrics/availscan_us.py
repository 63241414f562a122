"""Device time per call of the Pallas ``availscan_select`` kernel
(``kernels/availscan.py``), in microseconds: the summed durations of
its events over their count.  Moves ``admits_per_s``."""
KERNEL = "availscan_select"


def read(r):
    hits = [e - s for name, s, e in r.ops if name.startswith(KERNEL)]
    ns, calls = sum(hits), len(hits)
    if not calls:
        return None
    return ns / 1e3 / calls
