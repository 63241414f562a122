"""Device time of the admission scan per scan step, in microseconds.

The scan programs are the device modules of ``core/batch.py``'s
``admit_stream`` (jnp search fused in, or the Pallas kernel called from
it); steps are every request slot the window's chunks ran, filler
included.  Moves ``admits_per_s``.
"""
SCAN_MODULE = "admit_stream"


def read(r):
    ns = sum(e - s for name, s, e in r.modules if SCAN_MODULE in name)
    steps = r.counters["scan_steps"]
    if not ns or not steps:
        return None
    return ns / 1e3 / steps
