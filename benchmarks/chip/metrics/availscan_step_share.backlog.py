"""Share of the admission scan's device time spent in the Pallas
``availscan_select`` kernel (``kernels/availscan.py``), in percent: the
summed device time of its calls over the summed device time of the
``admit_stream`` programs (``core/batch.py``) that call it.  Says
whether the kernel does most of a scan step or the rest of the step
(release, commit, the index) does.  Moves ``admits_per_s``.
"""
KERNEL = "availscan_select"
SCAN_MODULE = "admit_stream"


def read(r):
    kernel = sum(e - s for name, s, e in r.ops if name.startswith(KERNEL))
    scan = sum(e - s for name, s, e in r.modules if SCAN_MODULE in name)
    if not kernel or not scan:
        return None
    return 100.0 * kernel / scan
