"""Share of the traced window in which no program ran on the device,
in percent: 100 * (1 - busy / window), busy being the union of the
device's module intervals (see ``trace_ops``).  Moves ``decision_p95_ms``."""


def read(r):
    window = r.t1 - r.t0
    if window <= 0 or not r.modules:
        return None
    return 100.0 * (1.0 - r.busy_ns / window)
