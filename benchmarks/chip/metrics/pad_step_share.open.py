"""Share of the window's scan steps spent on flush filler, in percent:
steps run (every chunk is ``chunk_size`` steps) less requests decided,
over steps run.  Moves ``decision_p95_ms``."""


def read(r):
    steps = r.counters["scan_steps"]
    if not steps:
        return None
    return 100.0 * (steps - r.counters["decided"]) / steps
