"""Median wall time of one ``Session.offer`` of the open loop, up to its
decisions being readable on the host, in milliseconds (host clock
around the benchmark's ``offer`` and ``readback`` spans).  Moves
``decision_p95_ms``."""
import statistics


def read(r):
    walls = r.counters["offer_wall_ms"]
    return statistics.median(walls) if walls else None
