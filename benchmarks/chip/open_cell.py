"""``BENCHMARK.json`` entries of the open-loop cell ``lanl_pe_w.open``.

Its mix (``mixes/open.json``) and readers (``metrics/*.open.py``) are
in place, but the cell is not in ``BENCHMARK.json``: at 0.8 of the knee
its ``decision_p95_ms`` spreads between seeds by more than any
admissible bound allows (see PERF.md).  A later benchmark change adds
these entries once that is cured; the tests add them to a copy to
exercise the open loop.
"""
from __future__ import annotations

WORKLOAD = dict(
    name="lanl_pe_w.open", config="lanl_cm5_pe_w", traffic="open",
    chips=1,
    why="open loop, Poisson arrivals at 0.8 of the sustained rate, each "
        "offer flushed: per-offer host work and chunk filler dominate")

END_TO_END = [dict(
    name="decision_p95_ms", unit="ms", better="lower", bound=0.25,
    source="host_clock", workloads=["lanl_pe_w.open"])]

PER_LAYER = [
    dict(name="offer_wall_ms.open", unit="ms", better="lower",
         source="host_clock", layer="service", moves="decision_p95_ms",
         workloads=["lanl_pe_w.open"]),
    dict(name="pad_step_share.open", unit="%", better="lower",
         source="program_counter", layer="service",
         moves="decision_p95_ms", workloads=["lanl_pe_w.open"]),
    dict(name="device_idle_share.open", unit="%", better="lower",
         source="device_trace", layer="device", moves="decision_p95_ms",
         workloads=["lanl_pe_w.open"]),
]


def add(bench: dict) -> dict:
    """``bench`` with the open cell's entries added."""
    return {**bench, "workloads": bench["workloads"] + [WORKLOAD],
            "end_to_end": bench["end_to_end"] + END_TO_END,
            "per_layer": bench["per_layer"] + PER_LAYER}
