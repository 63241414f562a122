"""One-time open-loop rate sweep: the highest rate the service sustains.

    python benchmarks/chip/sweep_open.py --config lanl_cm5_pe_w \
        --rates 2000,4000,8000 --seconds 5 --seed 1

One process on one chip.  For each rate it runs the open loop of
``mixes/open.json`` (a seeded Poisson schedule, each due batch offered
with flush and read back) for ``--seconds`` on a fresh pass, checks
every decision against the plain reference, and prints one row: the
rate offered, decisions per second, median and 95th-percentile
decision latency, how many requests were due but not yet offered when
the schedule closed (``behind_at_close``: it grows with the run length
once the rate is past what the service sustains), the mean backlog in
the window's first and last quarters, and how late the loop took up
the first due request of each offer (``lateness_p50_ms``).  The last
line of standard output is the table as JSON.  The open cell's rate is
0.8 of the highest rate whose backlog does not grow.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def sweep(config: dict, rates, seconds: float, seed: int,
          warm_chunks: int = 8) -> list:
    import harness
    import lanl_stream
    import plain_ref
    service = config["service"]
    chunk = int(service["chunk_size"])
    stream = lanl_stream.generate(config["workload"], seed)
    reqs = harness.to_requests(stream)
    sess, snap = harness.make_session(service)
    harness.warm_open(sess, snap, reqs, chunk, warm_chunks)
    ref = plain_ref.decide(stream, int(service["n_pe"]), service["policy"])
    rows = []
    for rate in rates:
        due = harness.due_schedule(seed, rate, seconds)
        rec = harness.Recorder()
        out = harness.open_window(sess, snap, reqs, due, rec,
                                  warm_chunks * chunk)
        times, behind, offered = (np.array(c) for c in
                                  zip(*out["backlog"]))
        q = seconds / 4
        first = behind[times < q].mean() if (times < q).any() else 0.0
        last = behind[times >= 3 * q].mean() if (times >= 3 * q).any() \
            else 0.0
        cmp = harness.compare(rec, ref)
        rows.append(dict(
            rate_per_s=rate, admits_per_s=out["admits_per_s"],
            decision_p50_ms=out["decision_p50_ms"],
            decision_p95_ms=out["decision_p95_ms"],
            behind_at_close=int(len(due) - offered[
                times < seconds].max(initial=0)),
            backlog_first_quarter=float(first),
            backlog_last_quarter=float(last),
            lateness_p50_ms=float(np.median(out["lateness"]) * 1e3),
            offers=len(rec.offer_walls),
            window_s=out["window_s"],
            mismatched=cmp["mismatched"]))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="lanl_cm5_pe_w")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    cache = ROOT / ".jax_cache"      # fixed: the path is part of the key
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("sweep_open: JAX finds no TPU", file=sys.stderr)
        return 2
    config = json.loads(
        (CHIP_DIR / "configs" / f"{args.config}.json").read_text())
    rows = sweep(config, [float(r) for r in args.rates.split(",")],
                 args.seconds, args.seed)
    for row in rows:
        print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
