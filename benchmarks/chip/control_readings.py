"""Readings of the check's control at a configuration's full size.

    python benchmarks/chip/control_readings.py --seeds 1,2,3

The control is the plain reference with one shortcut: PEs are tested
free at the start of each window only, which breaks the configuration's
guarantee of exclusive reservations.  Put in the program's place, its
decisions for one pass go through the same comparison a run makes;
each row gives the numbers compared.  A sound run reads 0 on each
(the limits are 0), so the control must read more on ``mismatched``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import harness
import lanl_stream
import plain_ref

CHIP_DIR = Path(__file__).resolve().parent


def readings(config: dict, seed: int) -> dict:
    n_pe, policy = config["service"]["n_pe"], config["service"]["policy"]
    stream = lanl_stream.generate(config["workload"], seed)
    ref = plain_ref.decide(stream, n_pe, policy)
    acc, ts, mask = plain_ref.decide(stream, n_pe, policy, start_only=True)
    rec = harness.Recorder()
    rec.new_pass()
    rec.add(dict(acc=acc, t_s=ts, mask=mask, steps=len(acc)))
    checks = harness.checks_of(harness.compare(rec, ref), len(acc), True)
    return dict(config=config["name"], seed=seed,
                **{k: c["value"] for k, c in checks.items()},
                accepted_reference=int(ref[0].sum()),
                accepted_control=int(acc.sum()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)
    rows = []
    for path in sorted((CHIP_DIR / "configs").glob("*.json")):
        config = json.loads(path.read_text())
        for seed in (int(s) for s in args.seeds.split(",")):
            rows.append(readings(config, seed))
            print(" ".join(f"{k}={v}" for k, v in rows[-1].items()),
                  flush=True)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
