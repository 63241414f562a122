"""Run one benchmark cell on the chip and print its result line.

    python benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell (an entry of ``workloads`` in ``BENCHMARK.json``):
set-up (imports, stream, service, compiles served by the persistent
cache in ``<checkout>/.jax_cache`` after the first run, one warm-up of
the cell's shapes), a window of ``--seconds``, then the check against
the plain reference.  ``--trace 1`` profiles the first
``trace_seconds`` of the mix and prints the per-layer metrics instead
of the end-to-end ones.  The last line of standard output is one JSON
object; the numbers compared, each with its limit, are the last lines
of standard error.  Exits 2, printing no result, where JAX finds no
TPU, fewer chips than the cell asks for, or no program beside the
benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        cell = harness.find_cell(args.workload, ROOT)
    except (harness.SetupError, OSError, KeyError, StopIteration) as e:
        print(f"run_cell: {e!r}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run_cell: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    cache = ROOT / ".jax_cache"      # fixed: the path is part of the key
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run_cell: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, devices)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
