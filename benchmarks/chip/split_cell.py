"""Split a closed-loop cell's time by the program's own spans and scopes.

    python benchmarks/chip/split_cell.py --workload <cell> --seed <n> \
        [--seconds 20] [--out <file.json>] [--fixture <file.json>]

One process, set up as ``run_cell.py`` sets up a run (the same stream,
session and warm-up, through ``harness``): a window of ``--seconds``
with no profiler (``admits_per_s_off``), then a profiled window of the
mix's ``trace_seconds`` (``admits_per_s_traced``), each checked against
the plain reference.  From the trace (``trace_scopes``) it prints, as
one JSON object on the last line of standard output:

- ``metrics``: the per-layer numbers the program's spans, scopes and
  counters make readable: ``host_dispatch_us`` (mean
  ``repro.offer.dispatch``), ``drain_idle_share`` (device-idle share
  of the window under ``repro.drain*``), ``release_step_us``,
  ``search_step_us``, ``commit_step_us`` (self time of the scan ops in
  that phase per scan step), ``early_reject_share`` and
  ``tile_skip_share`` (from ``Session.metrics()`` after the window:
  every pass restores the empty session, so the state holds one
  pass's counts), beside the benchmark's ``scan_step_us`` and
  ``device_idle_share``;
- ``scan_split_us_per_step``: the scan programs' time by scope, with
  the ops outside the step and the loop control apart;
- ``idle_by_span`` (benchmark and program spans) next to
  ``idle_by_bench_span`` (what ``breakdown.idle_gaps`` of a ``--trace
  1`` run prints), and ``scope_coverage``;
- ``top_unscoped``: the ops of the step that no phase names.

``--fixture`` also writes 3 ms of the trace, where the device runs
out of work inside the first drain, in ns from the slice's start, for ``test_chipbench_scopes.py``.  Needs the
chip (exits 2 elsewhere); the benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
FIXTURE_NS = 3_000_000


def hlo_maps(sess, service: dict, reqs, chunk: int) -> dict:
    """Instruction -> scope path maps of both scan programs, keyed by
    their module names (the op events of the trace name no path)."""
    import jax.numpy as jnp
    import trace_scopes
    from repro.api.config import policy_id_of
    from repro.core import batch as batch_lib
    from repro.core.types import Policy
    state = sess.snapshot()[0][0]
    batch = batch_lib.requests_to_batch(reqs[:chunk])
    pid = jnp.int32(policy_id_of(Policy(service["policy"])))
    kw = dict(n_pe=int(service["n_pe"]),
              use_kernel=bool(service["use_kernel"]))
    out = {}
    for key, fn in (("admit_stream_donated", batch_lib.admit_stream_donated),
                    ("admit_stream", batch_lib.admit_stream)):
        text = fn.lower(state, batch, pid, batch_lib.BF_NONE,
                        **kw).compile().as_text()
        out[key] = trace_scopes.hlo_paths(text)
    return out


def fixture(red: dict, paths: list, t0: int) -> dict:
    """``FIXTURE_NS`` of the trace around the moment the device runs
    out of work inside the first drain: the last scan module that ends
    in the window's first ``repro.drain.sync`` ends mid-slice (``None``
    where the trace has no such moment)."""
    import trace_ops
    import trace_scopes
    syncs = sorted(s for s in red["program_spans"]
                   if s[0] == "repro.drain.sync" and s[1] >= t0)
    ends = [e for n, _, e in red["modules"] if syncs
            and trace_scopes.SCAN_MODULE in n
            and syncs[0][1] <= e <= syncs[0][2]]
    if not ends:
        return None
    x = max(ends)
    a, b = x - FIXTURE_NS // 2, x + FIXTURE_NS // 2
    table: dict = {}
    ops = []
    for (name, s, e), p in zip(red["ops"], paths):
        if e > a and s < b:
            ops.append([name, max(s, a) - a, min(e, b) - a,
                        table.setdefault(p, len(table))])

    def cut(events):
        return [[n, s - a, e - a] for n, s, e in trace_ops.clip(events, a, b)]

    return dict(window=[0, b - a], ops=ops, paths=list(table),
                modules=cut(red["modules"]), spans=cut(red["spans"]),
                program_spans=cut(red["program_spans"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)

    import harness
    cell = harness.find_cell(args.workload, ROOT)
    if cell.mix["loop"] != "closed":
        print("split_cell: closed-loop mixes only", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("split_cell: needs a TPU", file=sys.stderr)
        return 2
    import lanl_stream
    import plain_ref
    import trace_ops
    import trace_scopes

    seed = args.seed % 2**63
    cfg, service = cell.config, cell.config["service"]
    n_pe, chunk = int(service["n_pe"]), int(service["chunk_size"])
    stream = lanl_stream.generate(cfg["workload"], seed)
    reqs = harness.to_requests(stream)
    sess, snap = harness.make_session(service)
    harness.closed_pass(sess, snap, reqs, chunk, harness.Recorder())
    setup_s = time.perf_counter() - T_START

    off = harness.Recorder()
    out_off = harness.closed_window(sess, snap, reqs, chunk, args.seconds,
                                    off)
    on = harness.Recorder()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_split_")
    jax.profiler.start_trace(trace_dir)
    out_on = harness.closed_window(sess, snap, reqs, chunk,
                                   float(cell.mix["trace_seconds"]), on)
    jax.profiler.stop_trace()
    counts = sess.metrics()

    ref = plain_ref.decide(stream, n_pe, service["policy"])
    checks = {}
    for name, rec in (("off", off), ("traced", on)):
        cmp = harness.compare(rec, ref)
        checks[name] = harness.checks_of(
            cmp, len(rec.passes) * len(reqs),
            counts["search_path"] == cfg["search_path"])

    red = trace_scopes.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    paths = trace_scopes.paths_by_module(
        red["ops"], red["modules"], hlo_maps(sess, service, reqs, chunk))
    spans = red["spans"]
    t0 = min(s for _, s, _ in spans)
    t1 = max(e for _, _, e in spans)
    window = t1 - t0
    steps = on.scan_steps
    split = trace_scopes.scan_split(red["ops"], paths, red["modules"],
                                    t0, t1)
    idle_all = trace_scopes.idle_by_span(red["modules"], spans,
                                         red["program_spans"], t0, t1)
    idle_bench = trace_ops.attribute(
        trace_ops.gaps(red["modules"], t0, t1), spans)
    busy = trace_ops.busy_ns(red["modules"], t0, t1)
    keep = [i for i, (_, s, e) in enumerate(red["ops"]) if e > t0 and s < t1]

    def per_step(ns):
        return None if ns is None else ns / 1e3 / steps

    def share(num, den):
        return None if num is None or not den else 100.0 * num / den

    scan_ns = sum(e - s for n, s, e in trace_ops.clip(red["modules"], t0, t1)
                  if trace_scopes.SCAN_MODULE in n)
    metrics = dict(
        host_dispatch_us=trace_scopes.mean_span_us(
            red["program_spans"], "repro.offer.dispatch", t0, t1),
        drain_idle_share=trace_scopes.share_of(idle_all, "repro.drain",
                                               window),
        release_step_us=per_step(trace_scopes.phase_ns(split,
                                                       "admit.release")),
        search_step_us=per_step(trace_scopes.phase_ns(split,
                                                      "admit.search")),
        commit_step_us=per_step(trace_scopes.phase_ns(split,
                                                      "admit.commit")),
        early_reject_share=share(counts.get("early_rejects"), len(reqs)),
        tile_skip_share=share(counts.get("search_tiles_skipped"),
                              counts.get("search_tiles")),
        scan_step_us=per_step(scan_ns),
        device_idle_share=100.0 * (1 - busy / window))
    result = dict(
        workload=cell.name, seed=args.seed, setup_s=setup_s,
        admits_per_s_off=out_off["admits_per_s"],
        admits_per_s_traced=out_on["admits_per_s"],
        passes_off=len(off.passes), passes_traced=len(on.passes),
        correct=all(c["value"] <= c["limit"] for ch in checks.values()
                    for c in ch.values()),
        checks=checks, metrics=metrics,
        counters={k: counts.get(k) for k in
                  ("early_rejects", "search_tiles", "search_tiles_skipped",
                   "search_path", "capacity")},
        requests_per_pass=len(reqs), scan_steps=steps,
        window_s=window / 1e9,
        scan_split_us_per_step={k: v / 1e3 / steps for k, v in sorted(
            split.items(), key=lambda kv: -kv[1])},
        scope_coverage=trace_scopes.coverage(
            [red["ops"][i] for i in keep], [paths[i] for i in keep]),
        idle_by_span={k: v / 1e9 for k, v in sorted(
            idle_all.items(), key=lambda kv: -kv[1])},
        idle_by_bench_span={k: v / 1e9 for k, v in sorted(
            idle_bench.items(), key=lambda kv: -kv[1])},
        program_spans_us={n: trace_scopes.mean_span_us(
            red["program_spans"], n, t0, t1) for n in sorted(
                {n for n, _, _ in red["program_spans"]})},
        top_unscoped=trace_ops.top(
            [red["ops"][i] for i in keep
             if trace_scopes.in_step(paths[i])
             and trace_scopes.phase(paths[i]) is None], 5),
        device=harness.device_info(devices, cell.chips))
    fx = fixture(red, paths, t0) if args.fixture else None
    if fx is not None:
        Path(args.fixture).write_text(json.dumps(fx, separators=(",", ":")))
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
