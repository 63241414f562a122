"""Benchmark harness: one section per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --list
    PYTHONPATH=src python -m benchmarks.run --check [--tolerance T]

Prints CSV blocks: ``name,...columns`` per section.  ``--full`` uses
the paper's 10^4-job workloads (slow); default is a reduced size that
preserves every reported ordering.

``--check`` is the perf-regression mode (CI ``perf-smoke``): it
re-measures the eight BENCH benchmarks at reduced sizes and compares
the freshly measured *ratios* — device-vs-host throughput, backfill
mode cost vs the plain scan, ring-vs-rescan streaming,
sharded-vs-single mesh placement, pipelined-vs-eager chunked offers,
batched-vs-sequential fleet ingress, tenancy-on-vs-off gated
admission (plus the hard zero on idle metrics-poll device fetches)
and the multi-resource timeline cost curve (R=1 parity overhead and
the R=4 plane cost vs the legacy single-plane session) — against the
committed
``BENCH_*.json`` files with a tolerance band, plus the hierarchical-
index floors: per-policy machine-normalised ``speedup_vs_pr5 >= 1.0``
and the index on-vs-off ratios (standard-stream floor, saturated
early-reject speedup, BENCH_index.json).  Ratios only:
absolute wall times are meaningless on shared runners, but a device
path that regresses from 3x-faster-than-host to slower-than-host
moves its ratio far beyond any plausible machine noise.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _emit(name: str, rows) -> None:
    print(f"\n== {name} ==")
    if not rows:
        print("(no rows)")
        return
    cols = list(rows[0].keys())
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))
    sys.stdout.flush()


def _committed(name: str) -> dict:
    path = _ROOT / f"BENCH_{name}.json"
    with open(path) as fh:
        return json.load(fh)


def check(tolerance: float) -> int:
    """Ratio gates vs the committed BENCH files; returns #failures.

    Fresh measurements use the committed workload sizes with fewer
    repeats; ``tolerance`` is the allowed *relative* drift of each
    ratio (default 0.5: a committed 3.0x device-vs-host gate fails
    below 1.5x).  Cost-ratio ("le") gates get an extra +0.5 absolute
    slack — their committed values sit near 1.0, where relative bands
    are tighter than shared-runner noise on tens-of-ms walls.  No
    absolute wall-time asserts anywhere.
    """
    from benchmarks import bench_backfill, bench_fleet, bench_index, \
        bench_mesh, bench_multires, bench_policies, bench_service, \
        bench_tenancy

    failures = []
    checks = []

    def gate(label: str, fresh: float, committed: float,
             direction: str) -> None:
        if direction == "ge":
            bound = committed * (1.0 - tolerance)
            ok = fresh >= bound
        else:
            bound = committed * (1.0 + tolerance) + 0.5
            ok = fresh <= bound
        checks.append({
            "gate": label, "fresh_ratio": round(fresh, 3),
            "committed_ratio": round(committed, 3),
            "bound": round(bound, 3),
            "direction": direction,
            "status": "PASS" if ok else "FAIL",
        })
        if not ok:
            failures.append(label)

    # -- admission: device stream vs host loop ------------------------
    # one gate on the MEDIAN ratio across the seven policies: the
    # per-policy ratios move several 10s of percent with the host
    # loop's cache behaviour on shared runners, the median is stable
    from benchmarks._measure import median

    ref_rows = _committed("admission")["rows"]
    rows = bench_policies.admission_throughput(repeats=3,
                                               out_path=None)
    fresh = median(
        r["device_stream_adm_per_s"] / max(
            r["host_loop_adm_per_s"], 1e-9) for r in rows)
    committed = median(
        r["device_stream_adm_per_s"] / max(
            r["host_loop_adm_per_s"], 1e-9) for r in ref_rows)
    gate("admission/median:stream_vs_host", fresh, committed, "ge")

    # -- admission: per-policy machine-normalised PR 5 floor ----------
    # the PR 5 regression rows must stay recovered: every freshly
    # measured speedup_vs_pr5 (host-geomean normalised, so runner
    # speed cancels) holds the 1.0 floor
    for r in rows:
        gate(f"admission/{r['policy']}:speedup_vs_pr5",
             r["speedup_vs_pr5"], 1.0, "ge")

    # -- index: on-vs-off ratio floors (BENCH_index.json) -------------
    # standard stream may not dip below the per-policy floor; the
    # saturated early-reject cell must keep its speedup.  Both are
    # same-machine A/B ratios, immune to runner speed.
    idx_rows = bench_index.index_throughput(repeats=3, out_path=None)
    for r in idx_rows:
        label = (f"index/{r['policy']}:on_vs_off"
                 if r["cell"] == "standard"
                 else "index/saturated:on_vs_off")
        gate(label, r["ratio_on_vs_off"], r["floor"], "ge")

    # -- sweep: vmapped grid vs host loop -----------------------------
    ref = {r["variant"]: r for r in _committed("sweep")["rows"]}
    rows = bench_policies.sweep_throughput(repeats=3, out_path=None)
    got = {r["variant"]: r for r in rows}
    for variant in ("device_scan", "vmapped_grid"):
        fresh = got[variant]["cells_per_s"] / max(
            got["host_loop"]["cells_per_s"], 1e-9)
        committed = ref[variant]["cells_per_s"] / max(
            ref["host_loop"]["cells_per_s"], 1e-9)
        gate(f"sweep/{variant}:vs_host", fresh, committed, "ge")

    # -- backfill: mode cost vs the plain scan ------------------------
    ref = {r["mode"]: r for r in _committed("backfill")["rows"]}
    rows = bench_backfill.backfill_throughput(repeats=5,
                                              out_path=None)
    for row in rows:
        mode = row["mode"]
        if mode in ("none", "none_idle") or mode not in ref:
            continue
        gate(f"backfill/{mode}:cost_vs_plain",
             row["warm_cost_vs_plain"],
             ref[mode]["warm_cost_vs_plain"], "le")

    # -- service: warm ring-chunked vs re-scan ------------------------
    ref = {r["variant"]: r for r in _committed("service")["rows"]}
    rows = bench_service.service_throughput(repeats=3, out_path=None)
    got = {r["variant"]: r for r in rows}
    fresh = got["ring_chunked"]["warm_req_per_s"] / max(
        got["rescan_per_group"]["warm_req_per_s"], 1e-9)
    committed = ref["ring_chunked"]["warm_req_per_s"] / max(
        ref["rescan_per_group"]["warm_req_per_s"], 1e-9)
    gate("service/ring_vs_rescan:warm", fresh, committed, "ge")

    # -- tenancy: gated step cost vs the zero-tenant session ----------
    # the zero-tenant path must stay at the PR 7 ring-chunked cost
    # (ratio vs the freshly measured service bench ~ the committed
    # one), the tenanted path within its committed constant factor,
    # and idle metrics polls must stay fetch-free (hard 0 gate)
    ten_ref = {r["variant"]: r for r in _committed("tenancy")["rows"]}
    ten_got = {r["variant"]: r for r in bench_tenancy.
               tenancy_throughput(repeats=3, out_path=None)}
    service_ref = ref
    fresh = ten_got["tenancy_off"]["warm_req_per_s"] / max(
        got["ring_chunked"]["warm_req_per_s"], 1e-9)
    committed = ten_ref["tenancy_off"]["warm_req_per_s"] / max(
        service_ref["ring_chunked"]["warm_req_per_s"], 1e-9)
    gate("tenancy/off_vs_pr7_ring:warm", fresh, committed, "ge")
    gate("tenancy/on_vs_off:cost",
         ten_got["tenancy_on"]["cost_vs_off"],
         ten_ref["tenancy_on"]["cost_vs_off"], "le")
    gate("tenancy/idle_poll:device_fetches",
         float(ten_got["metrics_poll"]["idle_device_fetches"]),
         float(ten_ref["metrics_poll"]["idle_device_fetches"]), "le")

    # -- multires: plane-count cost vs the legacy single-plane path ---
    # both gates are cost ratios against the SAME freshly measured
    # legacy stream, so machine speed cancels: r1 prices the rspec
    # code path on a byte-identical layout, r4 pins the plane cost
    # curve (a superlinear regression blows far past the band)
    mr_ref = {r["variant"]: r for r in _committed("multires")["rows"]}
    mr_got = {r["variant"]: r for r in bench_multires.
              multires_throughput(repeats=3, out_path=None)}
    for variant in ("r1", "r4"):
        gate(f"multires/{variant}_vs_legacy:cost",
             mr_got[variant]["cost_vs_legacy"],
             mr_ref[variant]["cost_vs_legacy"], "le")

    # -- mesh: sharded grid vs single placement, pipelined vs eager ---
    # a reduced 168-lane grid keeps the CI lane fast; both gates are
    # ratios of same-machine variants, so the size reduction cancels
    mesh_doc = _committed("mesh")
    ref = {r["variant"]: r
           for r in mesh_doc["sharded_grid"]["rows"]}
    got = {r["variant"]: r for r in bench_mesh.sharded_grid(
        n_seeds=8, repeats=3, out_path=None)}
    fresh = got["sharded_auto"]["cells_per_s"] / max(
        got["single_device"]["cells_per_s"], 1e-9)
    committed = ref["sharded_auto"]["cells_per_s"] / max(
        ref["single_device"]["cells_per_s"], 1e-9)
    gate("mesh/sharded_grid:vs_single", fresh, committed, "ge")
    gate("mesh/sharded_grid:steady_recompiles",
         float(got["sharded_auto"]["steady_recompiles"]),
         float(ref["sharded_auto"]["steady_recompiles"]), "le")

    ref = {r["variant"]: r
           for r in mesh_doc["offer_overlap"]["rows"]}
    got = {r["variant"]: r for r in bench_mesh.offer_overlap(
        repeats=3, out_path=None)}
    fresh = got["pipelined"]["warm_req_per_s"] / max(
        got["eager"]["warm_req_per_s"], 1e-9)
    committed = ref["pipelined"]["warm_req_per_s"] / max(
        ref["eager"]["warm_req_per_s"], 1e-9)
    gate("mesh/offer_overlap:pipelined_vs_eager", fresh, committed,
         "ge")

    # -- fleet: batched matcher vs sequential probe-commit ------------
    ref = {r["variant"]: r
           for r in _committed("fleet")["fleet_routing"]["rows"]}
    got = {r["variant"]: r for r in bench_fleet.fleet_routing(
        repeats=3, out_path=None)}
    fresh = got["batched"]["warm_req_per_s"] / max(
        got["sequential"]["warm_req_per_s"], 1e-9)
    committed = ref["batched"]["warm_req_per_s"] / max(
        ref["sequential"]["warm_req_per_s"], 1e-9)
    gate("fleet/batched_vs_sequential:warm", fresh, committed, "ge")
    gate("fleet/batched:dispatches",
         float(got["batched"]["dispatches"]),
         float(ref["batched"]["dispatches"]), "le")

    _emit("perf_check", checks)
    if failures:
        print(f"\n# PERF CHECK FAILED: {len(failures)} gate(s) out of "
              f"band (tolerance {tolerance}): {failures}")
    else:
        print(f"\n# perf check OK: {len(checks)} ratio gates within "
              f"tolerance {tolerance}")
    return len(failures)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale 10^4-job sweeps")
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true",
                    help="print every section name and exit")
    ap.add_argument("--check", action="store_true",
                    help="ratio-gate regression mode vs BENCH_*.json")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed relative ratio drift in --check")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(_ROOT / ".jax_cache")
    if args.check:
        sys.exit(1 if check(args.tolerance) else 0)
    n_jobs = 10_000 if args.full else 2_000
    t0 = time.time()

    from benchmarks import bench_backfill, bench_datastructure, \
        bench_fleet, bench_index, bench_mesh, bench_multires, \
        bench_policies, bench_service, bench_tenancy, gen_experiments
    from benchmarks.bench_roofline import ART_OPT, roofline_rows

    sections = {
        "fig2_3_umed_sweep":
            lambda: bench_policies.umed_sweep(n_jobs=n_jobs),
        "fig4_5_load_sweep":
            lambda: bench_policies.load_sweep(n_jobs=n_jobs),
        "fig6_7_flex_sweep":
            lambda: bench_policies.flex_sweep(n_jobs=n_jobs),
        "admission_throughput":
            lambda: bench_policies.admission_throughput(
                n_jobs=600 if args.full else 240),
        "sweep_throughput":
            lambda: bench_policies.sweep_throughput(
                n_jobs=300 if args.full else 120),
        "service_throughput":
            lambda: bench_service.service_throughput(
                n_jobs=600 if args.full else 240),
        "backfill_throughput":
            lambda: bench_backfill.backfill_throughput(
                n_jobs=600 if args.full else 240),
        "tenancy_throughput":
            lambda: bench_tenancy.tenancy_throughput(
                n_jobs=600 if args.full else 240),
        "multires_throughput":
            lambda: bench_multires.multires_throughput(
                n_jobs=600 if args.full else 240),
        "mesh_sharded_grid":
            lambda: bench_mesh.sharded_grid(),
        "mesh_offer_overlap":
            lambda: bench_mesh.offer_overlap(
                n_jobs=600 if args.full else 240),
        "fleet_routing":
            lambda: bench_fleet.fleet_routing(
                n_req=256 if args.full else 128),
        "index_throughput":
            lambda: bench_index.index_throughput(
                n_jobs=600 if args.full else 240),
        "datastructure_op_costs":
            lambda: bench_datastructure.op_costs(
                n_jobs=800 if args.full else 300),
        "datastructure_pe_scaling":
            lambda: bench_datastructure.scaling_with_pe_count(
                n_jobs=400 if args.full else 200),
        "roofline_single_pod":
            lambda: roofline_rows("single"),
        "roofline_multi_pod":
            lambda: roofline_rows("multi"),
        "roofline_optimized_single_pod":
            lambda: roofline_rows("single", ART_OPT),
        "experiments_tables":
            lambda: gen_experiments.tables(),
    }
    if args.list:
        for name in sections:
            print(name)
        return
    for name, fn in sections.items():
        if args.only and args.only != name:
            continue
        t = time.time()
        _emit(name, fn())
        print(f"# {name}: {time.time()-t:.1f}s")
    print(f"\n# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
