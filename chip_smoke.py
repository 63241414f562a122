"""Smoke run of the reservation service on a TPU at LANL-CM5 scale.

    python chip_smoke.py [--seed S]               # one chip
    python chip_smoke.py [--seed S] --chips 4     # the mesh paths only

One chip: the paper's deployment (section 6.1: 1024 PEs, a 10^4-job
LANL-CM5 stream from ``--seed``) goes through the entry points a client
calls, ``ReservationService(...).session().offer(...)`` on the chunked,
donated, pipelined ring path, then ``cancel()`` and ``tick()`` past the
horizon.  Two sessions run: PE_W on the jnp search path and FF on the
compiled Pallas ``availscan_select`` kernel with the availability index
on.  Every accept/reject, and every accepted start time, must equal the
host engine's (``simulate(..., engine="host")``).  A third, multi-tenant
session on the first 2,000 jobs must match the host ``TenantOracle``
bit for bit, telemetry included.

``--chips 4``: lane sharding (a 28-lane ensemble, 7 policies x 4 seeds)
and routed partitions (4 partitions, best-acceptance routing), each run
with ``placement="auto"`` and ``placement="single"``.  Decisions must be
identical between the two, and the sharded state must span 4 devices.

Earlier lines are informational.  The last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every check passed.  The script exits non-zero, printing no result,
when JAX finds no TPU or any check fails.  It starts no processes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parent
if str(_ROOT / "src") not in sys.path:
    sys.path.insert(0, str(_ROOT / "src"))

try:
    import jax
    from repro.api import ReservationService, ServiceConfig
    from repro.core.hostsched import TenantOracle
    from repro.core.types import ALL_POLICIES, Policy
    from repro.launch.compile_cache import use_compile_cache
    from repro.sim import WorkloadParams, generate, simulate
    from repro.tenancy import TenantSpec
except ModuleNotFoundError as e:   # run outside a checkout of the repo
    sys.exit(f"chip_smoke.py needs the repository around it: {e}")

N_PE = 1024          # LANL-CM5 (paper section 6.1)
N_JOBS = 10_000
INDEX_TILE = 16      # availability-index tile (records per summary)
N_CANCEL = 4         # reservations cancelled before the final tick
N_TENANCY_JOBS = 2_000
N_MESH_JOBS = 2_000  # jobs per stream on the --chips 4 paths


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def lanl_stream(seed: int, n_jobs: int = N_JOBS, n_pe: int = N_PE):
    """The paper's workload with its section 6.1 defaults."""
    return generate(WorkloadParams(n_jobs=n_jobs, n_pe=n_pe, seed=seed))


def host_decisions(jobs, policy: Policy, n_pe: int = N_PE):
    """(accepted, t_s) per job from the host engine's event loop."""
    res = simulate(jobs, n_pe, policy, engine="host",
                   record_decisions=True)
    acc = np.asarray([a for a, _ in res.decisions], bool)
    t_s = np.asarray([t for _, t in res.decisions], np.int64)
    return acc, t_s


def mismatches(acc, t_s, ref_acc, ref_t_s) -> int:
    """Jobs whose decision differs; start times count where both
    accepted (a rejection carries no start time)."""
    acc, ref_acc = np.asarray(acc, bool), np.asarray(ref_acc, bool)
    both = acc & ref_acc
    return int((acc != ref_acc).sum()
               + (np.asarray(t_s)[both] != np.asarray(ref_t_s)[both]).sum())


def offer_stream(cfg: ServiceConfig, jobs):
    """One session offering the whole stream, timed to a device sync.

    Returns ``(session, result, accepted, t_s, seconds)``.
    """
    t0 = time.perf_counter()
    sess = ReservationService(cfg).session()
    res = sess.offer(jobs)
    dec = res.decision           # drains the pipelined chunks
    jax.block_until_ready((dec.accepted, dec.t_s))
    seconds = time.perf_counter() - t0
    valid = np.asarray(res.valid)
    acc = np.asarray(dec.accepted)[valid]
    t_s = np.asarray(dec.t_s)[valid]
    return sess, res, acc, t_s, seconds


def cancel_and_drain(sess, res, jobs, n_cancel: int = N_CANCEL) -> dict:
    """Cancel reservations still held after the last arrival, then
    ``tick`` past the horizon: every reservation must be released."""
    allocs = [a for a in res.allocations() if a is not None]
    held = [a for a in allocs if a.t_e > jobs[-1].t_a][:n_cancel]
    _check(bool(held), "no reservation held after the last arrival")
    pending = sess.metrics()["n_pending"]
    first = [sess.cancel(a) for a in held]
    again = [sess.cancel(a) for a in held]
    _check(all(first), f"cancel of a held reservation failed: {first}")
    _check(not any(again), f"second cancel succeeded: {again}")
    released = sess.tick(max(a.t_e for a in allocs))
    m = sess.metrics()
    _check(released == pending - len(held),
           f"tick released {released}, expected {pending - len(held)}")
    _check(m["n_pending"] == 0, f"{m['n_pending']} pending after tick")
    _check(not any(busy for _, busy in sess.records()),
           "timeline not empty after the final tick")
    return dict(cancelled=len(held), released=released)


def session_phase(name: str, cfg: ServiceConfig, jobs, *,
                  want_path: str) -> dict:
    """Cold pass (compiles), host comparison, cancel + tick, warm pass."""
    sess, res, acc, t_s, cold = offer_stream(cfg, jobs)
    ref_acc, ref_t_s = host_decisions(jobs, cfg.policy, cfg.n_pe)
    bad = mismatches(acc, t_s, ref_acc, ref_t_s)
    _check(bad == 0, f"{name}: {bad} decisions differ from the host "
                     f"engine")
    m = sess.metrics()
    _check(m["search_path"] == want_path,
           f"{name}: searches ran on {m['search_path']!r}, "
           f"expected {want_path!r}")
    drained = cancel_and_drain(sess, res, jobs)
    # warm pass: a fresh session, every shape already compiled
    _, _, acc2, t_s2, warm = offer_stream(cfg, jobs)
    _check(mismatches(acc2, t_s2, acc, t_s) == 0,
           f"{name}: warm pass decided differently")
    return dict(session=name, policy=cfg.policy.value, jobs=len(jobs),
                accepted=int(acc.sum()), matched=len(jobs) - bad,
                search_path=m["search_path"], capacity=m["capacity"],
                growths=m["growths"], chunks=m["chunks"],
                cold_pass_s=cold, warm_pass_s=warm,
                decisions_per_s=len(jobs) / warm, **drained)


def single_chip_phases(seed: int, n_jobs: int = N_JOBS,
                       n_pe: int = N_PE, capacity: int = 32) -> list:
    """The one-chip sessions: (a) PE_W on the jnp path, (b) FF on the
    kernel path, and a multi-tenant FF session on a shorter stream.

    ``capacity`` starts small so the grow-and-replay protocol runs
    under donation and pipelining.
    """
    jobs = lanl_stream(seed, n_jobs, n_pe)
    base = ServiceConfig(n_pe=n_pe, capacity=capacity,
                         pending_capacity=capacity)
    return [
        session_phase("pe_w_jnp", base.replace(policy=Policy.PE_W),
                      jobs, want_path="jnp"),
        session_phase("ff_kernel", base.replace(
            policy=Policy.FF, use_kernel=True, index_tile=INDEX_TILE),
            jobs, want_path="kernel"),
        tenancy_phase(seed, min(n_jobs, N_TENANCY_JOBS), n_pe),
    ]


def tenancy_phase(seed: int, n_jobs: int, n_pe: int = N_PE) -> dict:
    """Three tenants, one with a binding quota, against the host
    ``TenantOracle``: decisions, counters and the fixed-point EWMAs
    must agree bit for bit on the chip too."""
    jobs = [dataclasses.replace(j, tenant=i % 3) for i, j in
            enumerate(lanl_stream(seed, n_jobs, n_pe))]
    # tenant 1's PE-seconds budget runs out about halfway through
    spec = TenantSpec(weights=(1.0, 2.0, 4.0),
                      quotas=(None, 2.5e4 * n_jobs, None))
    cfg = ServiceConfig(n_pe=n_pe, policy=Policy.FF, tenants=spec)
    sess, _, acc, t_s, seconds = offer_stream(cfg, jobs)
    orc = TenantOracle(n_pe, cfg.policy, "none", spec)
    ref = [orc.admit(r) for r in jobs]
    bad = mismatches(acc, t_s, [a for a, _, _ in ref],
                     [t for _, t, _ in ref])
    _check(bad == 0, f"tenancy: {bad} decisions differ from the oracle")
    got, want = sess.metrics()["tenants"], orc.accounts.snapshot()
    diff = [k for k in want if not np.array_equal(got[k], want[k])]
    _check(not diff, f"tenancy: table fields differ: {diff}")
    quota_rejected = int(want["n_quota_rejected"].sum())
    _check(quota_rejected > 0, "tenancy: the quota never bound")
    return dict(session="tenancy", jobs=len(jobs), matched=len(jobs),
                accepted=int(acc.sum()), quota_rejected=quota_rejected,
                fields_equal=len(want), acc_ewma=got["acc_ewma"].tolist(),
                seconds=seconds)


def _devices_of(tree) -> set:
    return set().union(*(leaf.sharding.device_set
                         for leaf in jax.tree_util.tree_leaves(tree)))


def lane_sharding_phase(seed: int, n_jobs: int, n_devices: int,
                        n_pe: int = N_PE, n_seeds: int = 4) -> dict:
    """7 policies x ``n_seeds`` lanes, sharded against single placement."""
    streams, pols = [], []
    for s in range(n_seeds):
        jobs = lanl_stream(seed + s, n_jobs, n_pe)
        for p in ALL_POLICIES:
            streams.append(jobs)
            pols.append(p)
    out = {}
    for placement in ("auto", "single"):
        cfg = ServiceConfig(n_pe=n_pe, lanes=len(streams),
                            placement=placement)
        t0 = time.perf_counter()
        sess = ReservationService(cfg).session()
        res = sess.offer(streams, policy=pols)
        dec = res.decision
        jax.block_until_ready((dec.accepted, dec.t_s))
        secs = time.perf_counter() - t0
        valid = np.asarray(res.valid)
        out[placement] = dict(
            acc=np.asarray(dec.accepted)[valid],
            t_s=np.asarray(dec.t_s)[valid], seconds=secs,
            devices=len(_devices_of(sess.engine.states)),
            shards=sess.metrics()["placement_shards"])
    a, s = out["auto"], out["single"]
    bad = mismatches(a["acc"], a["t_s"], s["acc"], s["t_s"])
    _check(bad == 0, f"lanes: {bad} decisions differ between placements")
    _check(a["devices"] == n_devices,
           f"lanes: sharded state spans {a['devices']} devices, "
           f"expected {n_devices}")
    return dict(session="lanes", lanes=len(streams), jobs_per_lane=n_jobs,
                accepted=int(a["acc"].sum()), mismatches=bad,
                shards=a["shards"], devices_auto=a["devices"],
                devices_single=s["devices"], auto_s=a["seconds"],
                single_s=s["seconds"])


def partition_phase(seed: int, n_jobs: int, n_devices: int,
                    n_pe: int = N_PE, n_partitions: int = 4) -> dict:
    """Best-acceptance routed partitions, sharded against single."""
    jobs = lanl_stream(seed, n_jobs, n_pe)
    out = {}
    for placement in ("auto", "single"):
        cfg = ServiceConfig(n_pe=n_pe, n_partitions=n_partitions,
                            routing="best_acceptance",
                            placement=placement)
        t0 = time.perf_counter()
        sess = ReservationService(cfg).session()
        allocs = sess.offer(jobs).allocations()
        secs = time.perf_counter() - t0
        out[placement] = dict(
            allocs=[None if a is None else
                    (a.t_s, a.t_e, tuple(a.pe_ids)) for a in allocs],
            seconds=secs, devices=len(_devices_of(sess.engine.states)))
    a, s = out["auto"], out["single"]
    bad = sum(x != y for x, y in zip(a["allocs"], s["allocs"]))
    _check(len(a["allocs"]) == len(jobs) and bad == 0,
           f"partitions: {bad} allocations differ between placements")
    _check(a["devices"] == n_devices,
           f"partitions: sharded state spans {a['devices']} devices, "
           f"expected {n_devices}")
    return dict(session="partitions", partitions=n_partitions,
                jobs=len(jobs), mismatches=bad,
                accepted=sum(x is not None for x in a["allocs"]),
                devices_auto=a["devices"], devices_single=s["devices"],
                auto_s=a["seconds"], single_s=s["seconds"])


class CompileStats:
    """Backend compile time and persistent-cache hits while active."""

    def __init__(self):
        self.compile_s = 0.0
        self.requests = 0
        self.hits = 0

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_secs

    def __enter__(self):
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def _report(row: dict) -> None:
    print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharding and partition "
                         "paths, across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"refusing to run on it", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 2
    cache = use_compile_cache(_ROOT / ".jax_cache")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"compile cache: {cache}", flush=True)
    try:
        with CompileStats() as stats:
            if args.chips == 4:
                rows = [lane_sharding_phase(args.seed, N_MESH_JOBS, 4),
                        partition_phase(args.seed, N_MESH_JOBS, 4)]
            else:
                rows = single_chip_phases(args.seed)
            for row in rows:
                _report(row)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile: backend_compile_s={stats.compile_s:.3f} "
          f"cache_requests={stats.requests} cache_hits={stats.hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
