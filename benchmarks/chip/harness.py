"""One run of one benchmark cell: set-up, measured window, check.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``configs/<file>.json``: the deployment, its generator
parameters and the service settings it runs) and a traffic mix
(``mixes/<traffic>.json``: how the stream is offered).  Each per-layer
metric is read by ``metrics/<name>.py``, a module with one function
``read(reading) -> float | None``.  All three are found by name, so a
new configuration, mix or metric is a new file and a new entry, never
an edit.

Every mix offers the configuration's stream, drawn from the seed by
the benchmark's own generator (``lanl_stream.py``), as *passes* into
one long-lived session.  Each pass restores a snapshot of the empty
session, so every pass must reproduce the same decisions, which the
plain reference (``plain_ref.py``) computes once, after the window.

``closed`` mixes offer each whole pass at once, one client;
``open`` mixes offer, in stream order, whatever a seeded Poisson
schedule at ``rate_per_s`` has made due, one ``offer`` per loop.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Callable, Dict, List

import numpy as np

import lanl_stream
import peaks as peaks_lib
import plain_ref
import trace_ops

CHIP_DIR = Path(__file__).resolve().parent


class SetupError(RuntimeError):
    """The cell cannot run here (no chip, no program, bad files)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]
    chip_dir: Path


def find_cell(name: str, root: Path, chip_dir: Path = CHIP_DIR) -> Cell:
    """Resolve a workload of ``root/BENCHMARK.json`` to its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    try:
        wl = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json") from None
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m["name"] for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e)]
    return Cell(name=name, chips=int(wl["chips"]),
                config=json.loads((root / cfg["file"]).read_text()),
                mix=json.loads(
                    (chip_dir / "mixes" / f"{wl['traffic']}.json").read_text()),
                end_to_end=e2e, per_layer=per_layer,
                units={m["name"]: m["unit"] for m in
                       bench["end_to_end"] + bench["per_layer"]},
                chip_dir=chip_dir)


def load_reader(chip_dir: Path, metric: str) -> Callable:
    """``read`` of ``metrics/<metric>.py``."""
    path = chip_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax
    with jax.profiler.TraceAnnotation(trace_ops.SPAN_PREFIX + name):
        yield


class CompileCount:
    """Backend compiles while active (there should be none in the
    window: every shape is warmed up first)."""

    def __init__(self):
        self.n = 0

    def _on(self, event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def make_session(service: dict):
    """A session of the program's reservation service, and a snapshot
    of it empty."""
    from repro.api import ReservationService, ServiceConfig
    from repro.core.types import Policy
    cfg = ServiceConfig(**{**service, "policy": Policy(service["policy"])})
    sess = ReservationService(cfg).session()
    return sess, sess.snapshot()


def to_requests(stream: Dict[str, np.ndarray]) -> list:
    from repro.core.types import ARRequest
    cols = [stream[f].tolist() for f in lanl_stream.FIELDS]
    return [ARRequest(t_a=a, t_r=r, t_du=d, t_dl=dl, n_pe=k)
            for a, r, d, dl, k in zip(*cols)]


def read_back(res) -> Dict[str, np.ndarray]:
    """The decisions of one offer on the host; ``steps`` counts the
    scan steps its chunks ran, filler included."""
    dec = res.decision
    if dec is None:                      # nothing was decided
        return dict(acc=np.zeros(0, bool), t_s=np.zeros(0, np.int32),
                    mask=np.zeros((0, 1), np.uint32), steps=0)
    valid = np.asarray(res.valid)
    return dict(acc=np.asarray(dec.accepted)[valid],
                t_s=np.asarray(dec.t_s)[valid],
                mask=np.asarray(dec.pe_mask)[valid],
                steps=len(valid))


class Recorder:
    """Decisions and counters of the window, pass by pass."""

    def __init__(self):
        self.passes: List[List[Dict[str, np.ndarray]]] = []
        self.scan_steps = 0
        self.decided = 0
        self.offer_walls: List[float] = []
        self.phases: List[List[float]] = []  # closed passes: restore,
        #                                      offer, readback (s)

    def new_pass(self) -> None:
        self.passes.append([])

    def add(self, got: Dict[str, np.ndarray]) -> None:
        self.passes[-1].append(got)
        self.scan_steps += got["steps"]
        self.decided += len(got["acc"])


def closed_pass(sess, snap, reqs, chunk: int, rec: Recorder) -> None:
    """One pass offered at once.  Its first chunk goes alone: a restored
    state is shared with the snapshot, so the service runs that first
    offer without donation and with a sync per chunk; the rest of the
    pass then pipelines as it would in a long-lived session."""
    rec.new_pass()
    t0 = time.perf_counter()
    with span("restore"):
        sess.restore(snap)
    t1 = time.perf_counter()
    with span("offer"):
        first = sess.offer(reqs[:chunk], flush=False)
        rest = sess.offer(reqs[chunk:])
    t2 = time.perf_counter()
    with span("readback"):
        rec.add(read_back(first))
        rec.add(read_back(rest))
    t3 = time.perf_counter()
    rec.offer_walls.append(t3 - t0)
    rec.phases.append([t1 - t0, t2 - t1, t3 - t2])


def closed_window(sess, snap, reqs, chunk: int, seconds: float,
                  rec: Recorder) -> Dict[str, float]:
    t0 = time.perf_counter()
    while True:
        with span("pass"):
            closed_pass(sess, snap, reqs, chunk, rec)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return dict(admits_per_s=rec.decided / elapsed, window_s=elapsed)


def due_schedule(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson process at
    ``rate``, from the seed, up to ``seconds``."""
    rng = np.random.default_rng([seed, 1])
    n = int(rate * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while due[-1] < seconds:   # a rare short draw: extend it
        due = np.append(due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=n)))
    return due[due < seconds]


def open_window(sess, snap, reqs, due: np.ndarray, rec: Recorder,
                max_batch: int) -> Dict[str, Any]:
    """Offer request ``i`` (job ``i % N`` of pass ``i // N``) once it is
    due, at most ``max_batch`` per offer (the shapes warmed up); every
    request due in the window is decided before returning.  Latency
    runs from a request's due time to its decision being readable on
    the host."""
    n_jobs, total = len(reqs), len(due)
    lat = np.empty(total)
    lateness: List[float] = []          # first due request -> its offer
    backlog: List[tuple] = []   # (time, due but not offered, offered)
    nxt = 0
    t0 = time.perf_counter()
    while nxt < total:
        now = time.perf_counter() - t0
        ready = int(np.searchsorted(due, now, side="right"))
        if ready <= nxt:
            time.sleep(max(due[nxt] - now, 0.0))
            continue
        j = nxt % n_jobs
        if j == 0:
            rec.new_pass()
            with span("restore"):
                sess.restore(snap)
        stop = min(ready, nxt + (n_jobs - j), nxt + max_batch)
        lateness.append(now - due[nxt])
        backlog.append((now, ready - nxt, stop))
        w0 = time.perf_counter()
        with span("offer"):
            res = sess.offer(reqs[j:j + stop - nxt])
        with span("readback"):
            rec.add(read_back(res))
        done = time.perf_counter()
        rec.offer_walls.append(done - w0)
        lat[nxt:stop] = done - t0 - due[nxt:stop]
        nxt = stop
    elapsed = time.perf_counter() - t0
    return dict(decision_p95_ms=float(np.percentile(lat, 95) * 1e3),
                decision_p50_ms=float(np.percentile(lat, 50) * 1e3),
                admits_per_s=rec.decided / elapsed, window_s=elapsed,
                lateness=lateness, backlog=backlog, latencies=lat)


def warm_open(sess, snap, reqs, chunk: int, max_chunks: int) -> None:
    """Compile every shape an open loop meets: offers of 1 to
    ``max_chunks`` chunks, right after a restore (unshared state) and
    pipelined."""
    for k in range(1, max_chunks + 1):
        sess.restore(snap)
        n = k * chunk - 1
        read_back(sess.offer(reqs[:n]))
        read_back(sess.offer(reqs[n:2 * n]))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def compare(rec: Recorder, ref) -> Dict[str, int]:
    """Every decision of every pass against the reference's decisions
    of the same jobs: accept/reject, start time, and PE set."""
    ref_acc, ref_ts, ref_mask = ref
    mismatched = missing = attempted = 0
    for parts in rec.passes:
        parts = [p for p in parts if len(p["acc"])]
        if not parts:
            continue
        acc = np.concatenate([p["acc"] for p in parts])
        ts = np.concatenate([p["t_s"] for p in parts])
        mask = np.concatenate([p["mask"] for p in parts])
        n = len(acc)
        attempted += n
        if n > len(ref_acc):
            missing += n - len(ref_acc)      # more answers than jobs
            n = len(ref_acc)
        a, r = acc[:n], ref_acc[:n]
        bad = (a != r) | (a & r & (ts[:n] != ref_ts[:n])) | (
            mask[:n] != ref_mask[:n]).any(axis=1)
        mismatched += int(bad.sum())
    return dict(mismatched=mismatched, missing=missing,
                attempted=attempted)


def checks_of(cmp: Dict[str, int], expected: int, path_ok: bool
              ) -> Dict[str, dict]:
    """Numbers compared, each with its limit (all exact: limit 0)."""
    return {
        "mismatched": dict(value=cmp["mismatched"], limit=0),
        "missing": dict(value=cmp["missing"] + max(
            expected - cmp["attempted"], 0), limit=0),
        "off_path": dict(value=int(not path_ok), limit=0),
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Reading:
    """What a per-layer reader sees: the reduced trace of the traced
    window, the run's counters and the session's shapes."""
    ops: list
    modules: list
    spans: list
    t0: int
    t1: int
    busy_ns: int
    counters: Dict[str, Any]
    shapes: Dict[str, int]
    peaks: Dict[str, float]


def device_info(devices, chips: int) -> Dict[str, Any]:
    """The devices as JAX reports them; the memory peak is that of the
    fullest chip the cell uses."""
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices), memory_peak_bytes=peak)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices, *, reference=plain_ref.decide
        ) -> Dict[str, Any]:
    """Set up, warm up, measure, check.  Returns the result line."""
    import jax
    seed = seed % 2**63
    cfg, mix = cell.config, cell.mix
    service = cfg["service"]
    n_pe, chunk = int(service["n_pe"]), int(service["chunk_size"])
    stream = lanl_stream.generate(cfg["workload"], seed)
    reqs = to_requests(stream)
    sess, snap = make_session(service)
    rec = Recorder()
    loop = mix["loop"]
    # warm-up: the cell's own shapes, counted in set-up
    if loop == "closed":
        closed_pass(sess, snap, reqs, chunk, rec)
    elif loop == "open":
        warm_open(sess, snap, reqs, chunk, int(mix["warm_chunks"]))
        due = due_schedule(seed, float(mix["rate_per_s"]), seconds)
    else:
        raise SetupError(f"unknown loop {loop!r}")
    rec = Recorder()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace \
        else None
    window_s = min(seconds, float(mix["trace_seconds"])) if trace \
        else seconds
    # what set-up made stays put: collections in the window then scan
    # only what the window allocates
    gc.collect()
    gc.freeze()
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    with CompileCount() as compiles:
        if loop == "closed":
            out = closed_window(sess, snap, reqs, chunk, window_s, rec)
        else:
            if trace:
                due = due[due < window_s]
            out = open_window(sess, snap, reqs, due, rec,
                              int(mix["warm_chunks"]) * chunk)
    if trace:
        jax.profiler.stop_trace()
    gc.unfreeze()
    dev = device_info(devices, cell.chips)
    path = sess.metrics()["search_path"]
    capacity = sess.metrics()["capacity"]
    del sess, snap
    gc.collect()
    # the reference, after the window and outside set-up
    ref = reference(stream, n_pe, service["policy"])
    expected = len(rec.passes) * len(reqs) if loop == "closed" \
        else len(due)
    cmp = compare(rec, ref)
    checks = checks_of(cmp, expected, path == cfg["search_path"])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    values = dict(setup_s=setup_s, **{k: v for k, v in out.items()
                                      if isinstance(v, float)})
    counters = dict(scan_steps=rec.scan_steps, decided=rec.decided,
                    offers=len(rec.offer_walls),
                    offer_wall_ms=[w * 1e3 for w in rec.offer_walls],
                    compiles_in_window=compiles.n)
    result: Dict[str, Any] = dict(
        correct=correct, attempted=max(cmp["attempted"], expected),
        failed=checks["mismatched"]["value"] + checks["missing"]["value"],
        metrics={}, device=dev)
    if trace:
        red = trace_ops.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans = red["spans"]
        t0 = min(s for _, s, _ in spans)
        t1 = max(e for _, _, e in spans)
        busy = sum(trace_ops.busy_ns(mods, t0, t1) for mods in
                   red["by_device"]) / max(len(red["by_device"]), 1)
        reading = Reading(
            ops=trace_ops.clip(red["ops"], t0, t1),
            modules=trace_ops.clip(red["modules"], t0, t1),
            spans=spans, t0=t0, t1=t1, busy_ns=int(busy),
            counters=counters,
            shapes=dict(capacity=capacity, n_pe=n_pe, chunk_size=chunk),
            peaks=peaks_lib.peaks(dev["kind"]))
        for name in cell.per_layer:
            val = load_reader(cell.chip_dir, name)(reading)
            if val is not None:
                result["metrics"][name] = dict(value=val,
                                               unit=cell.units[name])
        idle = trace_ops.gaps(red["modules"], t0, t1)
        by_host = trace_ops.attribute(idle, spans)
        result["device"].update(busy_s=busy / 1e9, window_s=(t1 - t0) / 1e9)
        info_trace = dict(device_modules=trace_ops.top(reading.modules, 5))
        result["breakdown"] = dict(
            device_ops=trace_ops.top(reading.ops, 10),
            idle_gaps=[[k, v / 1e9] for k, v in sorted(
                by_host.items(), key=lambda kv: -kv[1])[:10]])
    else:
        for name in cell.end_to_end:
            if name not in values:
                raise SetupError(f"{cell.name}: the {loop} loop gives no "
                                 f"{name}")
            result["metrics"][name] = dict(value=values[name],
                                           unit=cell.units[name])
    result["info"] = dict(
        **(info_trace if trace else {}),
        window_s=out["window_s"], passes=len(rec.passes),
        compiles_in_window=compiles.n, search_path=path,
        capacity=capacity,
        accepted_per_pass=int(np.asarray(ref[0]).sum()),
        **({"pass_phases_s": rec.phases} if rec.phases else {}),
        **({k: out[k] for k in ("decision_p50_ms",) if k in out}),
        **({"lateness_p95_ms": float(np.percentile(out["lateness"], 95)
                                     * 1e3)} if "lateness" in out else {}))
    result["checks"] = checks
    return result


def report(result: Dict[str, Any]) -> None:
    """Numbers compared, then the result line, last on each stream."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
