"""The benchmark's own copy of the Feitelson-Lublin LANL-CM5 generator.

Copied from the program's ``repro/sim/workload.py`` (arXiv:1203.0740,
section 6.1) so that the benchmark's traffic cannot move with the
program: the same parameters and seed give the same stream as the
program's ``generate``, drawn in the same order from one numpy
``default_rng(seed)``.  Returns plain int64 columns; the harness turns
them into the program's request objects.

Parameters come from a configuration file's ``workload`` block; keys
left out take the paper's defaults below.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

RUNTIME_VALUES = np.array([60, 300, 900, 1800, 3600, 10800], dtype=np.int64)

#: Section 6.1 defaults (target load 0.75, arrival factor 1, AR-time and
#: deadline factors 3, LANL-CM5's 1024 PEs, 10^4 jobs).
DEFAULTS: Dict[str, float] = dict(
    n_jobs=10_000, n_pe=1024,
    u_low=4.5, u_med=7.0, u_hi=10.0, u_prob=0.82,
    g1_shape=4.2, g1_scale=0.94, g2_shape=312.0, g2_scale=0.03,
    p_slope=-0.075, p_icept=1.1,
    arrival_shape=2.0, daily_cycle_amp=0.4, target_load=0.75,
    arrival_factor=1.0, artime_factor=3.0, deadline_factor=3.0)

FIELDS = ("t_a", "t_r", "t_du", "t_dl", "n_pe")


def params(workload: dict) -> dict:
    unknown = set(workload) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"unknown workload keys: {sorted(unknown)}")
    return {**DEFAULTS, **workload}


def _sizes(rng, p, n):
    stage = rng.random(n) < p["u_prob"]
    lo = rng.uniform(p["u_low"], p["u_med"], size=n)
    hi = rng.uniform(p["u_med"], p["u_hi"], size=n)
    k = np.clip(np.rint(np.where(stage, lo, hi)),
                np.ceil(p["u_low"]), np.floor(p["u_hi"]))
    return (2 ** k).astype(np.int64)


def _runtimes(rng, p, sizes):
    n = sizes.shape[0]
    prob_short = np.clip(p["p_slope"] * np.log2(sizes) + p["p_icept"],
                         0.05, 0.95)
    short = rng.random(n) < prob_short
    ln_r = np.where(short,
                    rng.gamma(p["g1_shape"], p["g1_scale"], size=n),
                    rng.gamma(p["g2_shape"], p["g2_scale"], size=n))
    dist = np.abs(ln_r[:, None] - np.log(RUNTIME_VALUES)[None, :])
    return RUNTIME_VALUES[np.argmin(dist, axis=1)]


def _arrivals(rng, p, n, seed):
    # base rate calibrated so the offered load hits target_load
    probe = np.random.default_rng(10_000 + seed)
    sz = _sizes(probe, p, 20_000)
    area = float(np.mean(sz * _runtimes(probe, p, sz)))
    mean_ia = area / (p["n_pe"] * p["target_load"])
    ia = rng.gamma(p["arrival_shape"], mean_ia / p["arrival_shape"], size=n)
    t = np.cumsum(ia)
    cyc = 1.0 + p["daily_cycle_amp"] * np.sin(2 * np.pi * t / 86_400.0)
    return np.cumsum(ia / np.maximum(cyc, 0.1)) / p["arrival_factor"]


def generate(workload: dict, seed: int) -> Dict[str, np.ndarray]:
    """One stream as int64 columns ``t_a, t_r, t_du, t_dl, n_pe``,
    in arrival order."""
    p = params(workload)
    rng = np.random.default_rng(seed)
    n = int(p["n_jobs"])
    t_a = np.rint(_arrivals(rng, p, n, seed)).astype(np.int64)
    n_pe = _sizes(rng, p, n)
    t_du = _runtimes(rng, p, n_pe)
    u_ar, u_dl = rng.random(n), rng.random(n)
    t_r = t_a + np.rint(p["artime_factor"] * u_ar * t_du).astype(np.int64)
    t_dl = t_r + t_du + np.rint(
        p["deadline_factor"] * u_dl * t_du).astype(np.int64)
    return dict(t_a=t_a, t_r=t_r, t_du=t_du, t_dl=t_dl, n_pe=n_pe)
