"""Poll-cheap telemetry snapshots of a device :class:`TenantTable`.

The counters live *in* the table, updated inside the fused admit step
(the same lazy-accumulator discipline as the service's
``_defer_accepted`` counter: nothing is read back per step).  A
snapshot is therefore one ``device_get`` of the whole table pytree —
and the service caches it until the state actually changes, so
polling an idle session costs zero device dispatches
(``tests/test_tenancy.py::test_idle_metrics_zero_device_fetches``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from .table import EWMA_ONE, TenantTable

#: Table fields surfaced per tenant by :func:`tenant_view`.
_PER_TENANT = ("weight", "quota", "max_live", "used", "live",
               "n_accepted", "n_rejected", "n_quota_rejected",
               "n_parked", "n_reaped", "acc_ewma", "slow_ewma")


def snapshot(table: TenantTable, fetch=None) -> Dict[str, np.ndarray]:
    """One fused host read of every tenant counter.

    ``fetch`` is the device->host transfer function (defaults to
    ``jax.device_get``); the service injects its counted
    ``_device_fetch`` hook so tests can assert poll cost.
    """
    if fetch is None:
        import jax
        fetch = jax.device_get
    return to_host(fetch({f: getattr(table, f) for f in _PER_TENANT
                          + ("occ_ewma",)}))


def to_host(fetched: Dict) -> Dict[str, np.ndarray]:
    """Fetched table fields as numpy, the fixed-point EWMAs as
    fractions (``/ EWMA_ONE``; exact in float64)."""
    out = {k: np.asarray(v) for k, v in fetched.items()}
    for k in ("acc_ewma", "slow_ewma", "occ_ewma"):
        if k in out:
            out[k] = out[k] / EWMA_ONE
    return out


def tenant_view(snap: Dict[str, np.ndarray], tenant: int) -> Dict:
    """One tenant's scalar slice of a :func:`snapshot` dict.

    Works on per-lane stacked snapshots too (leading ensemble axes
    are preserved; only the trailing tenant axis is indexed).
    """
    n = np.asarray(snap["weight"]).shape[-1]
    if not 0 <= tenant < n:
        raise ValueError(f"tenant {tenant} out of range [0, {n})")
    out = {}
    for k in _PER_TENANT:
        col = np.asarray(snap[k])[..., tenant]
        out[k] = col.item() if col.ndim == 0 else col
    out["tenant"] = tenant
    out["occ_ewma"] = snap["occ_ewma"]
    return out
