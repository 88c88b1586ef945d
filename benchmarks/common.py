"""Shared benchmark utilities."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Iterable, Optional, Tuple

import jax
import numpy as np

#: machine-readable perf rows accumulate here (one file, merged by row name
#: across runs) so the repo carries its own perf trajectory per PR
RESULTS_PATH = os.path.join(os.path.dirname(__file__), "results",
                            "perf.json")

#: schema v2 adds per-row execution-plan provenance (plan / device_count /
#: mesh_shape) so the trajectory distinguishes single- from multi-device
#: numbers; v1 rows are upgraded in place with single-device defaults
PERF_SCHEMA_VERSION = 2

_PLAN_DEFAULTS = {"plan": "single", "device_count": 1, "mesh_shape": None}
_ROW_FIELDS = ("name", "it_per_s", "us_per_call", "derived",
               "plan", "device_count", "mesh_shape")


def require_cpu_for_child(devices: int) -> None:
    """Guard before a mesh benchmark re-execs itself with virtual devices.

    Only the CPU backend can grow devices in a child process.  On an
    accelerator this process already holds the chips (asking for the device
    count initialized the backend), and a child that needs them would fail
    or hang, so refuse with the count that was missing."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"this benchmark needs {devices} devices but only "
            f"{jax.device_count()} {jax.default_backend()} device(s) are "
            "visible; it runs in this process and cannot hand the chips to "
            "a child process")


def time_iterations(step_fn: Callable, state, n_iter: int, warmup: int = 3,
                    windows: int = 3) -> Tuple[float, object]:
    """Returns (iterations/sec, final_state) for a jitted step.

    The rate is the median over ``windows`` independent timing windows of
    ``n_iter`` calls each — one hot window is not a stable estimate on a
    shared CI machine.
    """
    for _ in range(warmup):
        state, out = step_fn(state)
    jax.block_until_ready(out)
    rates = []
    for _ in range(max(windows, 1)):
        t0 = time.perf_counter()
        for _ in range(n_iter):
            state, out = step_fn(state)
        jax.block_until_ready(out)
        rates.append(n_iter / (time.perf_counter() - t0))
    return float(np.median(rates)), state


def row(name: str, it_per_s: float, *, plan: str = "single",
        device_count: int = 1, mesh_shape=None, **derived) -> dict:
    """One perf row.  ``plan``/``device_count``/``mesh_shape`` record the
    execution plan the number was measured under (schema v2); pass an
    :meth:`repro.algo.plan.ExecutionPlan.describe` dict via ``**`` or set
    them explicitly for meshed benchmarks."""
    d = ";".join(f"{k}={v}" for k, v in derived.items())
    return {"name": name, "us_per_call": 1e6 / it_per_s if it_per_s else 0.0,
            "it_per_s": it_per_s,
            "plan": plan, "device_count": device_count,
            "mesh_shape": list(mesh_shape) if mesh_shape else None,
            "derived": f"it_per_s={it_per_s:.1f}" + (";" + d if d else "")}


def write_perf_rows(rows: Iterable[dict],
                    path: Optional[str] = None) -> str:
    """Merge benchmark rows (by name, latest wins) into the perf-trajectory
    JSON at ``benchmarks/results/perf.json``.  Schema v2::

        {"schema_version": 2, "updated": <epoch seconds>,
         "rows": [{"name", "it_per_s", "us_per_call", "derived",
                   "plan", "device_count", "mesh_shape"}, ...]}

    v1 documents (no plan provenance) are read compatibly: their rows are
    kept and upgraded with single-device defaults.
    """
    path = path or RESULTS_PATH
    doc = {"rows": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            if old.get("schema_version") in (1, PERF_SCHEMA_VERSION):
                doc = old
        except (json.JSONDecodeError, OSError):
            pass
    merged = {r["name"]: dict(_PLAN_DEFAULTS, **r)
              for r in doc.get("rows", [])}
    for r in rows:
        merged[r["name"]] = dict(_PLAN_DEFAULTS,
                                 **{k: r[k] for k in _ROW_FIELDS if k in r})
    doc["schema_version"] = PERF_SCHEMA_VERSION
    doc["rows"] = [merged[k] for k in sorted(merged)]
    doc["updated"] = int(time.time())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path
