"""Plumbing shared by every cell of the chip benchmark.

Everything here is driven by names: a cell of ``BENCHMARK.json`` names its
configuration and its traffic mix, and this module finds

- ``bench/configs/<config>.json``   the configuration as it is run,
- ``bench/configs/<config>.py``     its plain reference,
- ``bench/traffic/<traffic>.json``  the traffic mix (its ``kind`` names the
                                    general driver ``bench/kinds/<kind>.py``),
- ``bench/limits/<cell>.json``      the limits of the cell's ``correct``,
- ``bench/metrics/<metric>.py``     one reader per per-layer metric,
- ``bench/flops/<name>.py``         operations and bytes from shapes,

so a later cell, mix or metric is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that cannot be measured: the harness prints no result line."""


# -- files by name -----------------------------------------------------------

def load_json(path: Path) -> Any:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (metric and config names carry dots, so they
    are not importable as packages)."""
    path = Path(path)
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload named {name!r} in BENCHMARK.json")


def config_of(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def reference_of(name: str):
    return load_module(BENCH / "configs" / f"{name}.py", f"bench_ref_{name}")


def traffic_of(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits_of(cell_name: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell_name}.json")


def kind_driver(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py", f"bench_kind_{kind}")


def flops_module(name: str):
    return load_module(BENCH / "flops" / f"{name}.py",
                       "bench_flops_" + name.replace(".", "_"))


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def applies(metric: dict, cell_name: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell_name in wl


def peaks_for(device_kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """The peaks of one device kind; an unknown kind is an error, never a
    default (a wrong peak would make every roofline share wrong)."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in {path}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


# -- the device ---------------------------------------------------------------

def require_devices(chips: int):
    """The TPU devices this cell runs on; refuses any other platform."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"no TPU visible to JAX (platform {platform!r}); "
                         "the benchmark runs on the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def setup_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache/`` at a fixed path in the checkout.  Small
    programs (refill, drain, init) are cached too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compiles, persistent-cache loads included
    (``jax.monitoring`` events), so a run can report how many happened
    inside its measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_doc(devices, memory_peak_bytes: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak_bytes}


# -- statistics ---------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) count as
    missing every limit."""
    if not values:
        return math.inf
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[k]


# -- output -------------------------------------------------------------------

def emit(doc: Dict[str, Any], checks: Dict[str, Dict[str, float]]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with the checks under the key that comes last."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    out = dict(doc)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
