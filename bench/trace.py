"""Reduce a profiler trace of the measured window to what per-layer
metrics read: device busy time, time per device operation, time per XLA
module, and the device's idle gaps labelled by the benchmark's host span
that was open while the device waited.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops`` line
holds one event per executed operation, their ``XLA Modules`` line one
per executed program.  Host spans are the ``bench.*`` events that the
harness opens with ``jax.profiler.TraceAnnotation`` around its calls into
each layer; the window is the ``bench.window`` span.  All times are
seconds; per-device quantities are averaged over the devices traced.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    devices: int
    #: op text (name and string stats) -> [count, seconds]
    ops: Dict[str, List[float]]
    #: module name -> [count, seconds]
    modules: Dict[str, List[float]]
    #: host span open during each idle gap -> [count, seconds]
    gaps: Dict[str, List[float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, pattern) -> Tuple[float, float]:
        """(count, seconds) of the device ops whose text (the HLO
        instruction, then its string stats) matches a compiled regular
        expression, per device."""
        n = t = 0.0
        for text, (c, s) in self.ops.items():
            if pattern.search(text):
                n += c
                t += s
        return n, t

    def module_time(self, pattern) -> Tuple[float, float]:
        n = t = 0.0
        for name, (c, s) in self.modules.items():
            if pattern.search(name):
                n += c
                t += s
        return n, t

    def breakdown(self, top: int = 10) -> dict:
        ops = defaultdict(float)
        for text, (_, s) in self.ops.items():
            ops[text.split(" | ")[0][:120]] += s
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in rank(ops)],
                "idle_gaps": [[f"{k} x{int(c)}", s] for k, (c, s) in
                              sorted(self.gaps.items(),
                                     key=lambda kv: -kv[1][1])[:top]]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _text(event) -> str:
    strs = [str(v) for k, v in event.stats
            if isinstance(v, str) and not k.startswith("_") and len(v) < 200]
    return " | ".join([event.name] + strs)


def _label(spans: List[Tuple[float, float, str]], times: List[float]
           ) -> List[str]:
    """For each of the sorted ``times``, the shortest host span open at
    it (spans nest, and other threads' spans may overlap)."""
    spans = sorted(spans)
    active: list = []
    i, out = 0, []
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] >= t]
        best = min(active, key=lambda s: s[1] - s[0], default=None)
        out.append(best[2] if best else "no bench span")
    return out


def reduce_profile(profile, window: Optional[Tuple[float, float]] = None
                   ) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``.  ``window`` (ns) defaults to
    the ``bench.window`` host span."""
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append((lines[OPS_LINE], lines.get(MODULES_LINE)))
    if not devices:
        raise ValueError("trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    if window is None:
        ws = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
        if not ws:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        window = ws[0]
    w0, w1 = window
    nd = len(devices)

    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    gaps: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    busy = 0.0
    for di, (op_line, mod_line) in enumerate(devices):
        ivs = []
        by_name: Dict[str, list] = {}
        for ev in op_line.events:
            start = ev.start_ns
            if not w0 <= start < w1:
                continue
            end = ev.end_ns
            rec = by_name.get(ev.name)
            if rec is None:
                rec = by_name[ev.name] = [0, 0.0, ev]
            rec[0] += 1
            rec[1] += end - start
            ivs.append((start, min(end, w1)))
        for c, t, ev in by_name.values():
            rec = ops[_text(ev)]
            rec[0] += c / nd
            rec[1] += t * 1e-9 / nd
        merged = _merge(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9 / nd
        if mod_line is not None:
            for ev in mod_line.events:
                if w0 <= ev.start_ns < w1:
                    rec = modules[ev.name]
                    rec[0] += 1 / nd
                    rec[1] += ev.duration_ns * 1e-9 / nd
        if di == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s]
            for (s, e), name in zip(idle, _label(
                    spans, [0.5 * (s + e) for s, e in idle])):
                gaps[name][0] += 1
                gaps[name][1] += (e - s) * 1e-9
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy, devices=nd,
                     ops=dict(ops), modules=dict(modules), gaps=dict(gaps))


def reduce_file(path: str) -> Reduction:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))


_TYPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\](\{[^}]*\})?")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def custom_call_types(text: str):
    """(results, operands) of a custom-call instruction's text, each a list
    of (dtype, dims, in_hbm): a layout with a memory space ``S(n)``, n > 0,
    is on-chip (VMEM), everything else is in HBM."""
    head = text.split(" | ")[0]
    rest = head.split(" = ", 1)[1]
    res, _, ops = rest.partition(" custom-call(")
    ops = ops.split("), custom_call_target")[0]

    def parse(part):
        out = []
        for dt, dims, layout in _TYPE.findall(part):
            shape = tuple(int(d) for d in dims.split(",") if d)
            space = re.search(r"S\((\d+)\)", layout or "")
            out.append((dt, shape, not space or space.group(1) == "0"))
        return out

    return parse(res), parse(ops)


def hbm_bytes(types) -> int:
    n = 0
    for dt, shape, in_hbm in types:
        if in_hbm:
            size = _BYTES.get(dt, 4)
            for d in shape:
                size *= d
            n += size
    return n


def roofline_share(red: Reduction, pattern, ops_of, peaks) -> Optional[float]:
    """A kernel's share of its roofline, in percent: over every call found,
    the least time the chip could take, the larger of its operations
    (``ops_of(operand shapes)``) over peak FLOP/s and the bytes of its
    HBM-resident operands and results over peak HBM bandwidth, divided by
    the calls' summed time.  Operands the compiler placed in on-chip
    memory cost no HBM traffic and are not counted (no VMEM bandwidth is
    published), so the share is a lower bound.  None when the kernel is
    not in the trace."""
    least = total = 0.0
    for text, (n, t) in red.ops.items():
        if not pattern.search(text):
            continue
        results, operands = custom_call_types(text)
        ops = ops_of([shape for _, shape, _ in operands])
        byts = hbm_bytes(results) + hbm_bytes(operands)
        least += n * max(ops / peaks["flops_per_s"],
                         byts / peaks["hbm_bytes_per_s"])
        total += t
    return 100.0 * least / total if total > 0 else None
