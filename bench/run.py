"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; everything it names is found
by file name (see ``bench/harness.py``).  The run

- refuses to start without as many TPU chips as the cell asks for (exit
  2, no result line);
- builds the system under test from the seed, warms up every shape the
  window uses (``setup_s``: process start to the first timed call), then
  measures for ``--seconds`` (with ``--trace 1`` a traced window of the
  traffic's ``trace_seconds``, reduced by ``bench/trace.py``);
- reads the device's peak memory, frees the program's state, and checks
  what the timed path produced against the configuration's plain
  reference (``bench/configs/<config>.py``);
- prints each compared number beside its limit on standard error, and as
  the last line of standard output one JSON object: ``correct``,
  ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
  or with ``--trace 1`` its per-layer metrics), ``device``, with
  ``--trace 1`` ``breakdown``, and ``checks`` last.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import compare, harness, trace  # noqa: E402


class Context:
    """What a kind driver (``bench/kinds/<kind>.py``) gets: the cell's
    files, the seed, the window, the devices, and the tracing switch."""

    def __init__(self, cell, config, traffic, ref, seed, seconds, traced,
                 devices):
        self.cell, self.config, self.traffic, self.ref = (cell, config,
                                                          traffic, ref)
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.devices = devices
        #: the seed folded into the 31 bits JAX's PRNGKey takes
        self.jax_seed = int(seed) % (2 ** 31 - 1)
        self.compiles = harness.CompileCounter()
        self.reduction = None
        self.setup_s = None

    def mark_setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    def window_seconds(self, traffic) -> float:
        if self.traced:
            return min(self.seconds, traffic["trace_seconds"])
        return self.seconds

    def note(self, msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def tracing(self):
        """Trace the window when ``--trace 1``; reduce the trace after."""
        if not self.traced:
            yield
            return
        import jax
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        try:
            self.reduction = trace.reduce_file(trace.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)


class RunView:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) gets."""

    def __init__(self, ctx, out, peaks):
        self.cell, self.config, self.traffic = (ctx.cell, ctx.config,
                                                ctx.traffic)
        self.host, self.peaks = out["host"], peaks
        self.reduction = ctx.reduction

    @staticmethod
    def flops(name: str):
        return harness.flops_module(name)


def measure(bench, cell, ctx, limits, peaks):
    """Drive the cell and build its result line and checks."""
    traffic = ctx.traffic
    out = harness.kind_driver(traffic["kind"]).run(ctx)
    correct, checks = compare.verdict(out["numbers"], limits)
    metrics = {}
    name = cell["name"]
    if not ctx.traced:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if harness.applies(m, name) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        view = RunView(ctx, out, peaks)
        for m in bench["per_layer"]:
            if harness.applies(m, name):
                v = harness.metric_reader(m["name"]).read(view)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = harness.device_doc(ctx.devices, out["memory_peak_bytes"])
    doc = {"correct": bool(correct), "attempted": int(out["attempted"]),
           "failed": int(out["failed"]), "metrics": metrics, "device": dev}
    if ctx.traced and ctx.reduction is not None:
        dev["busy_s"] = ctx.reduction.busy_s
        dev["window_s"] = ctx.reduction.window_s
        doc["breakdown"] = ctx.reduction.breakdown()
    return doc, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = harness.load_benchmark()
        cell = harness.find_cell(bench, args.workload)
        config = harness.config_of(cell["config"])
        traffic = harness.traffic_of(cell["traffic"])
        limits = harness.limits_of(cell["name"])
        devices = harness.require_devices(cell["chips"])
        peaks = harness.peaks_for(devices[0].device_kind)
        harness.setup_compile_cache()
        try:
            import repro  # noqa: F401  (the system under test)
        except ImportError as e:
            raise harness.BenchError(f"the program is not here: {e}")
        ref = harness.reference_of(cell["config"])
        ctx = Context(cell, config, traffic, ref, args.seed, args.seconds,
                      bool(args.trace), devices)
        doc, checks = measure(bench, cell, ctx, limits, peaks)
    except harness.BenchError as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    harness.emit(doc, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
