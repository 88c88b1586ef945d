"""Find a serving cell's knee: offer its traffic mix at several fixed
rates, one short open-loop window each, in one process on one chip, and
print per rate what was offered and served, the latency percentiles, and
the backlog (requests due but not answered) at the middle and at the
close of the window.  The knee is the highest rate whose backlog does not
grow from the middle to the close.

    python bench/sweep.py --workload bitseq120.serve_steady \\
        --rates 40 80 160 --seconds 6 --seed 1
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.config_of(cell["config"])
    traffic = harness.traffic_of(cell["traffic"])
    ref = harness.reference_of(cell["config"])
    try:
        harness.require_devices(cell["chips"])
    except harness.BenchError as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 2
    harness.setup_compile_cache()
    serve = harness.kind_driver(traffic["kind"])
    from bench.kinds.train import init_weights
    params = jax.jit(lambda k: init_weights(k, ref.param_shapes(cfg)))(
        jax.random.PRNGKey(args.seed))
    ckpt = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        front = serve._Front(cfg, traffic, params, ckpt)
        sched = serve.schedule(traffic, args.seed, 1.0)
        for (n, t), s in zip(traffic["warmup"], sched["warm_seeds"]):
            front.front.submit(front.request(n, s, t)).result(timeout=600)
        serve.warm_drain_slices(front.engine())
        for i, rate in enumerate(args.rates):
            tr = dict(traffic, rate_per_s=rate)
            sched = serve.schedule(tr, args.seed + 1000 * (i + 1),
                                   args.seconds)
            r = serve.drive(front, sched, args.seconds)
            results, failed, missing = serve.collect(
                r, args.seconds, tr["drain_timeout_s"])
            due, done = r["due"], r["done_t"]
            t0 = r["t0"]

            def backlog(at):
                return int(sum(1 for d, c in zip(due, done)
                               if d <= at and not c <= at))

            lat = [done[i] - due[i] for i in results]
            lat += [float("inf")] * (len(failed) + len(missing))
            served = sum(sched["sizes"][i] for i in results
                         if done[i] <= t0 + args.seconds)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(due),
                "answered": len(results), "failed": len(failed),
                "missing": len(missing),
                "samples_per_s": served / args.seconds,
                "offered_samples_per_s": float(sum(sched["sizes"]))
                / args.seconds,
                "p50_ms": harness.percentile(lat, 50) * 1e3,
                "p95_ms": harness.percentile(lat, 95) * 1e3,
                "backlog_mid": backlog(t0 + args.seconds / 2),
                "backlog_close": backlog(t0 + args.seconds),
                "late_max_ms": max(r["late"]) * 1e3}), flush=True)
            time.sleep(1.0)
        front.front.shutdown(drain=True, timeout=60)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
