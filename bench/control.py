"""Readings that set a cell's limits: the program's numbers on sound runs
(the lower readings), and those of its control and planted faults (the
upper readings), over many seeds in one process.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 4]

Training cells drive the program's set-up steps (no window) per seed and
compare them with the reference, then put the reference in the
program's place:

- ``control``: the reference computed in bfloat16, the nearest precision
  below the configuration's float32;
- ``half``: the loss taken over half the batch;
- ``altered``: one sampled action altered after it was drawn.

A step that returns its state unchanged reads 1 on ``grad`` and
``update`` by their definition, and needs no run.  Serving cells serve a
short window at the cell's load per seed and read, beside the program's
numbers, those of a bfloat16 reference in the program's place.  Prints
one JSON line per seed.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import compare, harness  # noqa: E402


def train_readings(ctx, driver) -> dict:
    import jax.numpy as jnp
    import numpy as np
    cfg, ref = ctx.config, ctx.ref
    s = driver.setup(ctx)
    del s["state"], s["step"]
    p0, batches = s["params0"], s["batches"]
    want = driver.reference(cfg, ref, p0, batches)
    out = {"sound": compare.train_numbers(s["prog"], want, p0)[0]}
    bf16 = driver.reference(cfg, ref, p0, batches, dt=jnp.bfloat16)
    out["control"] = compare.train_numbers(driver.as_program(bf16), want,
                                           p0)[0]
    B = batches[0]["actions"].shape[1]
    half = driver.reference(cfg, ref, p0, batches, keep=B // 2)
    out["half"] = compare.train_numbers(driver.as_program(half), want,
                                        p0)[0]
    # the first trajectory's first action, altered after it was drawn:
    # the program would report the drawn action's log-prob beside it
    altered = list(batches)
    a = np.array(batches[0]["actions"])
    a[0, 0] = a[0, 0] ^ 1 if a[0, 0] ^ 1 < ref.num_actions(cfg) \
        else a[0, 0] - 1
    altered[0] = ref.train_batch(cfg, {"actions": a})
    want_alt = driver.reference(cfg, ref, p0, altered)
    out["altered"] = compare.train_numbers(driver.as_program(want),
                                           want_alt, p0)[0]
    return out


def serve_readings(ctx, driver) -> dict:
    import jax.numpy as jnp
    w = driver.serve(ctx)
    numbers, ctl = driver.check(
        ctx.config, ctx.ref, w["params"], w["sched"], w["results"],
        ctx.traffic["check_samples"], ctx.seed, ctx.traffic["reward_beta"],
        control_dt=jnp.bfloat16)
    numbers["missing"] = float(len(w["missing"]))
    return {"sound": numbers, "control": ctl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    import bench.run as run
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.config_of(cell["config"])
    traffic = harness.traffic_of(cell["traffic"])
    try:
        devices = harness.require_devices(cell["chips"])
    except harness.BenchError as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 2
    harness.setup_compile_cache()
    ref = harness.reference_of(cell["config"])
    driver = harness.kind_driver(traffic["kind"])
    readings = train_readings if traffic["kind"] == "train" \
        else serve_readings
    for seed in args.seeds:
        ctx = run.Context(cell, cfg, traffic, ref, seed, args.seconds, False,
                          devices)
        print(json.dumps({"seed": seed, **readings(ctx, driver)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
