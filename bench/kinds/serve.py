"""Sampling-service cells: open-loop traffic into ``ServeFront`` ->
``Scheduler`` -> ``SamplingEngine``, serving the benchmark's weights from
a checkpoint the way a trained sampler is served.

Traffic (``bench/traffic/<mix>.json``):

- ``rate_per_s`` requests per second, as an open loop: the window's
  ``round(rate * seconds)`` arrival times are uniform order statistics
  over the window (a Poisson process conditioned on its count);
- ``num_samples`` per request log-uniform in ``[samples_min,
  samples_max]``; a ``temp_share`` of requests at a ``logit_temp``
  uniform in ``[temp_min, temp_max]``, the rest at 1; ``clients`` ids
  with Zipf(``zipf_s``) shares; ``reward_beta``;
- the multiset of gaps, sizes, temperatures and clients is drawn once from
  ``work_seed``, so every run serves the same work; ``--seed`` orders it
  and draws each request's unique seed (the dedup cache is never hit);
- ``num_lanes``, ``max_queue`` of the front; ``warmup`` requests before
  the window; ``check_samples`` served samples, drawn from the seed with
  the largest finished requests among them, are compared with the
  reference;
  ``drain_timeout_s`` after the window's close for answers still due.

Each request is timed from its due time to the moment its future
completes.  A request that fails or is refused counts in ``failed`` and
as missing every latency limit; one that never comes, or whose samples
are wrong, makes the run incorrect.
"""
from __future__ import annotations

import math
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, harness
from bench.kinds.train import init_weights, shapes_of


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    """The window's requests: arrival offsets (s), sizes, temperatures,
    client ids and request seeds, plus the warm-up requests' seeds."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    base = np.random.default_rng(traffic["work_seed"])
    gaps = base.exponential(size=n + 1)
    lo, hi = math.log(traffic["samples_min"]), math.log(
        traffic["samples_max"] + 1)
    sizes = np.minimum(np.floor(np.exp(base.uniform(lo, hi, n))),
                       traffic["samples_max"]).astype(int)
    temps = np.where(base.uniform(size=n) < traffic["temp_share"],
                     base.uniform(traffic["temp_min"], traffic["temp_max"],
                                  n), 1.0)
    shares = 1.0 / np.arange(1, traffic["clients"] + 1) ** traffic["zipf_s"]
    clients = base.choice(traffic["clients"], n, p=shares / shares.sum())
    run = np.random.default_rng(seed)
    gaps = run.permutation(gaps)
    times = np.cumsum(gaps[:n]) / gaps.sum() * seconds
    warm = len(traffic["warmup"])
    first = int(run.integers(0, 2 ** 31 - 1 - n - warm))
    seeds = first + run.permutation(n + warm)
    return {"times": times, "sizes": run.permutation(sizes),
            "temps": run.permutation(temps),
            "clients": run.permutation(clients),
            "seeds": seeds[warm:], "warm_seeds": seeds[:warm]}


class _Front:
    """The program's front over a checkpoint of the benchmark's weights."""

    def __init__(self, cfg, traffic, params, ckpt_dir):
        from repro.algo.loop import LoopState
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.types import TrainState
        from repro.serve import Scheduler, ServeFront

        CheckpointManager(ckpt_dir).save(0, LoopState(
            train=TrainState(params=params, opt_state=(),
                             step=jnp.zeros((), jnp.int32),
                             key=jnp.zeros((2,), jnp.uint32)), sampler=()))
        self.front = ServeFront(
            Scheduler(num_lanes=traffic["num_lanes"]),
            max_queue=traffic["max_queue"], checkpoint_poll_s=None,
            autosize=False)
        self.cfg, self.traffic, self.ckpt = cfg, traffic, ckpt_dir

    def request(self, n, seed, temp):
        from repro.serve import SampleRequest
        return SampleRequest(env=self.cfg["serve_env"], num_samples=int(n),
                             seed=int(seed), logit_temp=float(temp),
                             reward_beta=float(self.traffic["reward_beta"]),
                             overrides=dict(self.cfg["recipe_env"]),
                             checkpoint=self.ckpt, step=0)

    def engine(self):
        for r in self.front._runners.values():
            return r.engine
        return None


def warm_drain_slices(eng):
    """The engine's drain fetches the first ``count`` rows of each packed
    output, and every new ``count`` compiles its own slice program.  Warm
    all of them (1..lanes) so none compiles inside the window."""
    shapes = jax.eval_shape(eng._jpack, eng.lane,
                            jnp.zeros((eng.num_lanes,), bool))
    for x in jax.tree_util.tree_leaves(shapes):
        z = jnp.zeros(x.shape, x.dtype)
        for k in range(1, eng.num_lanes + 1):
            np.asarray(z[:k])


def drive(front, sched, seconds, span=jax.profiler.TraceAnnotation):
    """The open loop: submit each request at its due time; returns the due
    and completion times, futures, and how late the generator ran."""
    n = len(sched["times"])
    done_t = [math.nan] * n
    futs, late = [None] * n, []
    errors = [None] * n
    t0 = time.perf_counter()
    due = t0 + sched["times"]
    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            with span("bench.sleep"):
                time.sleep(wait)
        late.append(time.perf_counter() - due[i])
        req = front.request(sched["sizes"][i], sched["seeds"][i],
                            sched["temps"][i])
        with span("bench.submit"):
            try:
                f = front.front.submit(req,
                                       client=f"c{sched['clients'][i]}")
            except Exception as e:  # a refusal is an answer: count it
                errors[i] = e
                done_t[i] = time.perf_counter()
                continue
        f.add_done_callback(
            lambda _f, i=i: done_t.__setitem__(i, time.perf_counter()))
        futs[i] = f
    return {"t0": t0, "due": due, "done_t": done_t, "futs": futs,
            "late": late, "errors": errors}


def collect(run, seconds, timeout_s):
    """Wait for every answer up to ``timeout_s`` past the window's close;
    returns results, the failed and the missing request indices."""
    close = run["t0"] + seconds
    results, failed, missing = {}, [], []
    for i, f in enumerate(run["futs"]):
        if f is None:
            failed.append(i)
            continue
        try:
            results[i] = f.result(
                timeout=max(0.0, close + timeout_s - time.perf_counter()))
        except TimeoutError:
            missing.append(i)
        except Exception:
            failed.append(i)
    return results, failed, missing


def check(cfg, ref, params, sched, results, budget, seed, beta,
          control_dt=None):
    """Compare finished requests with the reference: requests whose sample
    count or shape is wrong (all of them), and over a sample of
    ``budget`` served samples drawn from the seed, always holding the
    largest finished requests, the widest Gumbel-max gap of a served word
    and the widest log-reward gap.  With ``control_dt`` also returns the
    control's numbers: the widest gap of the words a reference in that
    dtype would serve, and the widest gap of its log-rewards."""
    L, m, A = ref.sizes(cfg)
    ok = {}
    for i, res in results.items():
        samples = np.asarray(res.samples, np.int32)
        if samples.shape == (int(sched["sizes"][i]), L) and \
                len(res.log_rewards) == samples.shape[0]:
            ok[i] = (samples, np.asarray(res.log_rewards, np.float64))
    wrong = len(results) - len(ok)
    idx = sorted(ok)
    if not idx:
        return ({"gap": math.inf, "log_r": math.inf, "wrong": float(wrong)},
                None)
    rng = np.random.default_rng([seed, 1])
    largest = sorted(idx, key=lambda i: -sched["sizes"][i])[:4]
    rows = []
    for i in dict.fromkeys(largest + [idx[j] for j in
                                      rng.permutation(len(idx))]):
        rows += [(i, j) for j in range(len(ok[i][0]))]
        if len(rows) >= budget:
            break
    rows = (rows * (budget // len(rows) + 1))[:budget]
    seeds = jnp.asarray([sched["seeds"][i] for i, _ in rows], jnp.int32)
    which = jnp.asarray([j for _, j in rows], jnp.int32)
    temps = jnp.asarray([sched["temps"][i] for i, _ in rows], jnp.float32)
    tokens = jnp.asarray(np.stack([ok[i][0][j] for i, j in rows]))
    served_lr = np.asarray([ok[i][1][j] for i, j in rows])
    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.vmap(lambda s, j: ref.gumbel_of(s, j, L, A)))(
            seeds, which)
        gap, cgap = jax.jit(lambda p, t, g, tp: ref.follow_served(
            cfg, p, t, g, tp, control_dt=control_dt))(params, tokens, g,
                                                      temps)
        want = np.asarray(ref.log_reward(cfg, tokens,
                                         beta=cfg["env"]["beta"] * beta))
    numbers = {"gap": float(jnp.max(gap)),
               "log_r": compare.max_abs_gap(served_lr, want),
               "wrong": float(wrong)}
    if control_dt is None:
        return numbers, None
    low = ref.log_reward(cfg, tokens, beta=cfg["env"]["beta"] * beta,
                         dt=control_dt)
    return numbers, {"gap": float(jnp.max(cgap)),
                     "log_r": compare.max_abs_gap(np.asarray(
                         low.astype(jnp.float32)), want)}


def serve(ctx):
    """Set up the front from the seed, warm it, and serve one window;
    returns the schedule, the answers and the weights for the reference."""
    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.ref
    seconds = ctx.window_seconds(traffic)
    sched = schedule(traffic, ctx.seed, seconds)
    want = ref.param_shapes(cfg)
    key = jax.random.PRNGKey(ctx.jax_seed)
    marks = [("start", time.perf_counter())]
    params = jax.jit(lambda k: init_weights(k, want))(key)
    ckpt = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        front = _Front(cfg, traffic, params, ckpt)
        params = jax.device_get(params)
        marks.append(("weights and checkpoint", time.perf_counter()))
        # warm-up: one request of each listed (size, temperature), which
        # builds the engine and compiles its block, refill and drain
        warm = [front.front.submit(front.request(n, s, t))
                for (n, t), s in zip(traffic["warmup"], sched["warm_seeds"])]
        for f in warm:
            f.result(timeout=600)
        marks.append(("warm-up requests", time.perf_counter()))
        eng = front.engine()
        warm_drain_slices(eng)
        marks.append(("drain slices", time.perf_counter()))
        got = shapes_of(jax.device_get(eng._policy_params))
        if got != want:
            raise harness.BenchError(f"the served parameter shapes {got} "
                                     f"are not the configuration's {want}")
        ctx.mark_setup_done()
        ctx.note("set-up: " + ", ".join(
            f"{name} {b - a:.3f} s" for (_, a), (name, b) in
            zip(marks, marks[1:])) + f"; in all {ctx.setup_s:.3f} s")

        compiles0 = ctx.compiles.count
        with ctx.tracing():
            with jax.profiler.TraceAnnotation("bench.window"):
                r = drive(front, sched, seconds)
                if ctx.traced:
                    # the traced window ends at the close; answers still
                    # due are collected after it
                    time.sleep(max(0.0, r["t0"] + seconds
                                   - time.perf_counter()))
        results, failed, missing = collect(r, seconds,
                                           traffic["drain_timeout_s"])
        compiles = ctx.compiles.count - compiles0
        front.front.shutdown(drain=True, timeout=60)
        memory = harness.memory_peak(ctx.devices)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    return {"sched": sched, "seconds": seconds, "drive": r,
            "results": results, "failed": failed, "missing": missing,
            "compiles": compiles, "memory": memory, "params": params,
            "lanes": eng.num_lanes}


def run(ctx):
    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.ref
    w = serve(ctx)
    sched, r, results = w["sched"], w["drive"], w["results"]
    seconds = w["seconds"]
    n = len(sched["times"])
    lat = [math.inf] * n
    engine_s, wait_s = [], []
    served = 0
    for i, res in results.items():
        lat[i] = r["done_t"][i] - r["due"][i]
        engine_s.append(res.latency_s)
        wait_s.append(lat[i] - res.latency_s)
        if r["done_t"][i] <= r["t0"] + seconds:
            served += int(sched["sizes"][i])
    late = np.asarray(r["late"])
    ctx.note(f"window: {n} requests due in {seconds} s, {len(results)} "
             f"answered, {len(w['failed'])} failed, {len(w['missing'])} "
             f"missing; generator lateness median "
             f"{np.median(late) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} "
             f"ms; {w['compiles']} compilations inside the window")
    numbers, _ = check(cfg, ref, w["params"], sched, results,
                       traffic["check_samples"], ctx.seed,
                       traffic["reward_beta"])
    numbers["missing"] = float(len(w["missing"]))
    return {
        "attempted": n, "failed": len(w["failed"]) + len(w["missing"]),
        "end_to_end": {"serve_samples_per_s": served / seconds},
        "host": {"latency_s": lat, "engine_s": engine_s, "wait_s": wait_s,
                 "late_s": list(late), "window_s": seconds,
                 "samples_served": served, "num_lanes": w["lanes"],
                 "compiles_in_window": w["compiles"]},
        "numbers": numbers, "memory_peak_bytes": w["memory"],
    }
