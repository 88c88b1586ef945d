"""Run the GFlowNet main path once on the TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the paths that exist across chips

One chip, at the paper's bitseq width (n=120, k=8: a 3-layer, width-64,
8-head decode transformer over 15 words and 3840 forward actions):

- kernels: every main-path Pallas kernel against its jnp reference on the
  same random inputs, the reference at "highest" matmul precision;
- train:   ``bitseq_tb`` (TB) through ``TrainLoop`` at the paper's 16 envs
  and at 256, in ``python`` and ``scan`` mode;
- subtb:   ``hypergrid_subtb`` on the paper's 20^4 grid;
- serve:   bitseq requests through the ``Scheduler``/``SamplingEngine``
  (several seeds, a tempered request, a ``reward_beta`` request), each
  checked bitwise against ``forward_rollout`` with the request's key.

Four chips: ``bitseq_tb`` under ``data_parallel`` over a (4,) mesh against
the ``single`` plan on the same global batch, and a ``data_parallel`` lane
pool against a single-chip one.

Each phase prints its kernel count (``tpu_custom_call`` in the compiled
HLO), compile seconds, wall times (host clock around work that ends in
``block_until_ready``) and reference differences with their tolerances.  A
phase that fails is reported and the remaining phases still run, but the
script then exits 1.  Without a TPU it exits 2 before doing anything.  The
last line of a passing run is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

JAX's persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
points; when that is unset, to ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
BITSEQ_ENVS = (16, 256)        # the paper's num_envs, and a chip-filling one
TRAIN_STEPS = 20
WIDE_STEPS = 5
DP_GLOBAL_ENVS = 64
#: kernel-vs-reference tolerances, max |kernel - ref| on each output; the
#: kernels and the references both compute in float32 at full precision
TOL = {"decode_attention": 1e-4, "decode_step.y": 1e-4,
       "decode_step.log_pf": 1e-4, "decode_step.cache": 1e-5,
       "decode_step.action_score": 1e-4, "traj_logprob.per_step": 1e-4,
       "traj_logprob.total": 1e-3, "subtb_loss": 1e-4}
#: data_parallel vs single per-step losses: float reassociation of the
#: batch reduction only (the tolerance tests/test_plan.py holds on CPU)
DP_LOSS_RTOL, DP_LOSS_ATOL = 2e-3, 1e-4
#: ... and per-step mean log-rewards of the same sampled batches
DP_LOG_R_RTOL, DP_LOG_R_ATOL = 1e-5, 1e-6


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class PhaseFailed(AssertionError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def kernel_count(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def aot(fn, *args):
    """Compile ``fn`` for ``args``; returns (compiled, seconds, kernels)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0, kernel_count(compiled)


# ---------------------------------------------------------------------------
# phase: kernels against their jnp references
# ---------------------------------------------------------------------------

def phase_kernels(batch: int = 256) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import recipes
    from repro.kernels import ref
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                decode_step_pallas)
    from repro.kernels.subtb_loss import subtb_loss_pallas
    from repro.kernels.traj_logprob import traj_logprob_pallas
    from repro.nn.transformer import decoder_stacked_weights

    recipe = recipes.get("bitseq_tb")
    env = recipe.make_env()
    policy = recipe.make_policy(env)
    params = policy.init(jax.random.PRNGKey(SEED))
    w = decoder_stacked_weights(params["decoder"])
    L, D, H = w["q_w"].shape[0], w["q_w"].shape[1], 8   # recipe: 8 heads
    B, C, T, A = batch, env.L + 1, env.L, env.action_dim
    base = jax.random.PRNGKey(SEED + 1)
    ks = (jax.random.fold_in(base, i) for i in itertools.count())
    nrm = lambda *s: jax.random.normal(next(ks), s, jnp.float32)

    def compare(name, kernel_fn, ref_fn, *args):
        compiled, secs, kernels = aot(kernel_fn, *args)
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        check(kernels >= 1, f"{name}: no tpu_custom_call in compiled HLO")
        say("kernels", f"{name} B={B} tpu_custom_call={kernels} "
                       f"compile_s={secs:.3f} run_s={run_s:.6f}")
        return got, want

    def diff(name, got, want, tol_key=None):
        d = float(np.max(np.abs(np.asarray(got, np.float64)
                                - np.asarray(want, np.float64))))
        tol = TOL[tol_key or name]
        say("kernels", f"{name} max_abs_diff={d:.3e} tol={tol:.0e}")
        check(np.isfinite(d) and d <= tol, f"{name}: {d} > {tol}")

    # decode attention over the bitseq cache (the rollout's cached query)
    hd = D // H
    q, k, v = nrm(B, H, hd), nrm(B, C, H, hd), nrm(B, C, H, hd)
    kv_valid = jax.random.randint(next(ks), (B,), 0, C + 1)
    got, want = compare("decode_attention", decode_attention_pallas,
                        ref.ref_decode_attention, q, k, v, kv_valid)
    diff("decode_attention", got, want)

    # fused decode step with the recipe's real decoder weights
    lengths = jax.random.randint(next(ks), (B,), 0, C - 1)
    slot = lengths + 1
    gumbel = jax.random.gumbel(next(ks), (B, A))
    mask = jax.random.bernoulli(next(ks), 0.5, (B, A)).at[:, 0].set(True)
    temp = jax.random.uniform(next(ks), (B,), minval=0.5, maxval=2.0)
    w_out = params["readout"]["w"][:, :A]
    b_out = params["readout"]["b"][:A]
    step = functools.partial(decode_step_pallas, num_heads=H)
    ref_step = functools.partial(ref.ref_decode_step, num_heads=H)
    args = (w, nrm(B, D), nrm(L, B, C, D), nrm(L, B, C, D), lengths, slot,
            gumbel, mask, w_out, b_out, temp)
    got, want = compare("decode_step", step, ref_step, *args)
    diff("decode_step.y", got[2], want[2])
    diff("decode_step.new_k", got[3], want[3], "decode_step.cache")
    diff("decode_step.new_v", got[4], want[4], "decode_step.cache")
    # the reference's masked log-softmax and Gumbel score at every action:
    # the kernel's draw must be a maximizer of the reference score (exact
    # ties aside, the same action), and its log_pf the reference log-prob
    # of that action
    with jax.default_matmul_precision("highest"):
        logits = (want[2] @ w_out + b_out) * temp[:, None]
    ml = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    logp = ml - jax.scipy.special.logsumexp(ml, axis=-1, keepdims=True)
    score = logp + gumbel
    act = got[0]
    at = lambda x: jnp.take_along_axis(x, act[:, None], axis=-1)[:, 0]
    same = int(jnp.sum(act == want[0]))
    say("kernels", f"decode_step.action same_as_ref={same}/{B}")
    check(bool(jnp.all(jnp.take_along_axis(mask, act[:, None], -1))),
          "decode_step: kernel drew an illegal action")
    diff("decode_step.action_score", at(score), jnp.max(score, axis=-1))
    diff("decode_step.log_pf", got[1], at(logp))

    # trajectory log-probs, forward (3840 actions) and backward (15)
    for direction, width in (("fwd", A), ("bwd", env.backward_action_dim)):
        logits = nrm(B, T, width) * 3.0
        actions = jax.random.randint(next(ks), (B, T), 0, width)
        legal = jnp.logical_or(
            jax.random.bernoulli(next(ks), 0.5, (B, T, width)),
            jax.nn.one_hot(actions, width, dtype=bool))
        valid = (jnp.arange(T)[None, :]
                 < jax.random.randint(next(ks), (B, 1), 1, T + 1))
        got, want = compare(f"traj_logprob.{direction}", traj_logprob_pallas,
                            ref.ref_traj_logprob, logits, actions, legal,
                            valid)
        diff(f"traj_logprob.{direction}.per_step", got[1], want[1],
             "traj_logprob.per_step")
        diff(f"traj_logprob.{direction}.total", got[0], want[0],
             "traj_logprob.total")

    # SubTB over 20^4 hypergrid trajectories (77 steps, 78 states)
    phi = nrm(B, 78)
    length = jax.random.randint(next(ks), (B,), 1, 78)
    got, want = compare("subtb_loss",
                        functools.partial(subtb_loss_pallas, lam=0.9),
                        functools.partial(ref.ref_subtb, lam=0.9), phi,
                        length)
    diff("subtb_loss", got, want)


# ---------------------------------------------------------------------------
# phases: training
# ---------------------------------------------------------------------------

def make_loop(name: str, num_envs: int, iterations: int, plan="single",
              devices=None, env_kw=None):
    """The recipe's env, policy and config, as ``repro.run.run_recipe``
    resolves them, in a ``TrainLoop`` on the given plan."""
    import jax

    from repro import recipes
    from repro.algo import TrainLoop, make_plan
    from repro.recipes.base import RunOptions

    recipe = recipes.get(name)
    opts = RunOptions(seed=SEED, iterations=iterations, num_envs=num_envs)
    env = recipe.make_env(**(env_kw or {}))
    env_params = env.init(jax.random.PRNGKey(SEED))
    policy = recipe.make_policy(env)
    cfg = recipe.make_config(env, opts)
    return TrainLoop(env, env_params, policy, cfg,
                     plan=make_plan(plan, devices=devices,
                                    num_envs=num_envs))


def train_python(phase: str, tag: str, loop, steps: int, states=None):
    """Compile the step once ahead of time (kernel count, compile seconds),
    then drive ``TrainLoop.run(mode="python")`` with a per-step callback
    that blocks on the loss.  Returns the per-step losses and mean
    log-rewards; a list passed as ``states`` receives a host copy of every
    post-step ``TrainState``."""
    import jax
    import numpy as np

    key = jax.random.PRNGKey(SEED + 1)
    _, secs, kernels = aot(loop.step_fn, loop.init(key))
    losses, log_r, walls = [], [], []
    last = [time.perf_counter()]

    def on_step(it, train_state, metrics, batch):
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        now = time.perf_counter()
        walls.append(now - last[0])
        log_r.append(float(metrics["mean_log_reward"]))
        if states is not None:
            # the python driver donates its carry: copy before the next step
            states.append(jax.device_get(train_state))
        last[0] = time.perf_counter()

    loop.run(key, steps, mode="python", callback=on_step, callback_every=1)
    steady = walls[1:] or walls
    say(phase, f"{tag} mode=python tpu_custom_call={kernels} "
               f"compile_s={secs:.3f} first_step_s={walls[0]:.3f} "
               f"step_s_median={float(np.median(steady)):.6f} "
               f"step_s_min={min(steady):.6f} steps={steps} "
               f"loss_first={losses[0]:.6g} loss_last={losses[-1]:.6g}")
    check(kernels >= 1, f"{tag}: no tpu_custom_call in the train step")
    check(np.all(np.isfinite(losses)), f"{tag}: non-finite loss {losses}")
    return np.asarray(losses), np.asarray(log_r)


def train_scan(phase: str, tag: str, loop, steps: int) -> None:
    import jax
    import numpy as np

    t0 = time.perf_counter()
    _, (metrics, _) = loop.run(jax.random.PRNGKey(SEED + 1), steps,
                               mode="scan")
    losses = np.asarray(jax.block_until_ready(metrics["loss"]))
    say(phase, f"{tag} mode=scan steps={steps} "
               f"wall_s_incl_compile={time.perf_counter() - t0:.3f} "
               f"loss_first={losses[0]:.6g} loss_last={losses[-1]:.6g}")
    check(np.all(np.isfinite(losses)), f"{tag}: non-finite scan loss")


def phase_train(envs=BITSEQ_ENVS, steps=(TRAIN_STEPS, WIDE_STEPS),
                env_kw=None) -> None:
    for num_envs, n in zip(envs, steps):
        loop = make_loop("bitseq_tb", num_envs, n, env_kw=env_kw)
        tag = f"bitseq_tb n={loop.env.n} k={loop.env.k} num_envs={num_envs}"
        train_python("train", tag, loop, n)
        train_scan("train", tag, loop, n)


def phase_subtb(side: int = 20, steps: int = 10) -> None:
    loop = make_loop("hypergrid_subtb", 16, steps,
                     env_kw={"dim": 4, "side": side})
    train_python("subtb", f"hypergrid_subtb dim=4 side={side} num_envs=16",
                 loop, steps)


# ---------------------------------------------------------------------------
# phase: serving
# ---------------------------------------------------------------------------

#: (seed, num_samples, logit_temp, reward_beta) — two waves of distinct
#: requests, so the second wave is served warm and never from dedup
REQUESTS = ([(11, 5, 1.0, 1.0), (12, 16, 1.0, 1.0), (13, 3, 0.7, 1.0),
             (14, 8, 1.0, 2.0)],
            [(21, 7, 1.0, 1.0), (22, 2, 1.0, 1.0), (23, 16, 0.5, 1.0),
             (24, 4, 1.0, 0.5)])


@functools.lru_cache(maxsize=None)
def _reference_sampler(env_overrides: tuple):
    """``forward_rollout`` over the env and fresh policy the scheduler
    builds for an engine key, rebuilt independently of the engine."""
    import jax
    import jax.numpy as jnp

    from repro import recipes
    from repro.core.rollout import forward_rollout
    from repro.envs.registry import get_env, make_env

    env = make_env("bitseq", **dict(env_overrides))
    env_params = env.init(jax.random.PRNGKey(SEED))
    policy = recipes.get(get_env("bitseq").recipe).make_policy(env)
    params = policy.init(jax.random.PRNGKey(SEED))

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def sample(key, num, temp):
        pol = policy
        if temp != 1.0:
            # a tempered request scales every row's forward logits, as
            # the engine's per-lane logit_temp does
            def tempered(*a, **kw):
                kw["logit_temp"] = jnp.full((num,), temp, jnp.float32)
                return policy.sample_cached(*a, **kw)
            pol = policy._replace(sample_cached=tempered)
        b = forward_rollout(key, env, env_params, pol, params, num)
        return b.obs[-1], b.log_reward

    return sample


def serve_wave(phase: str, tag: str, sched, wave, env_overrides=()):
    """Submit one wave, drain it, and check every request against the
    reference.  Returns ``{seed: (samples, log_rewards)}``."""
    import jax
    import numpy as np

    from repro.serve import SampleRequest

    ids = {}
    for seed, num, temp, beta in wave:
        req = SampleRequest(env="bitseq", num_samples=num, seed=seed,
                            logit_temp=temp, reward_beta=beta,
                            overrides=dict(env_overrides))
        ids[sched.submit(req)] = (seed, num, temp, beta)
    t0 = time.perf_counter()
    results = sched.run()
    wall = time.perf_counter() - t0
    check(set(results) == set(ids), f"{tag}: missing results")
    sample = _reference_sampler(tuple(env_overrides))
    out = {}
    for rid, (seed, num, temp, beta) in ids.items():
        res = results[rid]
        got = np.asarray(res.samples)
        log_r = np.asarray(res.log_rewards, np.float32)
        ref_obs, ref_lr = jax.device_get(
            sample(jax.random.PRNGKey(seed), num, temp))
        same = np.array_equal(got, ref_obs)
        same_r = np.array_equal(log_r, np.float32(beta) * ref_lr)
        say(phase, f"{tag} request seed={seed} num_samples={num} "
                   f"logit_temp={temp} reward_beta={beta} "
                   f"latency_s={res.latency_s:.6f} "
                   f"samples_equal_forward_rollout={same} "
                   f"log_rewards_equal={same_r}")
        check(same and same_r, f"{tag}: request seed={seed} differs from "
                               "forward_rollout")
        out[seed] = (got, log_r)
    say(phase, f"{tag} wave requests={len(wave)} "
               f"samples={sum(w[1] for w in wave)} run_wall_s={wall:.6f}")
    return out


def bitseq_engine(sched, env_overrides=()):
    from repro.serve import SampleRequest
    return sched.engine_for(SampleRequest(env="bitseq",
                                          overrides=dict(env_overrides)))


def engine_kernels(phase: str, tag: str, engine) -> None:
    _, secs, kernels = aot(engine._jstep, engine.lane)
    say(phase, f"{tag} engine block tpu_custom_call={kernels} "
               f"compile_s={secs:.3f} lanes={engine.num_lanes} "
               f"steps_per_sync={engine.steps_per_sync}")
    check(kernels >= 1, f"{tag}: no tpu_custom_call in the engine block")


def phase_serve(env_overrides=(), lanes: int = 16) -> None:
    from repro.serve import Scheduler

    sched = Scheduler(num_lanes=lanes, init_seed=SEED)
    tag = "bitseq n=120 k=8" if not env_overrides else f"bitseq {env_overrides}"
    for i, wave in enumerate(REQUESTS):
        serve_wave("serve", f"{tag} wave={i}{' (compiles)' if i == 0 else ''}",
                   sched, wave, env_overrides)
    engine_kernels("serve", tag, bitseq_engine(sched, env_overrides))


# ---------------------------------------------------------------------------
# phases: four chips
# ---------------------------------------------------------------------------

def _mesh_devices(plan) -> int:
    return len({d.id for d in plan.mesh.devices.flat})


def phase_dp_train(chips: int, steps: int = 10,
                   global_envs: int = DP_GLOBAL_ENVS, env_kw=None) -> None:
    import jax
    import numpy as np

    from repro.core.types import replace

    dp = make_loop("bitseq_tb", global_envs, steps, plan="data_parallel",
                   devices=chips, env_kw=env_kw)
    n = _mesh_devices(dp.plan)
    say("dp_train", f"data_parallel mesh_shape={dp.plan.mesh_shape} "
                    f"distinct_devices={n}")
    check(n == chips, f"mesh spans {n} devices, not {chips}")
    tag = f"bitseq_tb num_envs={global_envs}"
    single = make_loop("bitseq_tb", global_envs, steps, env_kw=env_kw)
    states = []
    loss_1, log_r_1 = train_python("dp_train", f"{tag} plan=single", single,
                                   steps, states)
    train_python("dp_train", f"{tag} plan=data_parallel", dp, steps)
    # Free-running, the two runs part once one reassociated update flips
    # one of the 64 x 15 Gumbel-max draws over 3840 actions; from then on
    # they sample different batches.  Parity is therefore checked per step:
    # one data_parallel step from each of single's pre-step states.
    init = single.init(jax.random.PRNGKey(SEED + 1))
    step = jax.jit(dp.step_fn)
    loss_dp, log_r_dp = [], []
    for ts in [init.train] + states[:-1]:
        _, (m, _) = step(dp.plan.prepare_state(replace(init, train=ts)))
        loss_dp.append(float(m["loss"]))
        log_r_dp.append(float(m["mean_log_reward"]))
    loss_dp, log_r_dp = np.asarray(loss_dp), np.asarray(log_r_dp)
    rel = np.abs(loss_dp - loss_1) / (DP_LOSS_ATOL
                                      + DP_LOSS_RTOL * np.abs(loss_1))
    say("dp_train", f"{tag} per-step loss from the same state "
                    f"max_abs_diff={float(np.max(np.abs(loss_dp - loss_1))):.3e}"
                    f" worst_over_tol={float(np.max(rel)):.3e} "
                    f"rtol={DP_LOSS_RTOL} atol={DP_LOSS_ATOL}")
    rel_r = np.abs(log_r_dp - log_r_1) / (DP_LOG_R_ATOL
                                          + DP_LOG_R_RTOL * np.abs(log_r_1))
    say("dp_train", f"{tag} per-step mean_log_reward from the same state "
                    f"max_abs_diff={float(np.max(np.abs(log_r_dp - log_r_1))):.3e}"
                    f" worst_over_tol={float(np.max(rel_r)):.3e} "
                    f"rtol={DP_LOG_R_RTOL} atol={DP_LOG_R_ATOL}")
    check(np.all(rel <= 1.0), "data_parallel losses left the tolerance")
    check(np.all(rel_r <= 1.0), "data_parallel sampled other batches")


def phase_dp_serve(chips: int, env_overrides=(), lanes: int = 16) -> None:
    import numpy as np

    from repro.serve import Scheduler

    wave = REQUESTS[0] + REQUESTS[1]
    single = Scheduler(num_lanes=lanes, init_seed=SEED)
    pooled = Scheduler(num_lanes=lanes, init_seed=SEED,
                       plan="data_parallel", devices=chips)
    a = serve_wave("dp_serve", "single-chip pool", single, wave,
                   env_overrides)
    b = serve_wave("dp_serve", f"data_parallel pool over {chips}", pooled,
                   wave, env_overrides)
    engine = bitseq_engine(pooled, env_overrides)
    n = _mesh_devices(engine.plan)
    same = all(np.array_equal(a[s][0], b[s][0])
               and np.array_equal(a[s][1], b[s][1]) for s in a)
    say("dp_serve", f"lane pool distinct_devices={n} lanes={engine.num_lanes}"
                    f" samples_bitwise_equal={same}")
    check(n == chips, f"lane pool spans {n} devices, not {chips}")
    check(same, "sharded lane pool differs from the single-chip pool")
    engine_kernels("dp_serve", f"data_parallel pool over {chips}", engine)


# ---------------------------------------------------------------------------

def run_phases(phases) -> bool:
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            say(name, f"passed in {time.perf_counter() - t0:.1f} s")
        except Exception:
            ok = False
            say(name, f"FAILED after {time.perf_counter() - t0:.1f} s")
            traceback.print_exc(file=sys.stdout)
            sys.stdout.flush()
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path (default); 4: only "
                         "data_parallel training and the sharded lane pool")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU visible to JAX (platform {platform!r}); "
              "refusing to run anywhere else", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (fails here outside a checkout)

    cache = Path(jax.config.jax_compilation_cache_dir)
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    say("device", f"platform={platform} kind={devices[0].device_kind} "
                  f"count={len(devices)} jax={jax.__version__} "
                  f"cache_dir={cache} cache_entries_at_start={warm}")
    if args.chips == 1:
        phases = [("kernels", phase_kernels), ("train", phase_train),
                  ("subtb", phase_subtb), ("serve", phase_serve)]
    else:
        phases = [("dp_train", lambda: phase_dp_train(args.chips)),
                  ("dp_serve", lambda: phase_dp_serve(args.chips))]
    if not run_phases(phases):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
