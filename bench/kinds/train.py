"""Training cells: the configuration's recipe in ``TrainLoop``'s own
python-mode step (``jax.jit(loop._step_with_eval, donate_argnums=0)``,
as ``TrainLoop.run(mode="python")`` builds it), driven with no host read
inside the window.

Set-up builds that one compiled step and its state from the seed (the
weights are the benchmark's, made on the device in one jitted call), and
drives it through its first three steps, which the reference follows.
The window then keeps calling the same step on the same state, as
``TrainLoop.run`` does, with no host read or block until the window's
time is up, and ends on a block on the last step.

Traffic parameters (``bench/traffic/<mix>.json``): ``num_envs``,
``trace_seconds`` (the traced window's length).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, harness, refops

CHECK_STEPS = 3


def init_weights(key, shapes):
    """The benchmark's weights for a parameter tree of ``shapes``: matrices
    N(0, 1/fan_in), norm scales 1 + N(0, 0.1^2), ``log_z`` 0, every other
    vector or table N(0, 0.1^2)."""
    is_shape = lambda x: isinstance(x, tuple)
    paths, tdef = jax.tree_util.tree_flatten_with_path(shapes,
                                                       is_leaf=is_shape)
    leaves = []
    for i, (path, shape) in enumerate(paths):
        k = jax.random.fold_in(key, i)
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "w":
            leaves.append(z / np.sqrt(shape[0]))
        elif name == "scale":
            leaves.append(1.0 + 0.1 * z)
        elif name == "log_z":
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append(0.1 * z)
    return jax.tree_util.tree_unflatten(tdef, leaves)


def shapes_of(tree):
    return jax.tree_util.tree_map(lambda x: tuple(x.shape), tree)


def build(cfg, traffic):
    """The program's env, policy and ``TrainLoop`` as the recipe resolves
    them; refuses a program that departs from the configuration."""
    from repro import recipes
    from repro.algo import TrainLoop
    from repro.recipes.base import RunOptions

    recipe = recipes.get(cfg["recipe"])
    env = recipe.make_env(**cfg["recipe_env"])
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipe.make_policy(env)
    opts = RunOptions(seed=0, iterations=cfg["iterations"],
                      num_envs=traffic["num_envs"])
    gcfg = recipe.make_config(env, opts)
    for k, v in cfg["objective"].items():
        if getattr(gcfg, k) != v:
            raise harness.BenchError(
                f"recipe {cfg['recipe']!r} sets {k}={getattr(gcfg, k)!r}, "
                f"the configuration states {v!r}")
    return env, env_params, policy, TrainLoop(env, env_params, policy, gcfg)


def adam_mu(opt_state):
    for s in opt_state:
        if hasattr(s, "mu"):
            return s.mu
    raise harness.BenchError("no Adam state with a first moment found")


def setup(ctx):
    """The compiled step and its state, built from the seed and driven
    through the first ``CHECK_STEPS`` steps; what the reference needs of
    those steps is copied to the host."""
    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.ref
    env, env_params, policy, loop = build(cfg, traffic)
    want = ref.param_shapes(cfg)
    got = shapes_of(jax.eval_shape(policy.init, jax.random.PRNGKey(0)))
    if got != want:
        raise harness.BenchError(f"the program's parameter shapes {got} are "
                                 f"not the configuration's {want}")
    key = jax.random.PRNGKey(ctx.jax_seed)

    def init(key):
        state = loop.init(jax.random.fold_in(key, 1))
        params = init_weights(jax.random.fold_in(key, 2), want)
        train = state.train.__class__(
            params=params, opt_state=loop.tx.init(params),
            step=state.train.step, key=state.train.key)
        return state.__class__(train=train, sampler=state.sampler,
                               metrics=state.metrics)

    state = jax.jit(init)(key)
    params0 = jax.device_get(state.train.params)
    step = jax.jit(loop._step_with_eval, donate_argnums=0)

    prog = {"losses": [], "log_pf": [], "log_r": [], "actions": []}
    for i in range(CHECK_STEPS):
        state, (metrics, batch) = step(state)
        prog["losses"].append(float(metrics["loss"]))
        prog["log_pf"].append(np.asarray(batch.log_pf_beh))
        prog["log_r"].append(np.asarray(batch.log_reward))
        prog["actions"].append(np.asarray(batch.actions))
        if i == 0:
            beta1 = 0.9     # the first moment after one step is (1-b1) g
            prog["grads"] = jax.tree_util.tree_map(
                lambda m: np.asarray(m) / (1 - beta1),
                jax.device_get(adam_mu(state.train.opt_state)))
    prog["params"] = jax.device_get(state.train.params)
    batches = [ref.train_batch(cfg, {"actions": a}) for a in prog["actions"]]
    prog["illegal"] = sum(b["illegal"] for b in batches)
    return {"state": state, "step": step, "prog": prog, "params0": params0,
            "batches": batches}


def reference(cfg, ref, params0, batches, dt=jnp.float32, keep=None):
    """The reference's first three steps on the program's batches, in
    ``dt`` (float32 at highest matmul precision, or the control's
    bfloat16); ``keep`` plants the fault 'half the batch left out'."""
    obj = cfg["objective"]
    with jax.default_matmul_precision("highest"):
        return refops.train_three_steps(
            ref.loss_fn(cfg, dt, keep), params0, batches, obj["lr"],
            obj["log_z_lr"], dt)


def as_program(out):
    """A reference run's outputs in the shape of the program's."""
    return {"losses": [s["loss"] for s in out["steps"]],
            "log_pf": [s["log_pf"] for s in out["steps"]],
            "log_r": [s["log_r"] for s in out["steps"]],
            "grads": out["grads"], "params": out["params"], "illegal": 0}


def run(ctx):
    traffic = ctx.traffic
    B = traffic["num_envs"]
    s = setup(ctx)
    state, step = s["state"], s["step"]
    ctx.mark_setup_done()

    # -- the window --------------------------------------------------------
    seconds = ctx.window_seconds(traffic)
    span = jax.profiler.TraceAnnotation
    dispatch, losses = [], []
    compiles0 = ctx.compiles.count
    with ctx.tracing():
        with span("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                t = time.perf_counter()
                with span("bench.step"):
                    state, (metrics, _) = step(state)
                dispatch.append(time.perf_counter() - t)
                losses.append(metrics["loss"])
            with span("bench.sync"):
                jax.block_until_ready(state)
            window_s = time.perf_counter() - t0
    compiles = ctx.compiles.count - compiles0
    n = len(losses)
    finite = np.isfinite(np.asarray(jnp.stack(losses)))
    ctx.note(f"window: {n} steps of {B} trajectories in {window_s:.6f} s, "
             f"{compiles} compilations inside the window")
    memory = harness.memory_peak(ctx.devices)
    del state, step, losses, metrics, s["state"], s["step"]

    # -- the reference, once the window has closed -------------------------
    want = reference(ctx.config, ctx.ref, s["params0"], s["batches"])
    numbers, detail = compare.train_numbers(s["prog"], want, s["params0"])
    ctx.note(f"worst leaves: grad {detail['grad_leaf']}, update "
             f"{detail['update_leaf']}; left out as nought to rounding: "
             f"{detail['left_out']}")

    rate = n * B / window_s
    return {
        "attempted": n, "failed": int(n - finite.sum()),
        "end_to_end": {"train_traj_per_s": rate},
        "host": {"dispatch_s": dispatch, "window_s": window_s,
                 "traj_per_s": rate, "steps": n, "num_envs": B,
                 "compiles_in_window": compiles},
        "numbers": numbers, "memory_peak_bytes": memory,
    }
