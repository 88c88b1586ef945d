"""Compiled GFlowNet training — config, optimizer, loss, and back-compat
entry points.

``make_train_step`` builds one fully-jitted on-policy iteration:
rollout -> objective -> grad -> optimizer update.  The three seed drivers
(``train`` / ``train_compiled`` / ``train_vectorized``) survive only as
*deprecation shims* over :class:`repro.algo.TrainLoop` execution modes
(``python`` / ``scan`` / ``vmap_seeds``); new code should use ``TrainLoop``
directly, which additionally accepts pluggable samplers (replay, backward
replay, ...) and device-mesh execution plans (:mod:`repro.algo.plan`).
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..envs.base import Environment
from ..optim import adamw as optim
from .objectives import (OBJECTIVE_PARTS, OBJECTIVES, evaluate_trajectory,
                         shared_bank_engaged)
from .rollout import RolloutBatch
from .types import TrainState


class GFNConfig(NamedTuple):
    objective: str = "tb"
    num_envs: int = 16
    lr: float = 1e-3
    log_z_lr: Optional[float] = 1e-1
    weight_decay: float = 0.0
    max_grad_norm: Optional[float] = None
    subtb_lambda: float = 0.9
    exploration_eps: float = 0.0
    exploration_anneal_steps: int = 0
    stop_action: Optional[int] = None


def make_optimizer(cfg: GFNConfig):
    """Adam with a separate lr for the log_z leaf (paper Tables 3-7)."""
    lz_ratio = (cfg.log_z_lr / cfg.lr) if cfg.log_z_lr else 1.0
    parts = []
    if cfg.max_grad_norm is not None:
        parts.append(optim.clip_by_global_norm(cfg.max_grad_norm))
    parts.append(optim.scale_by_adam())
    if cfg.weight_decay:
        parts.append(optim.add_decayed_weights(cfg.weight_decay))
    parts.append(optim.scale_by_label(
        lambda name: "log_z" if "log_z" in name else "default",
        {"log_z": lz_ratio, "default": 1.0}))
    parts.append(optim.scale(-cfg.lr))
    return optim.chain(*parts)


def _make_eval_fn(env: Environment, policy_apply, cfg: GFNConfig):
    """The teacher-forced pass ``(params, batch) -> TrajEval``, with the
    shared K/V bank resolved once from the env and policy."""
    return functools.partial(
        evaluate_trajectory, policy_apply, stop_action=cfg.stop_action,
        shared_bank=shared_bank_engaged(env, policy_apply))


def make_loss_fn(env: Environment, policy_apply, cfg: GFNConfig):
    """Uniform loss over any registered objective: every entry in
    ``OBJECTIVES`` takes ``(ev, batch, params, cfg)``, so there is no
    per-objective dispatch here."""
    obj = OBJECTIVES[cfg.objective]
    eval_fn = _make_eval_fn(env, policy_apply, cfg)

    def loss_fn(params, batch: RolloutBatch):
        return obj(eval_fn(params, batch), batch, params, cfg)

    return loss_fn


def make_loss_parts_fn(env: Environment, policy_apply, cfg: GFNConfig):
    """The objective as additive ``(sum, weight)`` parts:
    ``loss == sum / max(weight, 1)``.

    Differentiating the sum (with the weight as aux) is what lets a
    data-parallel plan ``psum`` sums, weights, *and* gradients across
    shards before one global division — exactly the single-device loss and
    gradient, even when the normalizer is a data-dependent count
    (see :data:`repro.core.objectives.OBJECTIVE_PARTS`).
    """
    parts = OBJECTIVE_PARTS[cfg.objective]
    eval_fn = _make_eval_fn(env, policy_apply, cfg)

    def parts_fn(params, batch: RolloutBatch):
        return parts(eval_fn(params, batch), batch, params, cfg)

    return parts_fn


def current_eps(cfg: GFNConfig, step: jax.Array) -> jax.Array:
    if cfg.exploration_anneal_steps > 0:
        frac = jnp.clip(step.astype(jnp.float32)
                        / cfg.exploration_anneal_steps, 0.0, 1.0)
        return cfg.exploration_eps * (1.0 - frac)
    return jnp.asarray(cfg.exploration_eps, jnp.float32)


def make_train_step(env: Environment, env_params, policy, cfg: GFNConfig,
                    sampler=None):
    """One jittable on-policy iteration over a ``TrainState`` carry.

    This is the seed API (TrainState in, TrainState out), implemented as the
    on-policy special case of :func:`repro.algo.make_sampler_train_step`.
    Pass ``sampler`` only if its state is empty (``()``) — stateful samplers
    need the ``LoopState`` carry of :class:`repro.algo.TrainLoop`.
    """
    from ..algo.loop import LoopState, make_sampler_train_step
    from ..algo.samplers import OnPolicySampler
    step_fn, tx, init_sampler = make_sampler_train_step(
        env, env_params, policy, cfg, sampler or OnPolicySampler())
    if init_sampler() != ():
        raise ValueError(
            "make_train_step only supports stateless samplers; use "
            "repro.algo.TrainLoop for replay/backward-replay training")

    def train_step(ts: TrainState) -> Tuple[TrainState, Dict[str, jax.Array]]:
        state, (metrics, batch) = step_fn(LoopState(train=ts, sampler=()))
        return state.train, (metrics, batch)

    return train_step, tx


def init_train_state(key: jax.Array, policy, tx) -> TrainState:
    kp, kt = jax.random.split(key)
    params = policy.init(kp)
    return TrainState(params=params, opt_state=tx.init(params),
                      step=jnp.zeros((), jnp.int32), key=kt)


# ---------------------------------------------------------------------------
# Deprecated seed entry points — one shim, three names
# ---------------------------------------------------------------------------

def _loop_shim(name: str, mode: str, key, env, env_params, policy, cfg,
               num_iterations: int, sampler=None, **run_kwargs):
    warnings.warn(
        f"repro.core.trainer.{name} is deprecated; use "
        f"repro.algo.TrainLoop(...).run(mode={mode!r}) (which also accepts "
        "pluggable samplers, eval suites, and device-mesh plans)",
        DeprecationWarning, stacklevel=3)
    from ..algo.loop import TrainLoop
    loop = TrainLoop(env, env_params, policy, cfg, sampler=sampler)
    state, aux = loop.run(key, num_iterations, mode=mode, **run_kwargs)
    return state.train, aux


def train(key: jax.Array, env: Environment, env_params, policy,
          cfg: GFNConfig, num_iterations: int,
          callback: Optional[Callable] = None, callback_every: int = 100,
          sampler=None):
    """Deprecated alias for ``TrainLoop(...).run(mode="python")`` (paper
    Listing 1/2 usage); returns ``(TrainState, history)`` as in the seed."""
    return _loop_shim("train", "python", key, env, env_params, policy, cfg,
                      num_iterations, sampler=sampler, callback=callback,
                      callback_every=callback_every)


def train_compiled(key: jax.Array, env: Environment, env_params, policy,
                   cfg: GFNConfig, num_iterations: int, sampler=None):
    """Deprecated alias for ``TrainLoop(...).run(mode="scan")``; returns
    ``(TrainState, (metrics, log_rewards))`` as in the seed."""
    return _loop_shim("train_compiled", "scan", key, env, env_params, policy,
                      cfg, num_iterations, sampler=sampler)


def train_vectorized(key: jax.Array, env: Environment, env_params, policy,
                     cfg: GFNConfig, num_iterations: int, num_seeds: int,
                     sampler=None):
    """Deprecated alias for ``TrainLoop(...).run(mode="vmap_seeds")`` (the
    paper's 'Trainer vectorization' future-work bullet — now the
    ``vmap_seeds`` / ``seeds_x_data`` execution plans); returns
    ``(TrainState, metrics)`` with a leading seed axis, as in the seed."""
    return _loop_shim("train_vectorized", "vmap_seeds", key, env, env_params,
                      policy, cfg, num_iterations, sampler=sampler,
                      num_seeds=num_seeds)
