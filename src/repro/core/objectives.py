"""GFlowNet training objectives (paper Appendix A, Eqs. 3-7 + MDB).

Every objective consumes a :class:`RolloutBatch` and *re-evaluates* the policy
on the stored observations (teacher forcing), so the same code path serves
on-policy training, replay-buffer training, and backward-sampled trajectories.

  DB     Eq. (3)   (log F(s) P_F(s'|s) - log F(s') P_B(s|s'))^2
  TB     Eq. (4)   (log Z prod P_F - log R(x) prod P_B)^2
  SubTB  Eq. (5)   lambda^(k-j)-weighted all-subtrajectory balance
  FLDB   Eq. (7)   forward-looking DB with energy shaping, E(s0)=0
  MDB    Deleu'22  modified DB for all-states-terminal DAG environments

The estimators are agnostic to *where* ``log P_F`` / ``log P_B`` come from:
for discrete envs they are masked-categorical log-probabilities, for
continuous envs (``env.continuous_actions``) they are transition
log-*densities* w.r.t. the env's reference measures (Lahlou et al., "A
Theory of Continuous Generative Flow Networks" — TB/DB carry over verbatim
under that substitution).  :func:`evaluate_trajectory` resolves the right
path; everything downstream of :class:`TrajEval` is shared and never
touches an action vocabulary.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .rollout import PolicyApply, RolloutBatch, _cache_engaged, _policy_entry
from .types import masked_logprobs

#: Which K/V bank each categorical teacher-forced pass was traced with:
#: ``shared_bank`` (``Policy.apply_traj``, one bank per trajectory) or
#: ``per_state_bank`` (``apply`` on every flattened state).  Counted at
#: trace time, so it records the path a compiled loss took.
counters: Dict[str, int] = {"shared_bank": 0, "per_state_bank": 0}


def shared_bank_engaged(env, policy_apply) -> bool:
    """Whether :func:`evaluate_trajectory` may take ``Policy.apply_traj``.

    That needs the env's incremental-observation capability, resolved as
    the cached rollout resolves it (a slot is written at most once per
    trajectory, so every state's tokens are a subset of the trajectory's
    full token row), and a policy that exposes ``apply_traj``.
    """
    policy, _ = _policy_entry(policy_apply)
    return (_cache_engaged(env, policy, "auto")
            and getattr(policy, "apply_traj", None) is not None)


class TrajEval(NamedTuple):
    """Differentiable per-trajectory quantities under current params.

    log_pf      (T, B)   log P_F(a_t | s_t): categorical log-prob or
                         transition log-density (continuous envs)
    log_pb      (T, B)   log P_B(s_t | s_{t+1}), same convention
    log_flow    (T+1, B) flow head at s_t (zeros if policy lacks one)
    log_pf_stop (T+1, B) log P_F(stop | s_t) (zeros if env lacks stop)
    """
    log_pf: jax.Array
    log_pb: jax.Array
    log_flow: jax.Array
    log_pf_stop: jax.Array


def _evaluate_trajectory_continuous(policy, params,
                                    batch: RolloutBatch) -> TrajEval:
    """Density path: teacher-force the policy's ``log_prob``/``log_prob_b``
    heads on the stored float actions.  Observations carry everything the
    heads need to recompute supports, so replayed and backward-sampled
    batches evaluate identically to on-policy ones."""
    Tp1, B = batch.obs.shape[:2]
    T = Tp1 - 1

    def flat(x):
        return x.reshape((x.shape[0] * B,) + x.shape[2:])

    log_pf = policy.log_prob(params, flat(batch.obs[:-1]),
                             flat(batch.actions)).reshape(T, B)
    log_pb = policy.log_prob_b(params, flat(batch.obs[1:]),
                               flat(batch.bwd_actions)).reshape(T, B)
    if policy.log_state_flow is not None:
        log_flow = policy.log_state_flow(params,
                                         flat(batch.obs)).reshape(Tp1, B)
    else:
        log_flow = jnp.zeros((Tp1, B), jnp.float32)
    v = batch.valid
    return TrajEval(log_pf=jnp.where(v, log_pf, 0.0),
                    log_pb=jnp.where(v, log_pb, 0.0),
                    log_flow=log_flow,
                    log_pf_stop=jnp.zeros((Tp1, B), jnp.float32))


def evaluate_trajectory(policy_apply: PolicyApply, params,
                        batch: RolloutBatch,
                        stop_action: Optional[int] = None,
                        shared_bank: bool = False) -> TrajEval:
    """Accepts a bare ``apply(params, obs)`` callable (categorical path) or
    a full :class:`repro.core.policies.Policy` — a policy with density
    entry points (``log_prob`` non-None, see ``nn.flows``) is evaluated
    through :func:`_evaluate_trajectory_continuous` instead of the masked
    log-softmax + gather below.

    ``shared_bank`` (resolved by :func:`shared_bank_engaged`) evaluates
    every stored state through ``policy_apply.apply_traj``: the same heads,
    with each trajectory's tokens projected to K/V once."""
    if getattr(policy_apply, "log_prob", None) is not None:
        return _evaluate_trajectory_continuous(policy_apply, params, batch)
    Tp1, B = batch.obs.shape[:2]
    if shared_bank:
        counters["shared_bank"] += 1
        out = policy_apply.apply_traj(params, batch.obs)
    else:
        counters["per_state_bank"] += 1
        apply = getattr(policy_apply, "apply", policy_apply)
        out = apply(params, batch.obs.reshape((Tp1 * B,)
                                              + batch.obs.shape[2:]))

    def unflat(x):
        return x.reshape((Tp1, B) + x.shape[1:])

    # On the TPU the mask + log-softmax + action gather fuses into one
    # Pallas pass per direction (kernels.ops.traj_logprob, closed-form VJP);
    # stop-probability extraction needs the full log-softmax tensor, so envs
    # with a stop head keep the jnp path.
    from ..kernels.ops import traj_logprob
    fused = stop_action is None and jax.default_backend() == "tpu"

    logits = unflat(out["logits"])
    if fused:
        _, pf_step = traj_logprob(
            logits[:-1].transpose(1, 0, 2), batch.actions.T,
            batch.fwd_mask[:-1].transpose(1, 0, 2), batch.valid.T)
        log_pf = pf_step.T
    else:
        logp_f = masked_logprobs(logits, batch.fwd_mask)
        log_pf = jnp.take_along_axis(
            logp_f[:-1], batch.actions[..., None], axis=-1)[..., 0]

    logits_b = out.get("logits_b")
    if logits_b is None:
        logits_b = jnp.zeros(batch.bwd_mask.shape, jnp.float32)
    else:
        logits_b = unflat(logits_b)
    if fused:
        _, pb_step = traj_logprob(
            logits_b[1:].transpose(1, 0, 2), batch.bwd_actions.T,
            batch.bwd_mask[1:].transpose(1, 0, 2), batch.valid.T)
        log_pb = pb_step.T
    else:
        logp_b = masked_logprobs(logits_b, batch.bwd_mask)
        log_pb = jnp.take_along_axis(
            logp_b[1:], batch.bwd_actions[..., None], axis=-1)[..., 0]

    log_flow = unflat(out["log_flow"]) if "log_flow" in out else \
        jnp.zeros((Tp1, B), jnp.float32)
    if stop_action is not None:
        log_pf_stop = logp_f[..., stop_action]
    else:
        log_pf_stop = jnp.zeros((Tp1, B), jnp.float32)

    v = batch.valid
    return TrajEval(log_pf=jnp.where(v, log_pf, 0.0),
                    log_pb=jnp.where(v, log_pb, 0.0),
                    log_flow=log_flow, log_pf_stop=log_pf_stop)


# ---------------------------------------------------------------------------
# Objectives
# ---------------------------------------------------------------------------

def combine_parts(num: jax.Array, den: jax.Array) -> jax.Array:
    """Loss from an unreduced ``(sum, weight)`` pair (see OBJECTIVE_PARTS)."""
    return num / jnp.maximum(den, 1.0)


def tb_parts(ev: TrajEval, batch: RolloutBatch,
             log_z: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Trajectory Balance, Eq. (4), as an unreduced (sum, count) pair."""
    s_pf = jnp.sum(ev.log_pf, axis=0)
    s_pb = jnp.sum(ev.log_pb, axis=0)
    delta = log_z + s_pf - batch.log_reward - s_pb
    return jnp.sum(jnp.square(delta)), jnp.asarray(
        batch.log_reward.shape[0], jnp.float32)


def tb_loss(ev: TrajEval, batch: RolloutBatch, log_z: jax.Array) -> jax.Array:
    """Trajectory Balance, Eq. (4)."""
    return combine_parts(*tb_parts(ev, batch, log_z))


def _flow_targets(ev: TrajEval, batch: RolloutBatch) -> jax.Array:
    """log F(s_t) for t=0..T with terminal states pinned to log R(x)."""
    log_r = batch.log_reward[None, :]
    return jnp.where(batch.done, log_r, ev.log_flow)


def db_parts(ev: TrajEval,
             batch: RolloutBatch) -> Tuple[jax.Array, jax.Array]:
    """Detailed Balance, Eq. (3), as (residual sum, valid-transition count);
    F(terminal) := R."""
    flows = _flow_targets(ev, batch)
    delta = flows[:-1] + ev.log_pf - flows[1:] - ev.log_pb
    delta = jnp.where(batch.valid, delta, 0.0)
    n = jnp.sum(batch.valid).astype(jnp.float32)
    return jnp.sum(jnp.square(delta)), n


def db_loss(ev: TrajEval, batch: RolloutBatch) -> jax.Array:
    """Detailed Balance, Eq. (3); F(terminal) := R."""
    return combine_parts(*db_parts(ev, batch))


#: beyond this many states the dense (T+1, T+1, B) residual tensor is
#: skipped in favor of the O(T) prefix recurrence (``impl="auto"``)
_SUBTB_DENSE_MAX_T1 = 64


def _subtb_phi(ev: TrajEval, batch: RolloutBatch):
    """Flow-corrected potentials phi (T+1, B) and per-trajectory lengths.

    With c_t = sum_{u<t}(log_pf - log_pb) and phi_t = log F(s_t) - c_t, the
    (j, k) subtrajectory residual is phi_j - phi_k; state t is on the
    realized trajectory iff t <= n with n = #valid transitions (``valid`` is
    a True-prefix: once a sub-env terminates it stays terminated).
    """
    T, B = ev.log_pf.shape
    flows = _flow_targets(ev, batch)                       # (T+1, B)
    diffs = ev.log_pf - ev.log_pb                          # (T, B)
    c = jnp.concatenate(
        [jnp.zeros((1, B)), jnp.cumsum(diffs, axis=0)], axis=0)
    length = jnp.sum(batch.valid.astype(jnp.int32), axis=0)
    return flows - c, length


def _subtb_dense(phi: jax.Array, length: jax.Array, lam: float) -> jax.Array:
    """Materialized (T+1, T+1, B) pairwise form — O(T^2 B) memory."""
    T1, B = phi.shape
    idx = jnp.arange(T1)
    on_traj = idx[:, None] <= length[None, :]              # (T+1, B)
    pair_valid = (idx[:, None] < idx[None, :])[..., None]  # j < k
    pair_valid = jnp.logical_and(pair_valid, on_traj[:, None, :])
    pair_valid = jnp.logical_and(pair_valid, on_traj[None, :, :])
    w = lam ** (idx[None, :] - idx[:, None]).astype(jnp.float32)
    w = jnp.where(pair_valid, w[..., None], 0.0)
    resid = phi[:, None, :] - phi[None, :, :]              # (T+1, T+1, B)
    num = jnp.sum(w * jnp.square(resid), axis=(0, 1))
    den = jnp.maximum(jnp.sum(w, axis=(0, 1)), 1e-9)
    return num / den


def _subtb_prefix(phi: jax.Array, length: jax.Array, lam: float) -> jax.Array:
    """O(T) prefix-sum recurrence over k — no pairwise tensor.

    Expanding sum_{j<k} lam^(k-j) (phi_j - phi_k)^2 per k with the running
    sums S2_k = sum_{j<k} lam^(k-j) phi_j^2, S1_k (phi_j), W_k (1) — each
    satisfying X_k = lam * (X_{k-1} + x_{k-1}) — gives
    num = sum_k S2_k - 2 phi_k S1_k + phi_k^2 W_k over on-trajectory k.
    """
    T1, B = phi.shape
    zeros = jnp.zeros((B,), jnp.float32)

    def step(carry, inp):
        s2, s1, w, num, den = carry
        phi_prev, phi_k, on_k = inp
        s2 = lam * (s2 + jnp.square(phi_prev))
        s1 = lam * (s1 + phi_prev)
        w = lam * (w + 1.0)
        term = s2 - 2.0 * phi_k * s1 + jnp.square(phi_k) * w
        num = num + jnp.where(on_k, term, 0.0)
        den = den + jnp.where(on_k, w, 0.0)
        return (s2, s1, w, num, den), None

    ks = jnp.arange(1, T1)
    on = ks[:, None] <= length[None, :]                    # (T, B)
    (_, _, _, num, den), _ = jax.lax.scan(
        step, (zeros, zeros, zeros, zeros, zeros), (phi[:-1], phi[1:], on))
    return num / jnp.maximum(den, 1e-9)


def _subtb_pallas(phi: jax.Array, length: jax.Array, lam: float) -> jax.Array:
    """Pallas-kernel forward with a prefix-recurrence backward.

    The tiled kernel (``kernels/subtb_loss.py``) has no VJP of its own, but
    :func:`_subtb_prefix` computes the identical function with plain jnp
    ops — so the custom backward differentiates *that*, keeping the loss
    usable inside ``jax.grad`` (subtb trains through this path on TPU).
    """
    from ..kernels.ops import subtb_loss as subtb_kernel

    @jax.custom_vjp
    def f(p):
        return subtb_kernel(p.T, length, lam=lam)

    def fwd(p):
        return f(p), p

    def bwd(p, g):
        _, vjp_fn = jax.vjp(lambda q: _subtb_prefix(q, length, lam), p)
        return vjp_fn(g)

    f.defvjp(fwd, bwd)
    return f(phi)


def subtb_loss(ev: TrajEval, batch: RolloutBatch, lam: float = 0.9,
               impl: str = "auto") -> jax.Array:
    """Subtrajectory Balance, Eq. (5), weights lambda^(k-j), normalized
    per trajectory then averaged.

    ``impl`` selects the backend behind the same signature/semantics:
      - "dense":  materialize the (T+1, T+1, B) residual tensor;
      - "prefix": O(T)-memory prefix-sum recurrence (equivalent to fp
        reassociation; see ``tests/test_objectives.py``);
      - "pallas": the tiled Pallas kernel (``kernels/subtb_loss.py``)
        forward, prefix-recurrence backward (``jax.grad``-safe);
      - "auto":   pallas on the TPU, else dense for small T and prefix
        beyond ``_SUBTB_DENSE_MAX_T1`` states.
    """
    phi, length = _subtb_phi(ev, batch)
    if impl == "auto":
        if jax.default_backend() == "tpu":
            impl = "pallas"
        else:
            impl = "dense" if phi.shape[0] <= _SUBTB_DENSE_MAX_T1 \
                else "prefix"
    if impl == "dense":
        per_traj = _subtb_dense(phi, length, lam)
    elif impl == "prefix":
        per_traj = _subtb_prefix(phi, length, lam)
    elif impl == "pallas":
        per_traj = _subtb_pallas(phi, length, lam)
    else:
        raise ValueError(f"unknown subtb impl {impl!r}")
    return jnp.mean(per_traj)


def subtb_parts(ev: TrajEval, batch: RolloutBatch, lam: float = 0.9,
                impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """:func:`subtb_loss` as (per-trajectory sum, trajectory count)."""
    B = ev.log_pf.shape[1]
    return subtb_loss(ev, batch, lam, impl) * B, jnp.asarray(B, jnp.float32)


def fldb_parts(ev: TrajEval,
               batch: RolloutBatch) -> Tuple[jax.Array, jax.Array]:
    """Forward-Looking DB, Eq. (7), as (residual sum, transition count).

    The environment supplies energies with E(s0)=0 and E(x)=-log R(x) at
    terminals, so the terminal forward-looking flow target is
    log F~(x) = log R(x) + E(x) = 0.
    """
    fl_flows = jnp.where(batch.done, 0.0, ev.log_flow)
    dE = batch.energy[1:] - batch.energy[:-1]
    delta = fl_flows[:-1] + ev.log_pf - fl_flows[1:] - ev.log_pb + dE
    delta = jnp.where(batch.valid, delta, 0.0)
    n = jnp.sum(batch.valid).astype(jnp.float32)
    return jnp.sum(jnp.square(delta)), n


def fldb_loss(ev: TrajEval, batch: RolloutBatch) -> jax.Array:
    """Forward-Looking DB, Eq. (7)."""
    return combine_parts(*fldb_parts(ev, batch))


def mdb_parts(ev: TrajEval,
              batch: RolloutBatch) -> Tuple[jax.Array, jax.Array]:
    """Modified DB (Deleu et al. 2022) for envs where every state is
    terminal, as (residual sum, non-stop transition count).

    For a non-stop transition s -> s':
      R(s) P_F(s'|s) P_F(stop|s') = R(s') P_B(s|s') P_F(stop|s)
    """
    lr = batch.log_r_state                      # (T+1, B)
    delta = (lr[:-1] + ev.log_pf + ev.log_pf_stop[1:]
             - lr[1:] - ev.log_pb - ev.log_pf_stop[:-1])
    # transitions that are the stop action itself are excluded: a stop step
    # moves s -> terminal-copy(s); identified by done[t+1].
    real = jnp.logical_and(batch.valid, jnp.logical_not(batch.done[1:]))
    delta = jnp.where(real, delta, 0.0)
    n = jnp.sum(real).astype(jnp.float32)
    return jnp.sum(jnp.square(delta)), n


def mdb_loss(ev: TrajEval, batch: RolloutBatch) -> jax.Array:
    """Modified DB (Deleu et al. 2022)."""
    return combine_parts(*mdb_parts(ev, batch))


# ---------------------------------------------------------------------------
# Registry — uniform signature
# ---------------------------------------------------------------------------
# Every registered objective takes (ev, batch, params, cfg); objective-
# specific extras (log_z, subtb_lambda) are pulled from params/cfg inside the
# adapter, so trainers dispatch by name with zero per-objective branching and
# new objectives are one registry entry.
#
# Nothing below this line depends on a finite action vocabulary: the
# adapters consume only TrajEval's (T, B) log-prob/log-density grids and the
# batch's scalar fields, so the same TB/DB/SubTB estimators train discrete
# masked-categorical policies and continuous density policies unchanged
# (asserted in tests/test_box.py::TestVocabularyIndependence).
#
# OBJECTIVE_PARTS holds the *unreduced* form: (sum, weight) with
# loss == sum / max(weight, 1).  Both components are additive over batch
# slices, which is what lets a data-parallel plan compute them per shard,
# ``lax.psum`` each, and recover the exact global loss — a mean of
# per-shard means would silently differ whenever the denominator is a
# data-dependent count (DB/FLDB/MDB normalize by valid-transition counts).

def _tb_parts(ev, batch, params, cfg):
    return tb_parts(ev, batch, params["log_z"])


def _db_parts(ev, batch, params, cfg):
    return db_parts(ev, batch)


def _subtb_parts(ev, batch, params, cfg):
    return subtb_parts(ev, batch, cfg.subtb_lambda)


def _fldb_parts(ev, batch, params, cfg):
    return fldb_parts(ev, batch)


def _mdb_parts(ev, batch, params, cfg):
    return mdb_parts(ev, batch)


OBJECTIVE_PARTS = {
    "tb": _tb_parts, "db": _db_parts, "subtb": _subtb_parts,
    "fldb": _fldb_parts, "mdb": _mdb_parts,
}


def _reduced(parts_fn):
    def obj(ev: TrajEval, batch: RolloutBatch, params, cfg) -> jax.Array:
        return combine_parts(*parts_fn(ev, batch, params, cfg))
    return obj


OBJECTIVES = {name: _reduced(fn) for name, fn in OBJECTIVE_PARTS.items()}
