"""Plain reference for ``hypergrid20x4``: the d-dimensional hypergrid of
side H (gfnx paper section B.1, after Bengio et al. 2021), a ReLU MLP
policy with a flow head, and the SubTB(lambda) loss (paper Eq. 5).

Written from the descriptions in ``jax.numpy``; it imports nothing of the
program.

- State: coordinates in [0, H)^d and a terminal flag.  Forward actions
  0..d-1 increment a coordinate below H-1; action d stops (terminal copy).
  Observation: the d one-hot rows of the coordinates, concatenated.
- Backward policy: uniform over the legal backward actions (decrement a
  coordinate above 0; from a terminal copy, only un-stop).
- Reward: R(s) = R0 + R1 prod_i [0.25 < |s_i/(H-1) - 0.5|]
  + R2 prod_i [0.3 < |s_i/(H-1) - 0.5| < 0.4].
- SubTB: with c_t = sum_{u<t} (log P_F - log P_B) over real transitions
  and F(s_t) the flow head (log R at the terminal state),
  phi_t = log F(s_t) - c_t; per trajectory of n transitions,
  sum_{0<=j<k<=n} lambda^(k-j) (phi_j - phi_k)^2 / sum lambda^(k-j),
  averaged over the batch.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import refops


def sizes(cfg):
    e = cfg["env"]
    return e["dim"], e["side"], e["dim"] * (e["side"] - 1) + 1


def num_actions(cfg):
    return cfg["env"]["dim"] + 1


def param_shapes(cfg) -> dict:
    d, H, _ = sizes(cfg)
    dims = [d * H] + list(cfg["policy"]["hidden"]) + [d + 2]
    return {"torso": {f"layer_{i}": {"w": (dims[i], dims[i + 1]),
                                     "b": (dims[i + 1],)}
                      for i in range(len(dims) - 1)},
            "log_z": ()}


def forward(cfg, params, obs, dt=jnp.float32):
    """Forward logits (N, d+1) and log-flow (N,) of observations."""
    p = refops.cast(params, dt)["torso"]
    x = obs.astype(dt)
    n = len(p)
    for i in range(n):
        x = refops.dense(p[f"layer_{i}"], x)
        if i < n - 1:
            x = jnp.maximum(x, 0)
    d = cfg["env"]["dim"]
    return x[:, :d + 1], x[:, d + 1]


def log_reward(cfg, pos):
    e = cfg["env"]
    x = jnp.abs(pos.astype(jnp.float32) / (e["side"] - 1) - 0.5)
    t1 = jnp.all(x > 0.25, axis=-1)
    t2 = jnp.all((x > 0.3) & (x < 0.4), axis=-1)
    return jnp.log(e["r0"] + e["r1"] * t1 + e["r2"] * t2)


def replay(cfg, actions):
    """Replay actions (T, B): positions (T+1, B, d), terminal flags
    (T+1, B), forward masks (T+1, B, d+1), backward legal counts (T+1, B)
    and the number of illegal actions at live steps."""
    d, H, _ = sizes(cfg)
    T, B = actions.shape
    pos = np.zeros((B, d), np.int64)
    term = np.zeros(B, bool)
    P, D, M, NB = [], [], [], []
    illegal = 0

    def snap():
        P.append(pos.copy())
        D.append(term.copy())
        # a terminal state's row is never gathered; it keeps every action
        # legal so its log-softmax (and gradient) stays finite
        M.append(np.concatenate([(pos < H - 1) | term[:, None],
                                 np.ones((B, 1), bool)], axis=1))
        NB.append(np.where(term, 1, (pos > 0).sum(1)))

    snap()
    for t in range(T):
        a = np.asarray(actions[t])
        live = ~term
        illegal += int((~M[-1][np.arange(B), a] & live).sum())
        stop = (a == d) & live
        inc = (a < d) & live
        pos[inc, a[inc]] += 1
        term = term | stop
        snap()
    return (np.stack(P), np.stack(D), np.stack(M), np.stack(NB), illegal)


def train_batch(cfg, out):
    actions = np.asarray(out["actions"], np.int32)
    pos, done, mask, nb, illegal = replay(cfg, actions)
    d, H, _ = sizes(cfg)
    obs = np.eye(H, dtype=np.float32)[pos].reshape(pos.shape[:2] + (d * H,))
    return {"actions": actions, "obs": obs, "done": done, "fmask": mask,
            "nb": nb.astype(np.float32), "final": pos[-1],
            "illegal": illegal}


def loss_fn(cfg, dt, keep=None):
    """SubTB loss of a batch, every step computed in ``dt``; aux: per-step
    forward log-probs (T, B), zero after the trajectory ends, and terminal
    log-rewards (B,)."""
    lam = cfg["subtb_lambda"]

    def fn(params, batch):
        obs = jnp.asarray(batch["obs"])                  # (T+1, B, dH)
        T1, B = obs.shape[:2]
        actions = jnp.asarray(batch["actions"])
        done = jnp.asarray(batch["done"])
        logits, flow = forward(cfg, params, obs.reshape(T1 * B, -1), dt)
        logits = logits.reshape(T1, B, -1)
        flow = flow.reshape(T1, B)
        logp = refops.masked_log_softmax(logits, jnp.asarray(batch["fmask"]))
        valid = ~done[:-1]
        log_pf = jnp.where(valid, jnp.take_along_axis(
            logp[:-1], actions[..., None], axis=-1)[..., 0], 0.0)
        log_pb = jnp.where(valid, -jnp.log(jnp.asarray(batch["nb"])[1:]),
                           0.0).astype(dt)
        log_r = log_reward(cfg, jnp.asarray(batch["final"])).astype(dt)
        flows = jnp.where(done, log_r[None], flow)
        c = jnp.concatenate([jnp.zeros((1, B), dt),
                             jnp.cumsum(log_pf - log_pb, axis=0)])
        phi = flows - c                                  # (T+1, B)
        n = jnp.sum(valid, axis=0)
        idx = jnp.arange(T1)
        on = idx[:, None] <= n[None]
        pair = (idx[:, None] < idx[None, :])[..., None] & on[:, None] \
            & on[None, :]
        w = jnp.where(pair, (lam ** (idx[None, :] - idx[:, None]).astype(
            jnp.float32))[..., None], 0.0).astype(dt)
        per = jnp.sum(w * jnp.square(phi[:, None] - phi[None, :]),
                      axis=(0, 1)) / jnp.sum(w, axis=(0, 1))
        k = B if keep is None else keep
        return (jnp.mean(per[:k]).astype(jnp.float32),
                {"log_pf": log_pf.astype(jnp.float32),
                 "log_r": log_r.astype(jnp.float32)})

    return fn
