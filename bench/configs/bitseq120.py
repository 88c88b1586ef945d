"""Plain reference for ``bitseq120``: bit sequences of n bits in words of
k bits (gfnx paper section B.2), the decode transformer policy, the
trajectory-balance loss, and the served sampler's Gumbel-max draw.

Written from the descriptions, in ``jax.numpy``, with no cache, no kernel
and no batching tricks; it imports nothing of the program.

- State: L = n/k positions, each empty or holding a word in [0, 2^k).
  Forward action ``pos * 2^k + word`` writes an empty position; a
  trajectory ends after L writes.  The backward policy is uniform over
  the filled positions.
- Reward: log R(x) = -beta * min_{x' in M} Hamming(x, x') / n over a mode
  set M of 60 strings, each n/8 draws from H = {00000000, 11111111,
  11110000, 00001111, 00111100} (numpy ``RandomState(mode_seed)``).
- Policy: a latent query h0 = q0 reads a bank of keys and values made
  from frozen input embeddings: slot 0 a learned BOS vector, slot 1+p the
  word at position p plus position p's embedding (empty positions are
  masked out).  Per layer: q = LN1(h) Wq; K, V = bank Wkv; h += attention
  Wp; h += W2 gelu(W1 LN2(h)).  Output LN_f(h) Wr gives 2^k * L forward
  logits and one flow value.  The parameter tree is the program's, so the
  benchmark can hand the same weights to both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import refops

H_PATTERNS = np.array([[0, 0, 0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1],
                       [1, 1, 1, 1, 0, 0, 0, 0], [0, 0, 0, 0, 1, 1, 1, 1],
                       [0, 0, 1, 1, 1, 1, 0, 0]], np.int32)


def sizes(cfg):
    e = cfg["env"]
    L, m = e["n"] // e["k"], 2 ** e["k"]
    return L, m, L * m


def mode_words(cfg) -> np.ndarray:
    """(num_modes, L) word ids of the mode set, MSB-first within a word."""
    e = cfg["env"]
    rng = np.random.RandomState(e["mode_seed"])
    modes = np.zeros((e["num_modes"], e["n"]), np.int32)
    for i in range(e["num_modes"]):
        modes[i] = H_PATTERNS[rng.randint(0, 5, size=e["n"] // 8)].reshape(-1)
    pw = 2 ** np.arange(e["k"] - 1, -1, -1)
    return (modes.reshape(e["num_modes"], -1, e["k"]) * pw).sum(-1)


def log_reward(cfg, words, beta=None, dt=jnp.float32):
    """words: (N, L) filled word sequences; computed in ``dt``."""
    e = cfg["env"]
    mw = jnp.asarray(mode_words(cfg))
    bits = (words[:, None, :, None] >> jnp.arange(e["k"])) & 1
    mbits = (mw[None, :, :, None] >> jnp.arange(e["k"])) & 1
    ham = jnp.sum(bits != mbits, axis=(2, 3))
    beta = jnp.asarray(e["beta"] if beta is None else beta, dt)
    return -beta * jnp.min(ham, axis=1).astype(dt) / jnp.asarray(e["n"], dt)


def num_actions(cfg):
    return sizes(cfg)[2]


def param_shapes(cfg) -> dict:
    p = cfg["policy"]
    L, m, A = sizes(cfg)
    D, F = p["dim"], p["ff_dim"]
    lin = lambda i, o: {"w": (i, o), "b": (o,)}
    ln = {"scale": (D,), "bias": (D,)}
    dec = {f"layer_{i}": {"ln1": ln, "q": lin(D, D), "kv": lin(D, 2 * D),
                          "proj": lin(D, D), "ln2": ln, "ff1": lin(D, F),
                          "ff2": lin(F, D)} for i in range(p["num_layers"])}
    dec.update(ln_f=ln, q0=(D,))
    return {"embed": {"table": (m + 1, D)}, "pos": {"pos": (L, D)},
            "bos": (D,), "decoder": dec, "readout": lin(D, A + 1),
            "log_z": ()}


def forward(cfg, params, tokens, dt=jnp.float32):
    """Logits (N, A) and log-flow (N,) of states ``tokens`` (N, L), where
    the empty token is 2^k."""
    p = refops.cast(params, dt)
    pc = cfg["policy"]
    L, m, A = sizes(cfg)
    D, nh = pc["dim"], pc["num_heads"]
    hd = D // nh
    N = tokens.shape[0]
    xs = p["embed"]["table"][tokens] + p["pos"]["pos"][None, :L]
    bank = jnp.concatenate(
        [jnp.broadcast_to(p["bos"], (N, 1, D)), xs], axis=1)
    valid = jnp.concatenate([jnp.ones((N, 1), bool), tokens != m], axis=1)
    h = jnp.broadcast_to(p["decoder"]["q0"], (N, D))
    for i in range(pc["num_layers"]):
        lp = p["decoder"][f"layer_{i}"]
        kv = refops.dense(lp["kv"], bank)
        k = kv[..., :D].reshape(N, L + 1, nh, hd)
        v = kv[..., D:].reshape(N, L + 1, nh, hd)
        q = refops.dense(lp["q"], refops.layernorm(lp["ln1"], h))
        s = jnp.einsum("nhd,nshd->nhs", q.reshape(N, nh, hd), k) / jnp.sqrt(
            jnp.asarray(hd, dt))
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        att = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        o = jnp.einsum("nhs,nshd->nhd", att, v).reshape(N, D)
        h = h + refops.dense(lp["proj"], o)
        g = refops.layernorm(lp["ln2"], h)
        h = h + refops.dense(lp["ff2"], refops.gelu_tanh(
            refops.dense(lp["ff1"], g)))
    y = refops.layernorm(p["decoder"]["ln_f"], h)
    out = refops.dense(p["readout"], y)
    return out[:, :A], out[:, A]


def forward_mask(cfg, tokens):
    L, m, A = sizes(cfg)
    return jnp.repeat(tokens == m, m, axis=-1)


def replay(cfg, actions):
    """States before each action: (T, B, L) from actions (T, B)."""
    L, m, A = sizes(cfg)
    T, B = actions.shape
    tok = np.full((B, L), m, np.int32)
    states = []
    for t in range(T):
        states.append(tok.copy())
        a = np.asarray(actions[t])
        tok[np.arange(B), a // m] = a % m
    return np.stack(states), tok


# -- training -----------------------------------------------------------------

def train_batch(cfg, out):
    """What the reference needs of one program step's batch: its actions,
    and the states they lead through (replayed here, not read)."""
    actions = np.asarray(out["actions"], np.int32)
    states, final = replay(cfg, actions)
    L, m, A = sizes(cfg)
    legal = np.take_along_axis(
        np.repeat(states == m, m, axis=-1),
        actions[..., None], axis=-1)[..., 0]
    return {"actions": actions, "states": states, "final": final,
            "illegal": int((~legal).sum())}


def loss_fn(cfg, dt, keep=None):
    """TB loss of a batch under params, every step computed in ``dt``; aux:
    per-step forward log-probs (T, B) and terminal log-rewards (B,).
    ``keep`` limits the loss to the first ``keep`` trajectories (the fault
    'half the batch left out')."""
    L, m, A = sizes(cfg)

    def fn(params, batch):
        states = jnp.asarray(batch["states"])            # (T, B, L)
        actions = jnp.asarray(batch["actions"])          # (T, B)
        T, B = actions.shape
        logits, _ = forward(cfg, params, states.reshape(T * B, L), dt)
        logp = refops.masked_log_softmax(
            logits, forward_mask(cfg, states.reshape(T * B, L)))
        log_pf = jnp.take_along_axis(
            logp, actions.reshape(T * B, 1), axis=-1).reshape(T, B)
        # uniform backward policy: 1 / (filled positions after step t)
        log_pb = -jnp.log(jnp.arange(1, T + 1, dtype=dt))[:, None]
        log_r = log_reward(cfg, jnp.asarray(batch["final"])).astype(dt)
        log_z = params["log_z"].astype(dt)
        delta = log_z + jnp.sum(log_pf, 0) - log_r - jnp.sum(log_pb, 0)
        n = B if keep is None else keep
        return (jnp.mean(jnp.square(delta[:n])).astype(jnp.float32),
                {"log_pf": log_pf.astype(jnp.float32),
                 "log_r": log_r.astype(jnp.float32)})

    return fn


# -- serving ------------------------------------------------------------------

def gumbel_of(seed, sample, num_steps, num_actions):
    """The Gumbel noise the served draw of ``sample`` of a request keyed
    by ``seed`` uses at each step: split(PRNGKey(seed), T)[t], folded with
    the sample index, its second split, then A standard Gumbels (the
    documented reproducibility contract of a request)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), num_steps)

    def at(k):
        kc = jax.random.split(jax.random.fold_in(k, sample), 3)[1]
        return jax.random.gumbel(kc, (num_actions,))

    return jax.vmap(at)(keys)


def follow_served(cfg, params, samples, gumbels, temps, dt=jnp.float32,
                  control_dt=None):
    """Walk each served sample's trajectory under the reference.

    samples: (N, L) served words; gumbels: (N, T, A); temps: (N,).  At each
    step the reference scores every legal action as log-softmax of the
    tempered logits plus the request's Gumbel noise; the served sample
    admits only actions that write its own word at an empty position.
    The gap of a step is how far the best admitted action's score lies
    below the best score: 0 where the served draw is the reference's.
    The walk follows the best admitted action.  With ``control_dt`` the
    same states are also scored in that dtype, and the reference's gap of
    the action the control puts first is read.  Returns (N,) widest gaps,
    and the control's (or None)."""
    L, m, A = sizes(cfg)
    N = samples.shape[0]
    tok = jnp.full((N, L), m, jnp.int32)
    admit_word = jnp.repeat(jnp.asarray(samples), m, axis=-1) == jnp.tile(
        jnp.arange(m), L)[None]
    gap = jnp.zeros((N,), jnp.float32)
    cgap = jnp.zeros((N,), jnp.float32)

    def score(dtype, tokens, t):
        logits, _ = forward(cfg, params, tokens, dtype)
        lp = refops.masked_log_softmax(logits * temps[:, None].astype(dtype),
                                       forward_mask(cfg, tokens))
        return lp.astype(jnp.float32) + gumbels[:, t]

    for t in range(L):
        s = score(dt, tok, t)
        best = jnp.max(s, axis=-1)
        adm = jnp.where(admit_word & forward_mask(cfg, tok), s, -jnp.inf)
        a = jnp.argmax(adm, axis=-1)
        gap = jnp.maximum(gap, best - jnp.max(adm, axis=-1))
        if control_dt is not None:
            c = jnp.argmax(score(control_dt, tok, t), axis=-1)
            cgap = jnp.maximum(cgap, best - jnp.take_along_axis(
                s, c[:, None], axis=-1)[:, 0])
        tok = tok.at[jnp.arange(N), a // m].set(a % m)
    return gap, (cgap if control_dt is not None else None)
