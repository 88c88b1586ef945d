"""Mixture-of-Experts block: top-k router + GShard-style dense one-hot
dispatch with capacity (the TPU-native formulation — DESIGN.md §4).

qwen2-moe: 60 routed experts (padded to 64 for expert-parallel divisibility
over the 16-way model axis; pad experts get -inf router logits and receive
zero tokens) + 4 "shared" experts fused into one always-on gated MLP of
4x width.  qwen3-moe: 128 routed experts, top-8, no shared experts.

Dispatch shape discipline: tokens are processed in groups of ``group_size``
so the one-hot dispatch tensor is (G, Tg, E, C) with
C = ceil(topk * Tg / E * capacity_factor) — total memory T * topk * Tg * cf,
independent of E, and sharded over the data axis via the leading G dim.
Overflowing tokens are dropped (contribute only via the shared expert /
residual), the standard GShard trade-off.

The ``mla_moe`` family (Moonlight / DeepSeek-V3) uses the held-expert
layer at the end of this module instead: a sigmoid ``noaux_tc`` router
over every routed expert (``sigmoid_topk_route``) and the part of the
routed sum that the experts this chip holds give, with no token dropped
(``held_experts``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.core import Params, normal_init
from .layers import gated_mlp, gated_mlp_init, rmsnorm, rmsnorm_init


def padded_num_experts(cfg) -> int:
    """Pad expert count to a multiple of 16 for EP sharding divisibility."""
    return cfg.padded_experts


def moe_block_init(key, cfg, attn_init, dtype) -> Params:
    d = cfg.d_model
    E = padded_num_experts(cfg)
    ks = jax.random.split(key, 6)
    p = {
        "ln1": rmsnorm_init(d, dtype),
        "attn": attn_init(ks[0], cfg, dtype),
        "ln2": rmsnorm_init(d, dtype),
        "router": normal_init(ks[1], (d, E), 0.02, jnp.float32),
        "we_gate": normal_init(ks[2], (E, d, cfg.moe_d_ff), 0.02, dtype),
        "we_up": normal_init(ks[3], (E, d, cfg.moe_d_ff), 0.02, dtype),
        "we_down": normal_init(ks[4], (E, cfg.moe_d_ff, d), 0.02, dtype),
    }
    if cfg.shared_d_ff:
        p["shared"] = gated_mlp_init(ks[5], d, cfg.shared_d_ff, dtype)
        p["shared_gate"] = normal_init(jax.random.fold_in(key, 7), (d, 1),
                                       0.02, dtype)
    return p


def _router_probs(p: Params, x: jax.Array, cfg) -> jax.Array:
    """(T, E_padded) softmax router probs; pad experts masked to -inf."""
    logits = (x.astype(jnp.float32) @ p["router"])
    E = padded_num_experts(cfg)
    if E != cfg.num_experts:
        pad_mask = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    return jax.nn.softmax(logits, axis=-1)


def moe_mlp(p: Params, x: jax.Array, cfg, group_size: int = 512
            ) -> Tuple[jax.Array, jax.Array]:
    """Routed-expert MLP over (B, S, d); returns (out, aux_loss)."""
    B, S, d = x.shape
    T = B * S
    k = cfg.num_experts_per_tok
    E = padded_num_experts(cfg)
    xt = x.reshape(T, d)
    probs = _router_probs(p, xt, cfg)                      # (T, E)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    top_idx = jax.lax.top_k(probs, k)[1]                   # (T, k)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=1),
        axis=0) / k
    aux = cfg.router_aux_loss * E * jnp.sum(me * ce)

    gates = jnp.take_along_axis(probs, top_idx, axis=-1)   # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    Tg = min(group_size, T)
    G = T // Tg
    C = max(int(k * Tg / E * cfg.capacity_factor), 1)

    xg = xt.reshape(G, Tg, d)
    ig = top_idx.reshape(G, Tg, k)
    gg = gates.reshape(G, Tg, k)

    onehot = jax.nn.one_hot(ig, E, dtype=jnp.float32)      # (G, Tg, k, E)
    # position of each (token, slot) within its expert, token-major priority
    flat = onehot.reshape(G, Tg * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                  # (G, Tg*k, E)
    pos = jnp.sum(pos.reshape(G, Tg, k, E) * onehot, axis=-1)  # (G, Tg, k)
    keep = pos < C
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) \
        * keep[..., None].astype(jnp.float32)              # (G, Tg, k, C)
    # dispatch/combine tensors (G, Tg, E, C)
    dispatch = jnp.einsum('gtke,gtkc->gtec', onehot, pos_oh)
    combine = jnp.einsum('gtke,gtkc,gtk->gtec', onehot, pos_oh, gg)

    expert_in = jnp.einsum('gtec,gtd->gecd', dispatch.astype(x.dtype), xg)
    h = jnp.einsum('gecd,edf->gecf', expert_in, p["we_gate"])
    u = jnp.einsum('gecd,edf->gecf', expert_in, p["we_up"])
    h = jax.nn.silu(h) * u
    expert_out = jnp.einsum('gecf,efd->gecd', h, p["we_down"])
    out = jnp.einsum('gtec,gecd->gtd', combine.astype(x.dtype), expert_out)
    out = out.reshape(B, S, d)

    if "shared" in p:
        shared = gated_mlp(p["shared"], x)
        sg = jax.nn.sigmoid((x @ p["shared_gate"]).astype(jnp.float32))
        out = out + shared * sg.astype(x.dtype)
    return out, aux


def moe_block_apply(p, x, cfg, positions, attention_sublayer, rmsnorm_fn,
                    cache=None, cache_index=None, attn_chunk=1024,
                    window=0, group_size: int = 0):
    group_size = group_size or cfg.moe_group_size
    """Returns (x, new_cache, aux_loss); the backbone scan accumulates the
    per-layer load-balancing aux losses into the training objective."""
    a, new_cache = attention_sublayer(p["attn"], rmsnorm_fn(p["ln1"], x),
                                      cfg, positions, cache, cache_index,
                                      window, attn_chunk)
    x = x + a
    m, aux = moe_mlp(p, rmsnorm_fn(p["ln2"], x), cfg, group_size)
    x = x + m
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Held-expert dropless layer (DeepSeek-V3 / Moonlight routing)
# ---------------------------------------------------------------------------

#: What each traced held-expert layer was: ``held_dropless`` counts the
#: layers, ``experts_held`` the experts they held, summed.  Counted at trace
#: time, as ``core.objectives.counters``.
counters: Dict[str, int] = {"held_dropless": 0, "experts_held": 0}


def sigmoid_topk_route(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                       k: int, scaling: float) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3 ``noaux_tc`` routing with one expert group: scores
    ``s = sigmoid(x W_r)`` over every routed expert, the top ``k`` of
    ``s + bias`` chosen, their weights ``s`` renormalised to sum to 1 and
    scaled by ``scaling``.  The bias only steers the choice and takes no
    gradient.  Returns expert ids and weights, each (T, k)."""
    s = jax.nn.sigmoid((x @ w_router).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling
    return idx, w


def held_experts(p: Params, x: jax.Array, idx: jax.Array, weights: jax.Array,
                 first: int) -> jax.Array:
    """This chip's part of a routed-expert layer: the experts
    ``[first, first + G)`` that ``p`` holds, each a SiLU MLP, applied to
    the tokens routed to them, weighted and summed per token.  No token is
    dropped: every (token, choice) pair that lands on a held expert runs.

    ``p``: ``gate``/``up`` ``w`` (D, G, F) and ``down`` ``w`` (F, G, D) —
    fan-in first, experts second; x (T, D); idx, weights (T, k) over all
    routed experts.  The pairs are sorted by held expert (the rest last)
    and the held experts run as one grouped matmul per projection
    (``lax.ragged_dot``) over the sorted rows; rows past the held count
    are not computed, and are held at zero in both directions."""
    G = p["gate"]["w"].shape[1]
    counters["held_dropless"] += 1
    counters["experts_held"] += G
    T, k = idx.shape
    # XLA's TPU compiler gives its grouped-matmul kernel only a row count
    # that is a multiple of 8, and expands any other densely over every
    # held expert; on a v5e that expansion's results are wrong (its
    # gradients at the default precision by ~100%).  Pad with pairs that
    # land on no held expert
    pad = -(T * k) % 8
    local = jnp.concatenate([idx.reshape(-1) - first,
                             jnp.full((pad,), -1, idx.dtype)])
    held = (local >= 0) & (local < G)
    group = jnp.where(held, local, G)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=G + 1)[:G].astype(jnp.int32)
    rows = jnp.minimum(order // k, T - 1)               # token of each pair
    # the TPU kernel leaves rows past the groups unwritten, in its outputs
    # and in its gradients' rows: keep them out of both directions
    live = (jnp.arange(T * k + pad) < jnp.sum(sizes))[:, None]
    keep = lambda a: jnp.where(live, a, 0.0)
    gw = lambda name: jnp.transpose(p[name]["w"], (1, 0, 2))  # (G, in, out)
    xs = keep(x[rows])
    h = jax.nn.silu(keep(jax.lax.ragged_dot(xs, gw("gate"), sizes))) \
        * keep(jax.lax.ragged_dot(xs, gw("up"), sizes))
    y = keep(jax.lax.ragged_dot(h, gw("down"), sizes))
    w = jnp.concatenate([weights.reshape(-1), jnp.zeros((pad,), weights.dtype)]
                        )[order].astype(y.dtype)
    return jnp.zeros_like(x).at[rows].add(y * w[:, None])
