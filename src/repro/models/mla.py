"""Multi-head latent attention (DeepSeek-V2/V3 MLA with ``q_lora_rank``
null, as Moonlight-16B-A3B configures it).

Per token x (width D):

  q            = x W_q                     -> H x (nope + rope)
  [c, k_pe]    = x W_kv_a                  -> rank + rope
  c            = RMSNorm(c)
  [k_nope, v]  = c W_kv_b                  -> H x (nope + v_dim)
  q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe)    (k_pe is shared by the heads)
  out          = softmax([q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope))
                 v  W_o

The cache holds only ``c`` (after its norm) and ``k_pe`` (after RoPE): rank
+ rope numbers per token and layer, not per head.  Two paths:

- ``expanded``: prefill and the teacher-forced pass up-project every
  token's ``c`` to per-head keys and values and attend causally, after an
  optional prefix shared by every row (its latents at batch 1, up-projected
  once);
- ``latent_decode``: one new token per row attends over the latent cache
  with ``W_kv_b`` absorbed into the query and the output (``q_nope W_uk``
  scores against ``c``; the weighted ``c`` is mapped by ``W_uv``), so a
  decode step never expands the cache.

RoPE rotates the two halves of the rope dimension (``rotate_half``);
DeepSeek's code first de-interleaves the rope columns, a fixed permutation
of the columns of ``W_q`` and ``W_kv_a`` that random weights do not see.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..nn.core import Params
from .layers import rmsnorm

#: Which attention path each traced MLA call took: ``latent_decode`` (one
#: token per row against the latent cache, ``W_kv_b`` absorbed) or
#: ``expanded`` (keys and values up-projected from ``c``); and
#: ``shared_prefix``, each traced pass that runs a prompt once at batch 1
#: for every row (``models.mla_moe.shared_prefix``).  Counted at trace
#: time, as ``core.objectives.counters``.
counters: Dict[str, int] = {"latent_decode": 0, "expanded": 0,
                            "shared_prefix": 0}


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE over the last axis of ``x`` (..., r); ``positions``
    broadcasts against ``x``'s leading axes."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    half = r // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return (x * cos + rot * sin).astype(x.dtype)


def mla_shapes(cfg) -> Params:
    H = cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {"q": {"w": (cfg.hidden_size, H * qk)},
            "kv_a": {"w": (cfg.hidden_size,
                           cfg.kv_lora_rank + cfg.qk_rope_head_dim)},
            "kv_norm": {"scale": (cfg.kv_lora_rank,)},
            "kv_b": {"w": (cfg.kv_lora_rank,
                           H * (cfg.qk_nope_head_dim + cfg.v_head_dim))},
            "o": {"w": (H * cfg.v_head_dim, cfg.hidden_size)}}


def _queries(p, x, positions, cfg):
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    q = (x @ p["q"]["w"]).reshape(x.shape[:-1] + (H, -1))
    return q[..., :nope], rope(q[..., nope:], positions[..., None],
                               cfg.rope_theta)


def latents(p, x, positions, cfg):
    """The cache entries of tokens ``x`` (..., S, D) at ``positions``:
    normed ``c`` (..., S, rank) and rotated ``k_pe`` (..., S, rope)."""
    kv = x @ p["kv_a"]["w"]
    c = rmsnorm(p["kv_norm"], kv[..., :cfg.kv_lora_rank], cfg.rms_norm_eps)
    k_pe = rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    return c, k_pe


def _scale(cfg):
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_expanded(p, x, positions, cfg, prefix=None):
    """Causal attention over ``x`` (B, S, D) at ``positions`` (S,), with
    keys and values up-projected from the latents, after ``prefix``: the
    latents (c (1, P', rank), k_pe (1, P', rope)) of positions ``0..P'-1``
    shared by every row (none by default), whose keys and values are
    up-projected once and scored apart from the rows' own, under one
    softmax, so they are never copied per row.  ``positions`` then start at
    ``P'``.  Returns the output (B, S, D) and the rows' latents (c, k_pe)
    the cache keeps."""
    counters["expanded"] += 1
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    B, S, _ = x.shape
    w_kv_b = p["kv_b"]["w"]
    q_nope, q_pe = _queries(p, x, positions, cfg)
    c, k_pe = latents(p, x, positions, cfg)
    c_pre, kpe_pre = (c[0, :0], k_pe[0, :0]) if prefix is None else \
        (prefix[0][0], prefix[1][0])
    n = c_pre.shape[0]
    kv = (c @ w_kv_b).reshape(B, S, H, -1)
    kv_pre = (c_pre @ w_kv_b).reshape(n, H, kv.shape[-1])
    causal = positions[:, None] >= positions[None, :]
    s = jnp.concatenate(
        [jnp.einsum("bqhd,khd->bhqk", q_nope, kv_pre[..., :nope])
         + jnp.einsum("bqhr,kr->bhqk", q_pe, kpe_pre),
         jnp.where(causal, jnp.einsum("bqhd,bkhd->bhqk", q_nope,
                                      kv[..., :nope])
                   + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe), -jnp.inf)],
        axis=-1) * _scale(cfg)
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    o = (jnp.einsum("bhqk,khd->bqhd", a[..., :n], kv_pre[..., nope:])
         + jnp.einsum("bhqk,bkhd->bqhd", a[..., n:], kv[..., nope:]))
    return o.reshape(B, S, -1) @ p["o"]["w"], (c, k_pe)


def mla_latent_decode(p, x, slot, c_cache, kpe_cache, cfg):
    """One token per row: ``x`` (B, D) at position ``slot`` (a scalar, the
    rows move in lockstep) attends over slots ``[0, slot]`` of the latent
    caches ``c_cache`` (B, C, rank) and ``kpe_cache`` (B, C, rope), which
    already hold its own entries (``latents``), with ``W_kv_b`` absorbed.
    Returns the output (B, D)."""
    counters["latent_decode"] += 1
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    rank = cfg.kv_lora_rank
    q_nope, q_pe = _queries(p, x, jnp.reshape(slot, ()), cfg)  # (B, H, .)
    w_kv_b = p["kv_b"]["w"].reshape(rank, H, -1)
    w_uk, w_uv = w_kv_b[..., :nope], w_kv_b[..., nope:]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)        # (B, H, rank)
    s = (jnp.einsum("bhc,bkc->bhk", q_lat, c_cache)
         + jnp.einsum("bhr,bkr->bhk", q_pe, kpe_cache)) * _scale(cfg)
    live = jnp.arange(c_cache.shape[1]) <= slot
    s = jnp.where(live[None, None, :], s, -jnp.inf)
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhk,bkc->bhc", a, c_cache)
    o = jnp.einsum("bhc,chd->bhd", ctx, w_uv).reshape(x.shape[0], -1)
    return o @ p["o"]["w"]
