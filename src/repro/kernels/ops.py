"""Jitted public wrappers for the Pallas kernels (the ``ops.py`` contract).

Every wrapper lowers its kernel through Mosaic when the default backend is
the TPU and runs the same kernel in the Pallas interpreter on any other
platform (:func:`repro.kernels.resolve_interpret`); there is no switch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_pallas, decode_step_pallas
from .flash_attention import flash_attention_pallas
from .ref import ref_decode_attention
from .rwkv6_scan import rwkv6_scan_pallas
from .subtb_loss import subtb_loss_pallas
from .traj_logprob import traj_logprob_pallas

@functools.partial(jax.jit, static_argnames=("causal", "window", "kv_len",
                                             "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    kv_len: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """GQA flash attention.  q: (B, Sq, H, D); k/v: (B, Skv, KVH, D)."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  kv_len=kv_len, block_q=block_q,
                                  block_k=block_k)


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_valid: jax.Array, *, block_k: int = 128) -> jax.Array:
    """Single-query decode attention against a KV cache.

    q: (B, H, D); k/v: (B, S, H, D); kv_valid: (B,) valid slot counts."""
    return decode_attention_pallas(q, k, v, kv_valid, block_k=block_k)


def decode_attention_grad(q: jax.Array, k: jax.Array, v: jax.Array,
                          kv_valid: jax.Array, *,
                          block_k: int = 128) -> jax.Array:
    """:func:`decode_attention` with a custom VJP — the Pallas forward has
    no gradient rule of its own, so the backward differentiates the dense
    ``ref_decode_attention`` oracle (identical function, jnp ops).  This is
    the entry for training-path cache queries (backward replay re-evaluates
    trajectories through the same cached attention the rollout used)."""

    @jax.custom_vjp
    def f(q, k, v):
        return decode_attention(q, k, v, kv_valid, block_k=block_k)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: ref_decode_attention(q_, k_, v_, kv_valid),
            q, k, v)
        return vjp_fn(g)

    f.defvjp(fwd, bwd)
    return f(q, k, v)


@functools.partial(jax.jit, static_argnames=("num_heads",))
def decode_step(w, x_new: jax.Array, cache, lengths: jax.Array,
                slot: jax.Array, gumbel: jax.Array, action_mask: jax.Array,
                w_out: jax.Array, b_out: jax.Array,
                logit_temp: Optional[jax.Array] = None, *, num_heads: int):
    """Fused cached-rollout step: cache append + latent-query decode +
    masked Gumbel-max sampling in one Pallas program per 8 environments.

    ``cache`` is the transformer-layout stacked pair ``{"k", "v"}`` of
    (num_layers, B, C, H, hd) arrays; this wrapper merges the head axes for
    the kernel and restores them on the way out.  ``slot`` may be scalar
    (lockstep rollouts) or (B,) (serve lanes); ``logit_temp`` an optional
    (B,) per-row logit scale (tempered serve lanes).  Returns
    ``(action, log_pf, y, new_cache)``.
    """
    L, B, C, H, hd = cache["k"].shape
    D = H * hd
    slot = jnp.broadcast_to(slot, (B,))
    action, log_pf, y, new_k, new_v = decode_step_pallas(
        w, x_new, cache["k"].reshape(L, B, C, D),
        cache["v"].reshape(L, B, C, D), lengths, slot, gumbel, action_mask,
        w_out, b_out, logit_temp, num_heads=num_heads)
    return action, log_pf, y, {"k": new_k.reshape(L, B, C, H, hd),
                               "v": new_v.reshape(L, B, C, H, hd)}


def traj_logprob(logits: jax.Array, actions: jax.Array, mask: jax.Array,
                 valid: jax.Array, *, block_t: int = 128):
    """In-kernel TB/DB log-prob accumulation with a closed-form custom VJP.

    logits: (B, T, A); actions: (B, T); mask: (B, T, A); valid: (B, T).
    Returns ``(total (B,), per_step (B, T))`` — mask + log-softmax + action
    gather + trajectory reduction fused in one Pallas pass (TB consumes the
    total, DB the per-step terms).  Gradients flow to ``logits`` only:
    d/dlogits = (g_total + g_step) * valid * (onehot - softmax).
    """

    @jax.custom_vjp
    def f(lg):
        return traj_logprob_pallas(lg, actions, mask, valid,
                                   block_t=block_t)

    def fwd(lg):
        return f(lg), lg

    def bwd(lg, g):
        g_total, g_step = g
        neg = jnp.finfo(jnp.float32).min
        ml = jnp.where(mask != 0, lg.astype(jnp.float32), neg)
        p = jax.nn.softmax(ml, axis=-1)
        onehot = jax.nn.one_hot(actions, lg.shape[-1], dtype=jnp.float32)
        coeff = (g_total[:, None] + g_step) * (valid != 0)
        d = coeff[..., None] * (onehot - p)
        return (d.astype(lg.dtype),)

    f.defvjp(fwd, bwd)
    return f(logits)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: Optional[jax.Array] = None, chunk: int = 64
               ) -> Tuple[jax.Array, jax.Array]:
    """RWKV6 wkv recurrence; returns (out, final_state)."""
    return rwkv6_scan_pallas(r, k, v, w, u, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("lam", "block"))
def subtb_loss(phi: jax.Array, length: jax.Array, lam: float = 0.9,
               block: int = 128) -> jax.Array:
    """Per-trajectory SubTB(lambda) losses from potentials phi (B, T+1)."""
    return subtb_loss_pallas(phi, length, lam=lam, block=block)
