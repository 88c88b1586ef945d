"""Pallas TPU flash-attention kernel (GQA, causal, sliding-window).

TPU-native adaptation (DESIGN.md §4): q is tiled into (block_q x head_dim)
VMEM blocks; the kv sequence is the innermost *sequential* grid axis, so the
running-softmax state (m, l, acc) lives in VMEM scratch across kv steps —
the streaming-softmax recurrence mapped onto the TPU grid instead of a CUDA
thread-block loop.  Block shapes default to (128, 128): MXU-aligned for
bf16/fp32.

grid = (B, H, n_q_blocks, n_kv_blocks); GQA is expressed in the k/v
BlockSpec index maps (q head h reads kv head h // group_size), so no
repeated-KV materialization ever happens.

Validated in interpret mode against kernels.ref.ref_flash_attention
(``interpret=None`` lowers through Mosaic on the TPU, interprets elsewhere).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, sm_scale: float, causal: bool,
                  window: int, kv_len: Optional[int], n_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (block_k, d)
    v = v_ref[0, 0].astype(jnp.float32)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    if kv_len is not None:
        mask = jnp.logical_and(mask, k_pos < kv_len)

    s = (q @ k.T) * sm_scale                        # (block_q, block_k) MXU
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = corr * acc_scr[...] + p @ v
    m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           kv_len: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Skv, KVH, D).  Returns (B, Sq, H, D).

    Sq/Skv are padded to block multiples internally; GQA handled via the
    kv index map.  ``interpret=None`` picks Mosaic on the TPU and the
    interpreter elsewhere.
    """
    B, Sq, H, D = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Skv, 8))
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = Skv                     # mask the padding
    n_q = q.shape[1] // block_q
    n_kv = k.shape[1] // block_k

    # (B, S, H, D) -> (B, H, S, D) blocks
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        sm_scale=1.0 / (D ** 0.5), causal=causal, window=window,
        kv_len=kv_len, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)

    out = jnp.swapaxes(out, 1, 2)
    if pad_q:
        out = out[:, :Sq]
    return out
