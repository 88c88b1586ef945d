"""Pallas TPU kernel for the RWKV6 wkv recurrence (chunked linear attention
with data-dependent per-channel decay).

TPU adaptation (DESIGN.md §4): the recurrence
    S_t = diag(w_t) S_{t-1} + k_t^T v_t;   o_t = r_t S_{t-1} + (r.u.k) v_t
is rewritten in chunk-parallel form so the inner work is MXU matmuls instead
of a length-T scalar chain:
    o  = (r * W_excl) @ S_in  +  tril_strict((r*W_excl)(k/W_incl)^T) @ v
         + diag((r*u).k) v
    S' = diag(W_last) S_in + (k/W_incl * W_last)^T @ v
with W_* = running products of decays inside the chunk (computed in
log-space for stability).  The chunk axis is the innermost sequential grid
dimension; the (Dk x Dv) state lives in VMEM scratch across chunk steps.

grid = (B, H, n_chunks); chunk default 64 keeps the cumulative-decay
product well above underflow at bf16 decays >= exp(-8).

Validated in interpret mode against kernels.ref.ref_rwkv6.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref,
                 s_scr, *, chunk: int, n_chunks: int, use_bonus: bool):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)             # (c, Dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)             # (c, Dv)
    w = w_ref[0, 0].astype(jnp.float32)             # (c, Dk) in (0, 1]

    logw = jnp.log(jnp.clip(w, 1e-8, 1.0))
    cum = jnp.cumsum(logw, axis=0)
    w_incl = jnp.exp(cum)                           # prod_{s<=t}
    w_excl = jnp.exp(cum - logw)                    # prod_{s<t}
    r_t = r * w_excl
    k_t = k / jnp.maximum(w_incl, 1e-30)

    S = s_scr[...]                                  # (Dk, Dv)
    o = r_t @ S                                     # inter-chunk (MXU)
    A = r_t @ k_t.T                                 # (c, c) intra-chunk
    c = r.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(col < row, A, 0.0)                # strict lower triangle
    o = o + A @ v
    if use_bonus:
        u = u_ref[0].astype(jnp.float32)            # (Dk,)
        diag = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)
        o = o + diag * v

    w_last = w_incl[-1]                             # (Dk,)
    s_scr[...] = w_last[:, None] * S + (k_t * w_last[None, :]).T @ v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        s_out_ref[0, 0] = s_scr[...]


def rwkv6_scan_pallas(r: jax.Array, k: jax.Array, v: jax.Array,
                      w: jax.Array, u: Optional[jax.Array] = None,
                      chunk: int = 64, interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """r/k/w: (B, T, H, Dk); v: (B, T, H, Dv); u: (H, Dk) or None.
    Returns (o: (B, T, H, Dv), state: (B, H, Dk, Dv)).  T padded to chunk."""
    B, T, H, Dk = r.shape
    Dv = v.shape[-1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        pad4 = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = pad4(r), pad4(k), pad4(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    Tp = T + pad
    n_chunks = Tp // chunk
    use_bonus = u is not None
    if u is None:
        u = jnp.zeros((H, Dk), r.dtype)

    # (B, T, H, D) -> (B, H, T, D)
    rt, kt, vt, wt = (jnp.swapaxes(x, 1, 2) for x in (r, k, v, w))

    kernel = functools.partial(_rwkv_kernel, chunk=chunk, n_chunks=n_chunks,
                               use_bonus=use_bonus)
    o, s_out = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, Dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, Dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, Dv), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, Dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, Dk), lambda b, h, ic: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, Dv), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, Dk, Dv), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, Dv), r.dtype),
            jax.ShapeDtypeStruct((B, H, Dk, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rt, kt, vt, wt, u)

    o = jnp.swapaxes(o, 1, 2)[:, :T]
    return o, s_out
