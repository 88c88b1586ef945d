"""Pallas TPU kernel for the SubTB(lambda) objective (paper Eq. 5).

The SubTB loss over one trajectory is a weighted sum over ALL O(T^2)
subtrajectory pairs.  With prefix sums c_t = cumsum(log_pf - log_pb) and
phi_t = log F(s_t) - c_t, the (j, k) residual is phi_j - phi_k, so the loss
is a pairwise quadratic form — a natural fit for (block x block) VMEM tiles
on the VPU, with the lambda^(k-j) weights generated from iota on the fly
instead of materializing a (T, T) weight matrix in HBM.

grid = (B, n_j, n_k) with the (j, k) tile axes sequential; the per-batch
numerator/denominator accumulate in VMEM scratch.  phi is passed twice: as
a (B, T+1, 1) column selected by the j tile and as a (B, 1, T+1) row
selected by the k tile, so ``phi_j - phi_k`` is a plain broadcast to the
(block, block) tile.  Trajectory lengths are scalar-prefetched into SMEM.

Validated against kernels.ref.ref_subtb.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret, round_up


def _subtb_kernel(len_ref, phi_j_ref, phi_k_ref, out_ref, num_scr, den_scr,
                  *, block: int, log_lam: float, n_blocks: int):
    b, jb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(jb == 0, kb == 0))
    def _init():
        num_scr[...] = jnp.zeros_like(num_scr)
        den_scr[...] = jnp.zeros_like(den_scr)

    phi_j = phi_j_ref[0].astype(jnp.float32)        # (block, 1)
    phi_k = phi_k_ref[0].astype(jnp.float32)        # (1, block)
    n = len_ref[b]

    j_idx = jb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block),
                                                  0)
    k_idx = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block),
                                                  1)
    valid = jnp.logical_and(j_idx < k_idx,
                            jnp.logical_and(j_idx <= n, k_idx <= n))
    w = jnp.where(valid,
                  jnp.exp((k_idx - j_idx).astype(jnp.float32) * log_lam), 0.0)
    resid = phi_j - phi_k

    def total(x):
        return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                       keepdims=True)                # (1, 1)

    num_scr[...] += total(w * resid * resid)
    den_scr[...] += total(w)

    @pl.when(jnp.logical_and(jb == n_blocks - 1, kb == n_blocks - 1))
    def _emit():
        out_ref[0] = num_scr[...] / jnp.maximum(den_scr[...], 1e-9)


def subtb_loss_pallas(phi: jax.Array, length: jax.Array, lam: float = 0.9,
                      block: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """phi: (B, T+1) flow-corrected potentials; length: (B,) trajectory
    lengths; returns (B,) per-trajectory normalized SubTB losses.

    ``block`` is rounded up to the 128-lane tile (the k tile is a row of
    the lane axis), and to no more than the padded trajectory length."""
    B, T1 = phi.shape
    block = min(round_up(block, 128), round_up(T1, 128))
    pad = (-T1) % block
    if pad:
        phi = jnp.pad(phi, ((0, 0), (0, pad)))
    n_blocks = phi.shape[1] // block

    kernel = functools.partial(_subtb_kernel, block=block,
                               log_lam=math.log(lam), n_blocks=n_blocks)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks, n_blocks),
            in_specs=[
                pl.BlockSpec((1, block, 1), lambda b, jb, kb, n: (b, jb, 0)),
                pl.BlockSpec((1, 1, block), lambda b, jb, kb, n: (b, 0, kb)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1), lambda b, jb, kb, n: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32),
                            pltpu.VMEM((1, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(length.astype(jnp.int32), phi[:, :, None], phi[:, None, :])
    return out[:, 0, 0]
