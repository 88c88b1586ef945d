"""Pallas TPU kernel for in-kernel TB/DB log-probability accumulation.

The TB and DB objectives both reduce per-step action log-probabilities over
a trajectory: ``sum_t valid_t * log softmax(masked logits_t)[action_t]``.
The jnp path materializes the full (T, B, A) log-softmax tensor and gathers
from it; this kernel fuses mask + log-softmax + gather + the trajectory
reduction into one pass per environment, so the (T, A) logits tile is read
once and only a scalar per trajectory leaves the program:

  grid = (B, n_t_blocks) with the time axis innermost *sequential*; each
  program streams (block_t x A) logits/mask tiles while the running sum
  lives in VMEM scratch.  Per-step operands (actions, valid flags, the
  per-step output) travel as (B, T, 1) columns, so time sits on sublanes
  next to the logits tile and every block's trailing dims are tile-legal.
  The action gather is an iota-match (no dynamic indexing), masked slots
  sit at float32 min before the stable logsumexp — matching
  ``core.types.masked_logprobs`` — and steps with ``valid == 0`` contribute
  exactly zero.

``kernels.ops.traj_logprob`` wraps this with a custom VJP (softmax-minus-
one-hot closed form) so the TB/DB training path can lower through it on
TPU; ``kernels.ref.ref_traj_logprob`` is the oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret, round_up


def _tl_kernel(logits_ref, act_ref, mask_ref, valid_ref, out_ref, step_ref,
               acc_scr, *, n_t: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = logits_ref[0].astype(jnp.float32)                   # (block_t, A)
    ml = jnp.where(mask_ref[0] != 0, x, jnp.finfo(jnp.float32).min)
    m = jnp.max(ml, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(ml - m), axis=-1, keepdims=True))
    aidx = jax.lax.broadcasted_iota(jnp.int32, ml.shape, 1)
    hit = aidx == act_ref[0]                                # (block_t, A)
    lpa = jnp.sum(jnp.where(hit, ml - lse, 0.0), axis=-1,
                  keepdims=True)                            # (block_t, 1)
    lpa = jnp.where(valid_ref[0] != 0, lpa, 0.0)            # time padding too
    step_ref[0] = lpa
    acc_scr[...] += jnp.sum(lpa, axis=0, keepdims=True)

    @pl.when(it == n_t - 1)
    def _finalize():
        out_ref[0] = acc_scr[...]


#: largest (block_t, A) float32 logits tile: 1 MiB, a sixteenth of the
#: 16 MiB scoped VMEM a v5e kernel gets by default
_TILE_BYTES = 1 << 20


def traj_logprob_pallas(logits: jax.Array, actions: jax.Array,
                        mask: jax.Array, valid: jax.Array, *,
                        block_t: int = 128,
                        interpret: Optional[bool] = None):
    """logits: (B, T, A); actions: (B, T) int; mask: (B, T, A) nonzero=legal;
    valid: (B, T) nonzero=live.  Returns ``(total (B,), per_step (B, T))``
    — the accumulated log-prob (TB) and the fused per-transition gathered
    log-probs (DB), zero where ``valid == 0``.

    The time axis is padded to a ``block_t`` multiple internally; padded
    steps carry ``valid == 0`` and contribute nothing.  ``block_t`` is cut
    to a multiple of 8 that keeps one (block_t, A) float32 tile within
    ``_TILE_BYTES``, so that wide action spaces (an LM vocabulary) fit the
    kernel's scoped VMEM with its double buffers and temporaries.
    """
    B, T, A = logits.shape
    fit = max(8, _TILE_BYTES // (4 * A) // 8 * 8)
    block_t = min(block_t, round_up(max(T, 1), 8), fit)
    pad_t = (-T) % block_t
    actions = actions.astype(jnp.int32)[..., None]
    maski = (mask != 0).astype(jnp.int32)
    validi = (valid != 0).astype(jnp.int32)[..., None]
    if pad_t:
        logits = jnp.pad(logits, ((0, 0), (0, pad_t), (0, 0)))
        actions = jnp.pad(actions, ((0, 0), (0, pad_t), (0, 0)))
        maski = jnp.pad(maski, ((0, 0), (0, pad_t), (0, 0)),
                        constant_values=1)  # keep the lse finite
        validi = jnp.pad(validi, ((0, 0), (0, pad_t), (0, 0)))
    Tp = logits.shape[1]
    n_t = Tp // block_t

    steps = lambda b, it: (b, it, 0)
    total, per_step = pl.pallas_call(
        functools.partial(_tl_kernel, n_t=n_t),
        grid=(B, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, A), steps),
            pl.BlockSpec((1, block_t, 1), steps),
            pl.BlockSpec((1, block_t, A), steps),
            pl.BlockSpec((1, block_t, 1), steps),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, it: (b, 0, 0)),
            pl.BlockSpec((1, block_t, 1), steps),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Tp, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(logits, actions, maski, validi)
    return total[:, 0, 0], per_step[:, :T, 0]
