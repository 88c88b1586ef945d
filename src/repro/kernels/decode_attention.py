"""Pallas TPU kernels for the incremental-decode rollout hot path.

Two kernels share this file:

``decode_attention_pallas`` — single-query attention.  The KV-cached rollout
fast path issues one query per environment per step against a growing
per-layer K/V cache (``core/rollout.py``'s cache-in-carry design).  That
access pattern — q: (B, H, hd) single rows, k/v: (B, S, H, hd) cache slots, a
per-batch valid-slot count — is the "decode" shape of LLM inference kernels:

  grid = (B_pad / R, n_kv_blocks) with the kv axis innermost *sequential*;
  each program owns a block of R batch rows in the merged-head (…, H*hd)
  layout and streams (R, block_k, D) K/V tiles HBM -> VMEM while the
  running-softmax state (m, l, acc), (R, D) each, lives in VMEM scratch
  across kv steps.  The block is attended with vector operations over all
  R rows at once: one ``(R * block_k, D) @ E`` matmul for the scores, then
  the masked max, exp and sums over the slot axis.  R is the batch padded
  to the 8-row f32 sublane tile, unless one (R, block_k, D) K or V block
  would pass ``_KV_BLOCK_BYTES`` of VMEM; the batch is then split into the
  fewest equal 8-aligned blocks under that cap (``_row_block``: four
  64-row blocks for bitseq's 16-slot cache at 256 envs, one 16-row block
  at 16 envs, 16-row blocks for AMP's 61-slot cache).  Slots at or beyond
  ``kv_valid[b]`` (an (R, 1, 1) VMEM block per program) are masked before
  the streaming max/sum update, so cache capacity can exceed the live
  prefix.  Rows with ``kv_valid == 0`` return a defined all-zero output.
  ``counters`` records the R of each traced call.

``decode_step_pallas`` — the fused decode STEP.  One program per 8
environments executes the *entire* cached-rollout inner loop that
``core/rollout.py`` otherwise issues as a chain of small XLA ops:

  1. append:  K/V projections of the new token's embedding land in the
     stacked cache ``(num_layers, B, capacity, D)`` at ``slot[b]``;
  2. query:   the latent query (``q0``) runs through every decoder layer,
     cross-attending to the just-updated cache masked to
     ``lengths[b] + 1`` valid slots (BOS + tokens);
  3. readout + sample: forward-action logits, action-mask + log-softmax,
     and a Gumbel-max draw (the caller precomputes the Gumbel noise from
     the same key ``jax.random.categorical`` would consume, so kernel
     sampling matches the jnp path's draws);
  4. it returns ``(action, log_pf, y, new_k, new_v)`` — everything the
     scan body needs to advance the env and the TB/DB accumulators.

Both kernels use one layout trick so that no head is ever sliced out of the
lane axis: a (D, D) block-diagonal head indicator ``E`` (``E[i, j] = 1`` iff
lanes i and j belong to the same head) turns the elementwise product
``k * q`` into per-head scores broadcast over their head's lanes with one
MXU matmul, ``(k * q) @ E``.  The softmax then runs over the cache (sublane)
axis on full-width (C, D) tiles, and ``sum(p * v)`` over that axis is the
attention output, already in the merged-head layout.

The fused-step contract mirrors ``kernels.ref.ref_decode_step``;
``kernels.ops.decode_step`` is the jitted entry that reshapes the
(Lyr, B, C, H, hd) transformer cache into the kernel's merged-head layout.
The kernels lower through Mosaic on the TPU and run in interpret mode
elsewhere (``interpret=None`` picks by platform).
"""
from __future__ import annotations

import functools
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret, round_up

NEG_INF = -1e30
ROWS = 8                       # batch rows per program: one f32 sublane tile
_KV_BLOCK_BYTES = 512 << 10    # VMEM cap on one decode_attention K or V block
_HIGHEST = jax.lax.Precision.HIGHEST


#: Rows per program of each traced ``decode_attention_pallas`` call
#: (``rows_<R>``), counted at trace time, as ``core.objectives.counters``.
counters: Counter = Counter()


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _head_indicator(dim: int, num_heads: int) -> jax.Array:
    """(D, D) float32 block-diagonal ones: lanes i, j share a head."""
    head = jnp.arange(dim) // (dim // num_heads)
    return (head[:, None] == head[None, :]).astype(jnp.float32)


def _pad_axis(x, axis: int, size: int):
    """Zero-pad ``axis`` of ``x`` up to ``size``."""
    pad = size - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, e_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k: int, sm_scale: float, n_kv: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    rows, dim = q_ref.shape
    q = q_ref[...].astype(jnp.float32)                       # (R, D)
    k = k_ref[...].astype(jnp.float32)                       # (R, block_k, D)
    v = v_ref[...].astype(jnp.float32)
    pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                  (1, block_k, 1), 1)
    live = pos < len_ref[...]                                # (R, block_k, 1)
    kq = (k * q[:, None, :]).reshape(rows * block_k, dim)
    s = _dot(kq, e_ref[...]).reshape(rows, block_k, dim) * sm_scale
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # re-mask after the exp: a block with no live slot has
    # m_new == NEG_INF and exp(s - m_new) == 1 on its masked slots;
    # zeroing p keeps (l, acc) an empty sum, so rows with
    # kv_valid == 0 finalize to a defined zero output
    p = jnp.where(live, jnp.exp(s - m_new[:, None, :]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1)
    acc_scr[...] = corr * acc_scr[...] + jnp.sum(p * v, axis=1)
    m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def _row_block(batch: int, block_k: int, dim: int) -> int:
    """Batch rows per ``decode_attention`` program.

    The whole batch, padded to the 8-row f32 sublane tile, unless one
    (R, block_k, D) K or V block (f32, lanes padded to 128) would pass
    ``_KV_BLOCK_BYTES``; the batch is then split into the fewest blocks
    under that cap, of equal 8-row-aligned size.  The cap is small enough
    that the next block's K/V copy overlaps this block's compute: on a TPU
    v5e, four 64-row blocks over a 256 x 16-slot cache run faster than one
    256-row block, and faster than 8-, 32- or 128-row blocks.
    """
    rows = round_up(batch, ROWS)
    per_row = block_k * round_up(dim, 128) * 4
    cap = max(ROWS, _KV_BLOCK_BYTES // per_row // ROWS * ROWS)
    n_blocks = -(-rows // cap)
    return round_up(-(-rows // n_blocks), ROWS)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_valid: jax.Array, *, block_k: int = 128,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, hd); k/v: (B, S, H, hd); kv_valid: (B,) valid slot counts.

    Returns (B, H, hd).  The batch is padded to a multiple of the row block
    (``_row_block``) and the cache axis to a ``block_k`` multiple
    internally; padded slots are masked by the valid-count check.  Rows
    with ``kv_valid[b] == 0`` get an all-zero output row (defined, not
    NaN/garbage).
    """
    B, S, H, hd = k.shape
    D = H * hd
    # clamp the block to the cache length *rounded up to the 8-sublane f32
    # tile* — min(block_k, S) alone would yield unaligned blocks for
    # S % 8 != 0 and an oversized block (block_k > S) for S < 8
    block_k = min(block_k, round_up(max(S, 1), 8))
    R = _row_block(B, block_k, D)
    counters[f"rows_{R}"] += 1
    Sp, Bp = round_up(S, block_k), round_up(B, R)
    qm = _pad_axis(q.reshape(B, D), 0, Bp)
    km = _pad_axis(_pad_axis(k.reshape(B, S, D), 1, Sp), 0, Bp)
    vm = _pad_axis(_pad_axis(v.reshape(B, S, D), 1, Sp), 0, Bp)
    lens = _pad_axis(kv_valid.astype(jnp.int32).reshape(B, 1, 1), 0, Bp)
    n_kv = Sp // block_k

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               sm_scale=1.0 / (hd ** 0.5), n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // R, n_kv),
        in_specs=[
            pl.BlockSpec((R, 1, 1), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((R, D), lambda b, ik: (b, 0)),
            pl.BlockSpec((R, block_k, D), lambda b, ik: (b, ik, 0)),
            pl.BlockSpec((R, block_k, D), lambda b, ik: (b, ik, 0)),
            pl.BlockSpec((D, D), lambda b, ik: (0, 0)),
        ],
        out_specs=pl.BlockSpec((R, D), lambda b, ik: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, D), jnp.float32),    # running max m
            pltpu.VMEM((R, D), jnp.float32),    # running denom l
            pltpu.VMEM((R, D), jnp.float32),    # output accumulator
        ],
        out_shape=jax.ShapeDtypeStruct((Bp, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lens, qm, km, vm, _head_indicator(D, H))
    return out[:B].reshape(B, H, hd)


# ===========================================================================
# Fused decode STEP: append + all-layer latent query + masked Gumbel sampling
# ===========================================================================

def _layernorm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _step_kernel(len_ref, slot_ref, temp_ref, x_ref, kc_ref, vc_ref,
                 gum_ref, mask_ref, e_ref,
                 ln1s_ref, ln1b_ref, qw_ref, qb_ref, kw_ref, kb_ref,
                 vw_ref, vb_ref, pw_ref, pb_ref, ln2s_ref, ln2b_ref,
                 f1w_ref, f1b_ref, f2w_ref, f2b_ref, lnfs_ref, lnfb_ref,
                 q0_ref, wout_ref, bout_ref,
                 act_ref, lp_ref, y_ref, kco_ref, vco_ref, o_scr, *,
                 num_layers: int, sm_scale: float):
    ib = pl.program_id(0)
    C = kc_ref.shape[-2]
    f32 = jnp.float32
    row = lambda ref, l: ref[l:l + 1, :].astype(f32)          # (1, n)

    x = x_ref[...].astype(f32)                                 # (ROWS, D)
    e = e_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    h = jnp.broadcast_to(q0_ref[...].astype(f32), x.shape)
    for l in range(num_layers):
        # --- 1. append this layer's K/V of the new token at `slot` -------
        kn = _dot(x, kw_ref[l].astype(f32)) + row(kb_ref, l)
        vn = _dot(x, vw_ref[l].astype(f32)) + row(vb_ref, l)
        # --- 2. latent query against the just-updated cache --------------
        g = _layernorm(h, row(ln1s_ref, l), row(ln1b_ref, l))
        q = _dot(g, qw_ref[l].astype(f32)) + row(qb_ref, l)
        for r in range(ROWS):
            at = pos == slot_ref[ib * ROWS + r]                # (C, 1)
            k = jnp.where(at, kn[r:r + 1], kc_ref[l, r].astype(f32))
            v = jnp.where(at, vn[r:r + 1], vc_ref[l, r].astype(f32))
            kco_ref[l, r] = k.astype(kco_ref.dtype)
            vco_ref[l, r] = v.astype(vco_ref.dtype)
            live = pos < len_ref[ib * ROWS + r] + 1            # + BOS slot
            s = jnp.where(live, _dot(k * q[r:r + 1], e) * sm_scale, NEG_INF)
            p = jnp.where(live, jnp.exp(s - jnp.max(s, axis=0,
                                                    keepdims=True)), 0.0)
            o_scr[r:r + 1, :] = (jnp.sum(p * v, axis=0, keepdims=True)
                                 / jnp.maximum(jnp.sum(p, axis=0,
                                                       keepdims=True), 1e-30))
        h = h + _dot(o_scr[...], pw_ref[l].astype(f32)) + row(pb_ref, l)
        g2 = _layernorm(h, row(ln2s_ref, l), row(ln2b_ref, l))
        ff = jax.nn.gelu(_dot(g2, f1w_ref[l].astype(f32)) + row(f1b_ref, l))
        h = h + _dot(ff, f2w_ref[l].astype(f32)) + row(f2b_ref, l)
    y = _layernorm(h, lnfs_ref[...].astype(f32), lnfb_ref[...].astype(f32))
    y_ref[...] = y.astype(y_ref.dtype)

    # --- 3. readout + masked log-softmax + Gumbel-max sample -------------
    logits = (_dot(y, wout_ref[...].astype(f32))
              + bout_ref[...].astype(f32)) * temp_ref[...]   # (ROWS, A)
    ml = jnp.where(mask_ref[...] != 0, logits, jnp.finfo(f32).min)
    m = jnp.max(ml, axis=-1, keepdims=True)
    logp = ml - (m + jnp.log(jnp.sum(jnp.exp(ml - m), axis=-1,
                                     keepdims=True)))
    score = logp + gum_ref[...].astype(f32)
    # first index of the maximum (jnp.argmax's tie rule), as a float lane
    # reduction: action ids stay exact in f32 far beyond any action count
    aidx = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1).astype(f32)
    best = jnp.max(score, axis=-1, keepdims=True)
    a = jnp.min(jnp.where(score == best, aidx, float(score.shape[-1])),
                axis=-1, keepdims=True)
    act_ref[...] = a.astype(jnp.int32)
    lp_ref[...] = jnp.sum(jnp.where(aidx == a, logp, 0.0), axis=-1,
                          keepdims=True)


def decode_step_pallas(w, x_new: jax.Array, k_cache: jax.Array,
                       v_cache: jax.Array, lengths: jax.Array,
                       slot: jax.Array, gumbel: jax.Array,
                       action_mask: jax.Array, w_out: jax.Array,
                       b_out: jax.Array,
                       logit_temp: Optional[jax.Array] = None, *,
                       num_heads: int, interpret: Optional[bool] = None):
    """One fused cached-rollout step per environment (see module docstring).

    w:           stacked decoder weights (``nn.transformer
                 .decoder_stacked_weights``), merged-head (…, D) layout;
    x_new:       (B, D) new-token embedding;
    k/v_cache:   (num_layers, B, C, D) stacked cache, heads merged;
    lengths:     (B,) live token counts (kv_valid = lengths + 1 incl. BOS);
    slot:        (B,) per-row write slots;
    gumbel:      (B, A) Gumbel noise from the categorical-sampling key;
    action_mask: (B, A) nonzero = legal action;
    w_out/b_out: (D, A)/(A,) forward-logits readout slice;
    logit_temp:  optional (B,) per-row logit scale applied before the mask
                 (the serve tier's tempered lanes; None = 1).

    The batch is padded to a multiple of 8 rows and the cache capacity to
    a multiple of 8 slots internally (padded slots are never live).

    Returns ``(action (B,) i32, log_pf (B,) f32, y (B, D), new_k, new_v)``.
    """
    L, B, C, D = k_cache.shape
    A = action_mask.shape[-1]
    F = w["ff1_w"].shape[-1]
    Bp, Cp = round_up(B, ROWS), round_up(C, 8)
    if logit_temp is None:
        logit_temp = jnp.ones((B,), jnp.float32)
    rows = lambda a: _pad_axis(a, 0, Bp)
    cache = lambda c: _pad_axis(_pad_axis(c, 2, Cp), 1, Bp)

    def fixed(*shape):  # broadcast operand: same block for every program
        return pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))

    def batched(*shape):  # ROWS rows of a (B, ...) operand
        return pl.BlockSpec((ROWS,) + shape,
                            lambda b, *_: (b,) + (0,) * len(shape))

    cache_spec = pl.BlockSpec((L, ROWS, Cp, D), lambda b, *_: (0, b, 0, 0))
    kernel = functools.partial(_step_kernel, num_layers=L,
                               sm_scale=1.0 / ((D // num_heads) ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bp // ROWS,),
            in_specs=[
                batched(1),                                    # logit_temp
                batched(D),                                    # x_new
                cache_spec, cache_spec,                        # k/v cache
                batched(A), batched(A),                        # gumbel, mask
                fixed(D, D),                                   # head map
                fixed(L, D), fixed(L, D),                      # ln1
                fixed(L, D, D), fixed(L, D),                   # q
                fixed(L, D, D), fixed(L, D),                   # k
                fixed(L, D, D), fixed(L, D),                   # v
                fixed(L, D, D), fixed(L, D),                   # proj
                fixed(L, D), fixed(L, D),                      # ln2
                fixed(L, D, F), fixed(L, F),                   # ff1
                fixed(L, F, D), fixed(L, D),                   # ff2
                fixed(1, D), fixed(1, D),                      # ln_f
                fixed(1, D),                                   # q0
                fixed(D, A), fixed(1, A),                      # readout
            ],
            out_specs=[batched(1), batched(1), batched(D),
                       cache_spec, cache_spec],
            scratch_shapes=[pltpu.VMEM((ROWS, D), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, D), x_new.dtype),
            jax.ShapeDtypeStruct((L, Bp, Cp, D), k_cache.dtype),
            jax.ShapeDtypeStruct((L, Bp, Cp, D), v_cache.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(rows(lengths.astype(jnp.int32)), rows(slot.astype(jnp.int32)),
      rows(logit_temp.astype(jnp.float32).reshape(B, 1)), rows(x_new),
      cache(k_cache), cache(v_cache), rows(gumbel),
      rows((action_mask != 0).astype(jnp.int32)), _head_indicator(D, num_heads),
      w["ln1_scale"], w["ln1_bias"], w["q_w"], w["q_b"],
      w["kv_w"][..., :D], w["kv_b"][..., :D],
      w["kv_w"][..., D:], w["kv_b"][..., D:],
      w["proj_w"], w["proj_b"],
      w["ln2_scale"], w["ln2_bias"], w["ff1_w"], w["ff1_b"],
      w["ff2_w"], w["ff2_b"],
      w["ln_f_scale"].reshape(1, D), w["ln_f_bias"].reshape(1, D),
      w["q0"].reshape(1, D), w_out, b_out.reshape(1, A))

    action, log_pf, y, new_k, new_v = out
    return (action[:B, 0], log_pf[:B, 0], y[:B], new_k[:, :B, :C],
            new_v[:, :B, :C])
