"""Small transformer encoder used by GFlowNet sequence policies.

Mirrors the paper's policy parameterization for bit-sequences / AMP /
phylogenetic trees: N encoder layers, multi-head attention, GELU MLP,
pre-LayerNorm, no dropout at inference (the paper uses dropout 0 everywhere
except phylo's 0.01, which we support but default off; dropout under jit uses
an explicit rng).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .core import (Params, dense_apply, dense_init, layernorm_apply,
                   layernorm_init, normal_init)


def encoder_init(key: jax.Array, *, num_layers: int, dim: int, num_heads: int,
                 ff_dim: Optional[int] = None, dtype=jnp.float32) -> Params:
    ff_dim = ff_dim if ff_dim is not None else 4 * dim
    keys = jax.random.split(key, num_layers)
    layers = {}
    for i, k in enumerate(keys):
        ks = jax.random.split(k, 4)
        layers[f"layer_{i}"] = {
            "ln1": layernorm_init(dim, dtype),
            "qkv": dense_init(ks[0], dim, 3 * dim, dtype=dtype),
            "proj": dense_init(ks[1], dim, dim, dtype=dtype),
            "ln2": layernorm_init(dim, dtype),
            "ff1": dense_init(ks[2], dim, ff_dim, dtype=dtype),
            "ff2": dense_init(ks[3], ff_dim, dim, dtype=dtype),
        }
    layers["ln_f"] = layernorm_init(dim, dtype)
    return layers


def _mha(p: Params, x: jax.Array, num_heads: int,
         mask: Optional[jax.Array], causal: bool) -> jax.Array:
    B, S, D = x.shape
    hd = D // num_heads
    qkv = dense_apply(p["qkv"], x).reshape(B, S, 3, num_heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(hd).astype(x.dtype)
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    if causal:
        cm = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(cm[None, None], logits, neg)
    if mask is not None:
        # mask: (B, S) validity of keys
        logits = jnp.where(mask[:, None, None, :], logits, neg)
    attn = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(B, S, D)
    return dense_apply(p["proj"], out)


def encoder_apply(p: Params, x: jax.Array, *, num_heads: int,
                  mask: Optional[jax.Array] = None,
                  causal: bool = False) -> jax.Array:
    """x: (B, S, D) token embeddings; mask: (B, S) True=valid."""
    num_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(num_layers):
        lp = p[f"layer_{i}"]
        x = x + _mha(lp, layernorm_apply(lp["ln1"], x), num_heads, mask, causal)
        h = layernorm_apply(lp["ln2"], x)
        h = dense_apply(lp["ff2"], jax.nn.gelu(dense_apply(lp["ff1"], h)))
        x = x + h
    return layernorm_apply(p["ln_f"], x)


def positional_embedding_init(key: jax.Array, max_len: int, dim: int,
                              dtype=jnp.float32) -> Params:
    return {"pos": normal_init(key, (max_len, dim), std=0.02, dtype=dtype)}


# ===========================================================================
# Incremental-decode (latent-query) encoder with a per-layer KV cache
# ===========================================================================
#
# The rollout fast path needs a policy whose per-step cost does not re-encode
# the whole padded sequence.  A standard causal self-attention KV cache is
# only exact for strictly left-to-right generation; GFlowNet sequence envs
# also write tokens at *arbitrary* positions (bitseq) — so each layer here
# computes K/V from the token's frozen input embedding (token + position)
# alone, while a learned latent query evolves through the layer stack and
# cross-attends to the cache.  Consequences:
#
#  - appending one token's K/V per layer is *exact*: an entry never depends
#    on the rest of the sequence, so insertion order cannot invalidate it;
#  - the output is a function of the *set* of (token, position) pairs, i.e.
#    of the spatial observation — teacher-forcing objectives, replay, and
#    the exact-DP evaluators keep working off stored observations;
#  - the full (uncached) pass and the cached pass are the same math, so
#    cached rollouts match uncached ones to fp tolerance.
#
# Layout: cache slot 0 holds a learned BOS entry (so the empty state still
# has something to attend to); the token appended at generation step i lands
# in slot i+1.  Queries mask slots > current length.
#
# Cache layout: ONE stacked pair ``{"k", "v"}`` shaped
# (num_layers, B, capacity, H, hd) — not a per-layer dict.  Stacking is what
# makes the per-step append *fused*: all layers' K (and V) land in a single
# ``dynamic_update_slice`` (lockstep scalar slot) or a single per-row
# scatter (the serving engine's vector slot), instead of 2 x num_layers
# small updates chained through the rollout scan carry.  The fused Pallas
# decode-step kernel (``kernels/decode_attention.decode_step_pallas``)
# consumes the same layout directly.


def decode_encoder_init(key: jax.Array, *, num_layers: int, dim: int,
                        num_heads: int, ff_dim: Optional[int] = None,
                        dtype=jnp.float32) -> Params:
    """Latent-query decoder stack: per layer, q projection of the evolving
    query state + K/V projections of frozen token embeddings + GELU MLP,
    pre-LayerNorm on the query path (mirrors :func:`encoder_init`)."""
    ff_dim = ff_dim if ff_dim is not None else 4 * dim
    keys = jax.random.split(key, num_layers + 1)
    layers: Params = {}
    for i, k in enumerate(keys[:-1]):
        ks = jax.random.split(k, 5)
        layers[f"layer_{i}"] = {
            "ln1": layernorm_init(dim, dtype),
            "q": dense_init(ks[0], dim, dim, dtype=dtype),
            "kv": dense_init(ks[1], dim, 2 * dim, dtype=dtype),
            "proj": dense_init(ks[2], dim, dim, dtype=dtype),
            "ln2": layernorm_init(dim, dtype),
            "ff1": dense_init(ks[3], dim, ff_dim, dtype=dtype),
            "ff2": dense_init(ks[4], ff_dim, dim, dtype=dtype),
        }
    layers["ln_f"] = layernorm_init(dim, dtype)
    layers["q0"] = normal_init(keys[-1], (dim,), std=0.02, dtype=dtype)
    return layers


def _num_layers(p: Params) -> int:
    return sum(1 for k in p if k.startswith("layer_"))


def _kv_heads(lp: Params, x: jax.Array, num_heads: int):
    """K/V of token embeddings x (..., D) -> two (..., H, hd) arrays."""
    D = x.shape[-1]
    hd = D // num_heads
    kv = dense_apply(lp["kv"], x).reshape(x.shape[:-1] + (2, num_heads, hd))
    return kv[..., 0, :, :], kv[..., 1, :, :]


def _kv_heads_stacked(p: Params, x: jax.Array, num_heads: int):
    """All layers' K/V of token embeddings x (..., D) -> two stacked
    (num_layers, ..., H, hd) arrays (one pair of values per layer, computed
    with that layer's projection)."""
    ks, vs = zip(*(_kv_heads(p[f"layer_{i}"], x, num_heads)
                   for i in range(_num_layers(p))))
    return jnp.stack(ks), jnp.stack(vs)


def _single_query_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid: jax.Array) -> jax.Array:
    """q: (..., B, H, hd); k/v: (B, S, H, hd); valid: (..., B, S) bool.
    Leading query axes share the one (B, S) bank, broadcast in the einsums.
    Shared by the cached and full paths so both reduce in the same order
    (parity)."""
    hd = q.shape[-1]
    logits = jnp.einsum('...bhd,bshd->...bhs', q, k) / jnp.sqrt(hd).astype(
        q.dtype)
    logits = jnp.where(valid[..., None, :], logits, -1e30)
    attn = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum('...bhs,bshd->...bhd', attn, v)


def cache_init(p: Params, x0: jax.Array, capacity: int, *,
               num_heads: int) -> Params:
    """Preallocated stacked K/V cache seeded with the BOS entry at slot 0.

    x0: (B, D) BOS embedding; returns ``{"k", "v"}`` with both arrays
    shaped (num_layers, B, capacity, H, hd).
    """
    B, D = x0.shape
    hd = D // num_heads
    k0, v0 = _kv_heads_stacked(p, x0, num_heads)        # (Lyr, B, H, hd)
    zeros = jnp.zeros((_num_layers(p), B, capacity, num_heads, hd),
                      x0.dtype)
    return {"k": zeros.at[:, :, 0].set(k0), "v": zeros.at[:, :, 0].set(v0)}


def cache_fill(p: Params, cache: Params, xs: jax.Array, *,
               num_heads: int) -> Params:
    """Bulk-write token embeddings xs (B, S, D) into slots 1..S in one batched
    pass (token i -> slot i+1) — used by pop-only backward rollouts, which
    build the cache from the terminal sequence once and then only query."""
    S = xs.shape[1]
    kn, vn = _kv_heads_stacked(p, xs, num_heads)        # (Lyr, B, S, H, hd)
    return {"k": cache["k"].at[:, :, 1:S + 1].set(kn),
            "v": cache["v"].at[:, :, 1:S + 1].set(vn)}


def cache_append(p: Params, cache: Params, x_new: jax.Array,
                 slot: jax.Array, *, num_heads: int) -> Params:
    """Write one token's K/V for every layer at ``slot`` — one fused update
    per cache tensor, not one per layer.

    ``slot`` is either a traced *scalar* index shared by the whole batch (a
    cheap ``dynamic_update_slice``, no per-env scatter) or a (B,) *vector*
    of per-row slots (a ``.at[:, arange(B), slot]`` scatter — the serving
    engine's continuous-batching path, where each lane sits at its own
    trajectory step).  Per-row writes land the same values at the same
    (row, slot) locations a scalar write would for that row, so a lane's
    cache rows are bitwise those of a dedicated rollout at its step.

    The batch-uniform scalar slot is correct for lockstep rollouts because
    they append the token added at scan step t-1 into slot t for every env:
    envs whose step t-1 added nothing (stopped / terminal) get a garbage
    entry at a slot their ``length`` mask never reaches, and envs at max
    length re-write their newest token's slot with identical values."""
    kn, vn = _kv_heads_stacked(p, x_new, num_heads)     # (Lyr, B, H, hd)
    if jnp.ndim(slot) == 1:
        rows = jnp.arange(slot.shape[0])
        return {"k": cache["k"].at[:, rows, slot].set(kn),
                "v": cache["v"].at[:, rows, slot].set(vn)}
    start = (0, 0, slot, 0, 0)
    return {"k": jax.lax.dynamic_update_slice(cache["k"], kn[:, :, None],
                                              start),
            "v": jax.lax.dynamic_update_slice(cache["v"], vn[:, :, None],
                                              start)}


def _decode_query(p: Params, num_heads: int, kv_of_layer, attend,
                  batch: int, dim: int) -> jax.Array:
    """Shared latent-query stack; ``attend(q_heads, k, v) -> (B, H, hd)``."""
    hd = dim // num_heads
    h = jnp.broadcast_to(p["q0"][None, :], (batch, dim))
    for i in range(_num_layers(p)):
        lp = p[f"layer_{i}"]
        k, v = kv_of_layer(i)
        qh = dense_apply(lp["q"], layernorm_apply(lp["ln1"], h))
        o = attend(qh.reshape(batch, num_heads, hd), k, v)
        h = h + dense_apply(lp["proj"], o.reshape(batch, dim))
        g = layernorm_apply(lp["ln2"], h)
        h = h + dense_apply(lp["ff2"], jax.nn.gelu(dense_apply(lp["ff1"], g)))
    return layernorm_apply(p["ln_f"], h)


def encoder_query_cached(p: Params, cache: Params, lengths: jax.Array, *,
                         num_heads: int, attn_impl: str = "auto"
                         ) -> jax.Array:
    """Latent-query pass over the cache; slots 0..lengths[b] are attended
    (BOS + the env's tokens).  Returns (B, D).

    ``attn_impl``: "jnp" (masked softmax, the CPU path), "kernel" (the
    Pallas decode-attention kernel), or "auto" (the kernel on the TPU, where
    it lowers through Mosaic; jnp elsewhere, since an interpret-mode kernel
    on the rollout hot path would be far slower than the jnp path).
    """
    ks = cache["k"]
    B, C = ks.shape[1], ks.shape[2]
    dim = ks.shape[3] * ks.shape[4]
    if attn_impl == "auto":
        attn_impl = "kernel" if jax.default_backend() == "tpu" else "jnp"
    if attn_impl == "kernel":
        from ..kernels.ops import decode_attention
        kv_valid = lengths.astype(jnp.int32) + 1          # + BOS slot
        attend = lambda q, k, v: decode_attention(q, k, v, kv_valid)
    else:
        valid = jnp.arange(C)[None, :] <= lengths[:, None]
        attend = lambda q, k, v: _single_query_attention(q, k, v, valid)
    return _decode_query(
        p, num_heads,
        lambda i: (cache["k"][i], cache["v"][i]),
        attend, B, dim)


def encoder_apply_cached(p: Params, x_new: jax.Array, cache: Params,
                         lengths: jax.Array, *, num_heads: int,
                         attn_impl: str = "auto", slot: Optional[jax.Array]
                         = None):
    """One incremental-decode step: append ``x_new``'s K/V per layer at
    ``slot`` (scalar, default ``max(lengths)``; or per-row (B,) — see
    :func:`cache_append`), then attend the single latent query against the
    cache masked to ``lengths``.  Returns ``(y (B, D), new_cache)``.
    """
    cache = cache_append(p, cache, x_new,
                         jnp.max(lengths) if slot is None else slot,
                         num_heads=num_heads)
    y = encoder_query_cached(p, cache, lengths, num_heads=num_heads,
                             attn_impl=attn_impl)
    return y, cache


def encoder_step_cached(p: Params, x_new: jax.Array, cache: Params,
                        lengths: jax.Array, slot: jax.Array, *,
                        num_heads: int, attn_impl: str = "auto"):
    """Fused decode step: append + query as ONE entry point, so callers
    (rollout scan body, serve lane step) issue a single op instead of the
    append -> query chain.  ``slot`` is a traced scalar (lockstep rollouts)
    or a (B,) vector (serve lanes).  Returns ``(y (B, D), new_cache)``.

    On the jnp path this is exactly ``cache_append`` + ``encoder_query_cached``
    (bitwise parity with the unfused chain); on the TPU the attention itself
    lowers through the decode-attention kernel, and the fully-fused sampling
    variant lives one level up in ``core.policies`` (which also folds in
    masked sampling via ``kernels.ops.decode_step``).
    """
    cache = cache_append(p, cache, x_new, slot, num_heads=num_heads)
    y = encoder_query_cached(p, cache, lengths, num_heads=num_heads,
                             attn_impl=attn_impl)
    return y, cache


def decoder_stacked_weights(p: Params) -> Params:
    """Stack the per-layer decoder weight dicts into (num_layers, ...) arrays
    for the fused Pallas decode-step kernel (which loops layers statically
    over a single stacked ref instead of taking 7 x num_layers operands).
    Trace-time only — checkpoints keep the per-layer dict layout."""
    L = _num_layers(p)

    def stack(path_fn):
        return jnp.stack([path_fn(p[f"layer_{i}"]) for i in range(L)])

    return {
        "ln1_scale": stack(lambda lp: lp["ln1"]["scale"]),
        "ln1_bias": stack(lambda lp: lp["ln1"]["bias"]),
        "q_w": stack(lambda lp: lp["q"]["w"]),
        "q_b": stack(lambda lp: lp["q"]["b"]),
        "kv_w": stack(lambda lp: lp["kv"]["w"]),
        "kv_b": stack(lambda lp: lp["kv"]["b"]),
        "proj_w": stack(lambda lp: lp["proj"]["w"]),
        "proj_b": stack(lambda lp: lp["proj"]["b"]),
        "ln2_scale": stack(lambda lp: lp["ln2"]["scale"]),
        "ln2_bias": stack(lambda lp: lp["ln2"]["bias"]),
        "ff1_w": stack(lambda lp: lp["ff1"]["w"]),
        "ff1_b": stack(lambda lp: lp["ff1"]["b"]),
        "ff2_w": stack(lambda lp: lp["ff2"]["w"]),
        "ff2_b": stack(lambda lp: lp["ff2"]["b"]),
        "ln_f_scale": p["ln_f"]["scale"],
        "ln_f_bias": p["ln_f"]["bias"],
        "q0": p["q0"],
    }


def encoder_apply_bank(p: Params, xs: jax.Array, mask: jax.Array, *,
                       num_heads: int) -> jax.Array:
    """Full (uncached) latent-query pass over a bank of token embeddings.

    xs: (B, S, D) embeddings (BOS included by the caller); mask: (B, S)
    True = attendable.  Same math as the cached path — K/V from frozen
    embeddings, query through the layer stack — computed in one batch.

    A mask of shape (N, B, S) runs N queries against each bank row, each
    under its own mask: K/V are projected once per bank row and broadcast
    over N in the attention einsums, never copied.  Returns (B, D), or
    (N * B, D) with rows in (N, B) order.
    """
    B, S, D = xs.shape
    lead = mask.shape[:-2]

    def kv_of_layer(i):
        return _kv_heads(p[f"layer_{i}"], xs, num_heads)

    def attend(q, k, v):
        o = _single_query_attention(q.reshape(lead + (B,) + q.shape[1:]),
                                    k, v, mask)
        return o.reshape(q.shape)

    return _decode_query(p, num_heads, kv_of_layer, attend,
                         math.prod(lead) * B, D)
