"""The ``mla_moe`` family: DeepSeek-V3-style decoders with multi-head latent
attention and sigmoid-routed experts (Moonlight-16B-A3B).

Layer i: ``x += MLA(RMSNorm(x))``, then ``x += FFN(RMSNorm(x))``, where the
FFN of the first ``first_k_dense_replace`` layers is a dense SiLU MLP of
width ``intermediate_size`` and every later one a routed-expert layer: a
sigmoid top-k router over all ``n_routed_experts``
(``moe.sigmoid_topk_route``), the experts this chip holds
(``moe.held_experts``: ``experts_held`` of them, from ``expert_offset``),
and the shared experts as one SiLU MLP of width ``n_shared_experts x
moe_intermediate_size`` with no gate.  A final RMSNorm and an untied head
give the logits.

Parameters keep one ``w`` per projection with its fan-in first (experts'
``w`` are (in, G, out)).  ``hidden`` is the teacher-forced pass (every
layer recomputed in the backward pass); ``prefill`` and
``decode`` run through the latent cache (``models.mla``).  Rows that all
continue one prompt run it once: ``shared_prefix`` passes the prompt at
batch 1 and returns each layer's latents, which ``hidden(prefix=...)``
attends over and ``load`` broadcasts into a cache.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.core import Params
from . import mla, moe
from .layers import rmsnorm


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    max_position_embeddings: int
    #: routed experts held here, and the first of them
    experts_held: int
    expert_offset: int = 0


def _mlp_shapes(d, f):
    return {"gate": {"w": (d, f)}, "up": {"w": (d, f)}, "down": {"w": (f, d)}}


def param_shapes(cfg: MLAMoEConfig) -> Params:
    D = cfg.hidden_size
    G, F = cfg.experts_held, cfg.moe_intermediate_size
    layers = {}
    for i in range(cfg.num_hidden_layers):
        lp = {"attn_norm": {"scale": (D,)}, "attn": mla.mla_shapes(cfg),
              "ffn_norm": {"scale": (D,)}}
        if i < cfg.first_k_dense_replace:
            lp["ffn"] = _mlp_shapes(D, cfg.intermediate_size)
        else:
            lp["moe"] = {
                "router": {"w": (D, cfg.n_routed_experts),
                           "bias": (cfg.n_routed_experts,)},
                "experts": {"gate": {"w": (D, G, F)}, "up": {"w": (D, G, F)},
                            "down": {"w": (F, G, D)}},
                "shared": _mlp_shapes(D, cfg.n_shared_experts * F)}
        layers[f"layer_{i}"] = lp
    return {"embed": {"table": (cfg.vocab_size, D)}, "layers": layers,
            "final_norm": {"scale": (D,)}, "head": {"w": (D, cfg.vocab_size)}}


def init_params(key: jax.Array, cfg: MLAMoEConfig) -> Params:
    """Matrices N(0, 1/fan_in), norm scales 1, the router bias 0, the
    embedding N(0, 1)."""
    is_shape = lambda x: isinstance(x, tuple)
    paths, tdef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg),
                                                       is_leaf=is_shape)
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "w":
            leaves.append(jax.random.normal(k, shape) / np.sqrt(shape[0]))
        elif name == "scale":
            leaves.append(jnp.ones(shape))
        elif name == "bias":
            leaves.append(jnp.zeros(shape))
        else:
            leaves.append(jax.random.normal(k, shape))
    return jax.tree_util.tree_unflatten(tdef, leaves)


def silu_mlp(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["gate"]["w"]) * (x @ p["up"]["w"])) \
        @ p["down"]["w"]


def ffn(lp: Params, x: jax.Array, cfg: MLAMoEConfig) -> jax.Array:
    """The FFN half of a layer on normed tokens x (..., D)."""
    if "ffn" in lp:
        return silu_mlp(lp["ffn"], x)
    p = lp["moe"]
    flat = x.reshape(-1, x.shape[-1])
    idx, w = moe.sigmoid_topk_route(flat, p["router"]["w"],
                                    p["router"]["bias"],
                                    cfg.num_experts_per_tok,
                                    cfg.routed_scaling_factor)
    routed = moe.held_experts(p["experts"], flat, idx, w, cfg.expert_offset)
    return routed.reshape(x.shape) + silu_mlp(p["shared"], x)


def _norm(p, x, cfg):
    return rmsnorm(p, x, cfg.rms_norm_eps)


def _layers(params):
    return [params["layers"][f"layer_{i}"]
            for i in range(len(params["layers"]))]


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    return params["embed"]["table"][tokens]


def hidden(params: Params, tokens: jax.Array, cfg: MLAMoEConfig,
           with_latents: bool = False, prefix=None):
    """The teacher-forced pass: tokens (B, S) at positions 0..S-1 to final
    normed hidden states (B, S, D); with ``with_latents`` also each layer's
    cache entries (c (L, B, S, rank), k_pe (L, B, S, rope)).  After a
    ``prefix`` (``shared_prefix``'s latents of P' tokens) the tokens sit at
    positions P'..P'+S-1 and attend over it in every layer."""
    start = 0 if prefix is None else prefix[0].shape[2]
    positions = start + jnp.arange(tokens.shape[1])

    @jax.checkpoint
    def layer(lp, x, pre):
        a, lat = mla.mla_expanded(lp["attn"], _norm(lp["attn_norm"], x, cfg),
                                  positions, cfg, pre)
        x = x + a
        return x + ffn(lp, _norm(lp["ffn_norm"], x, cfg), cfg), lat

    x = embed(params, tokens)
    cs, kpes = [], []
    for i, lp in enumerate(_layers(params)):
        pre = None if prefix is None else (prefix[0][i], prefix[1][i])
        x, (c, k_pe) = layer(lp, x, pre)
        cs.append(c)
        kpes.append(k_pe)
    h = _norm(params["final_norm"], x, cfg)
    if with_latents:
        return h, (jnp.stack(cs), jnp.stack(kpes))
    return h


def shared_prefix(params: Params, tokens: jax.Array, cfg: MLAMoEConfig):
    """One prompt shared by every row, tokens (P',) at positions 0..P'-1,
    run once at batch 1: each layer's latents (c (L, 1, P', rank), k_pe
    (L, 1, P', rope)), for ``hidden(prefix=...)`` and ``load``."""
    mla.counters["shared_prefix"] += 1
    return hidden(params, tokens[None], cfg, with_latents=True)[1]


def logits(params: Params, h: jax.Array) -> jax.Array:
    return h @ params["head"]["w"]


def cache_init(cfg: MLAMoEConfig, batch: int, capacity: int):
    L = cfg.num_hidden_layers
    return {"c": jnp.zeros((L, batch, capacity, cfg.kv_lora_rank)),
            "k_pe": jnp.zeros((L, batch, capacity, cfg.qk_rope_head_dim))}


def load(cache, latents):
    """Write latents (c (L, B or 1, S, rank), k_pe) at slots 0..S-1 of every
    row of the cache; a batch-1 prefix is broadcast to the rows."""
    def put(buf, new):
        new = jnp.broadcast_to(new, buf.shape[:2] + new.shape[2:])
        return jax.lax.dynamic_update_slice_in_dim(buf, new, 0, axis=2)
    return {"c": put(cache["c"], latents[0]),
            "k_pe": put(cache["k_pe"], latents[1])}


def prefill(params: Params, cache, tokens: jax.Array, cfg: MLAMoEConfig):
    """Load tokens (B, S) at positions 0..S-1 into the latent cache."""
    return load(cache, hidden(params, tokens, cfg, with_latents=True)[1])


def decode(params: Params, cache, token: jax.Array, slot,
           cfg: MLAMoEConfig):
    """One token per row, token (B,) at position ``slot`` (a scalar),
    through the latent cache: returns logits (B, V) and the cache with the
    token's latents written at ``slot`` of every layer."""
    x = embed(params, token)
    c_all, kpe_all = cache["c"], cache["k_pe"]
    pos = jnp.reshape(slot, (1,))
    for i, lp in enumerate(_layers(params)):
        xn = _norm(lp["attn_norm"], x, cfg)
        c, k_pe = mla.latents(lp["attn"], xn[:, None], pos, cfg)
        c_all = jax.lax.dynamic_update_slice(c_all, c[None], (i, 0, slot, 0))
        kpe_all = jax.lax.dynamic_update_slice(kpe_all, k_pe[None],
                                               (i, 0, slot, 0))
        x = x + mla.mla_latent_decode(lp["attn"], xn, slot, c_all[i],
                                      kpe_all[i], cfg)
        x = x + ffn(lp, _norm(lp["ffn_norm"], x, cfg), cfg)
    h = _norm(params["final_norm"], x, cfg)
    return logits(params, h), {"c": c_all, "k_pe": kpe_all}
