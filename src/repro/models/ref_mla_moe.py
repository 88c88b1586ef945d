"""Plain reference for the ``mla_moe`` family (Moonlight-16B-A3B,
DeepSeek-V3 layer equations): the forward pass, and the TB loss of
continuations of a prompt with its gradients, in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``, with no cache, no kernel
and no batching tricks.  It reads the program's parameter tree
(``models.mla_moe.param_shapes``) and nothing else of the program.

Departures from the published description, each shared with the program:

- RoPE rotates the two halves of the 64 rope dimensions; DeepSeek's code
  first de-interleaves them, a fixed permutation of the rope columns of
  ``W_q`` and ``W_kv_a`` that random weights do not see.
- Only the routed experts this chip holds (``experts_held`` from
  ``expert_offset``) contribute; the router still scores all of them.
  The absent experts' part lies on other chips of the deployment.
- The vocabulary is the configured slice.
- No router load balancing: the score-correction bias is a fixed input.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, ..., r) rotated by ``pos`` (S,)."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv              # (S, r/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]["w"]) * (x @ p["up"]["w"])) \
        @ p["down"]["w"]


def attention(p, x, cfg):
    """Causal MLA over one sequence x (S, D), keys and values up-projected
    from the latent."""
    S = x.shape[0]
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    rank = cfg.kv_lora_rank
    pos = jnp.arange(S)
    q = (x @ p["q"]["w"]).reshape(S, H, -1)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, cfg.rope_theta)], -1)
    kv_a = x @ p["kv_a"]["w"]
    c = _rms(p["kv_norm"]["scale"], kv_a[:, :rank], cfg.rms_norm_eps)
    k_pe = _rope(kv_a[:, rank:], pos, cfg.rope_theta)          # (S, rope)
    kv = (c @ p["kv_b"]["w"]).reshape(S, H, -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe[:, None], (S, H, k_pe.shape[-1]))],
                        -1)
    v = kv[..., nope:]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.float32(nope + cfg.qk_rope_head_dim))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", a, v).reshape(S, -1) @ p["o"]["w"]


def route(p, x, cfg):
    """(T, E) routing weights over all routed experts: sigmoid scores, the
    top k of scores + bias, their scores renormalised and scaled; zero
    elsewhere."""
    s = jax.nn.sigmoid(x @ p["router"]["w"])
    _, idx = jax.lax.top_k(s + p["router"]["bias"], cfg.num_experts_per_tok)
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1]), axis=1) > 0
    w = jnp.where(chosen, s, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scaling_factor


def routed(p, x, weights, first):
    """The held experts' part: expert ``first + g`` applied to every token
    and weighted by its routing weight (zero where it was not chosen)."""
    e = p["experts"]
    out = jnp.zeros_like(x)
    for g in range(e["gate"]["w"].shape[1]):
        mlp = {n: {"w": e[n]["w"][:, g]} for n in ("gate", "up", "down")}
        out = out + weights[:, first + g, None] * _mlp(mlp, x)
    return out


def moe(p, x, cfg):
    return routed(p, x, route(p, x, cfg), cfg.expert_offset) \
        + _mlp(p["shared"], x)


def forward(params, tokens, cfg):
    """Logits (S, V) of one sequence of tokens (S,)."""
    eps = cfg.rms_norm_eps
    x = params["embed"]["table"][tokens]
    for i in range(cfg.num_hidden_layers):
        lp = params["layers"][f"layer_{i}"]
        x = x + attention(lp["attn"], _rms(lp["attn_norm"]["scale"], x, eps),
                          cfg)
        h = _rms(lp["ffn_norm"]["scale"], x, eps)
        x = x + (_mlp(lp["ffn"], h) if "ffn" in lp else moe(lp["moe"], h, cfg))
    return _rms(params["final_norm"]["scale"], x, eps) @ params["head"]["w"]


def continuation_log_probs(params, prompt, cont, cfg):
    """log P(cont_t | prompt, cont_<t) for each of the T tokens of one
    continuation: the logits at positions P-1 .. P+T-2."""
    P, T = prompt.shape[0], cont.shape[0]
    logits = forward(params, jnp.concatenate([prompt, cont]), cfg)
    lp = jax.nn.log_softmax(logits[P - 1:P + T - 1], axis=-1)
    return jnp.take_along_axis(lp, cont[:, None], axis=-1)[:, 0]


def tb_loss(params, prompt, conts, log_r, cfg):
    """Trajectory balance over continuations (B, T) with a degenerate
    backward policy: mean of (log Z + sum_t log P_F - log R)^2.  Returns
    the loss and the per-step log-probs (T, B)."""
    with jax.default_matmul_precision("highest"):
        log_pf = jnp.stack([continuation_log_probs(params, prompt, c, cfg)
                            for c in conts], axis=1)
        delta = params["log_z"] + jnp.sum(log_pf, 0) - log_r
        return jnp.mean(jnp.square(delta)), log_pf


def loss_and_grads(params, prompt, conts, log_r, cfg):
    return jax.value_and_grad(tb_loss, has_aux=True)(params, prompt, conts,
                                                     log_r, cfg)
