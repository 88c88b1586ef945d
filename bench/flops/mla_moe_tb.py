"""Model operations of one trajectory of TB fine-tuning on an ``mla_moe``
policy (``moonlight16b_ep8``), from the configuration's shapes.

Counted: matrix multiplications at 2 operations per multiply-add, in

- the rollout's prefill: the prompt but its last token through every
  layer (no head);
- the rollout's decode: one token per step through every layer against
  the latent cache (``W_kv_b`` absorbed: the query mapped into the latent,
  scores over the latent and the rope part, the weighted latent mapped
  out), and the head;
- the objective's teacher-forced pass over prompt + continuation, the
  head at the T + 1 states' positions, forward and backward (backward = 2
  x forward).

Per token and MoE layer, the held experts are counted at the share of the
router's choices they get under even routing, ``num_experts_per_tok x
experts_held / n_routed_experts`` expert applications.  Attention scores
count the causal prefix.  Not counted: element-wise work, norms, softmax,
the router's top-k and sort, the reward, the optimizer; nothing is
recomputed.  The programs compute in float32; shares are taken against
the chip's bf16 peak, so they are lower bounds.
"""


def _dims(cfg):
    H = cfg["num_attention_heads"]
    return (cfg["hidden_size"], H, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def ffn_flops(cfg, layer):
    """One token through layer ``layer``'s FFN half."""
    D = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return 2 * 3 * D * cfg["intermediate_size"]
    F = cfg["moe_intermediate_size"]
    held = cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["n_routed_experts"]
    return (2 * D * cfg["n_routed_experts"] + held * 2 * 3 * D * F
            + 2 * 3 * D * cfg["n_shared_experts"] * F)


def projection_flops(cfg):
    """One token's query, latent and output projections."""
    D, H, nope, rope, vd, rank = _dims(cfg)
    return 2 * D * H * (nope + rope) + 2 * D * (rank + rope) \
        + 2 * H * vd * D


def expanded_flops(cfg, context):
    """One token of a teacher-forced or prefill pass attending over
    ``context`` positions: projections, the up-projection of its latent,
    scores and values."""
    D, H, nope, rope, vd, rank = _dims(cfg)
    return (projection_flops(cfg) + 2 * rank * H * (nope + vd)
            + 2 * H * (nope + rope) * context + 2 * H * vd * context)


def latent_flops(cfg, context):
    """One decode token attending over ``context`` cached latents."""
    D, H, nope, rope, vd, rank = _dims(cfg)
    return (projection_flops(cfg) + 2 * H * nope * rank
            + 2 * H * (rank + rope) * context + 2 * H * rank * context
            + 2 * H * rank * vd)


def pass_flops(cfg, tokens, attn):
    """``tokens`` positions 0..tokens-1 through every layer, with
    ``attn(context)`` per token's attention."""
    layers = cfg["num_hidden_layers"]
    return sum(attn(p + 1) for p in range(tokens)) * layers + tokens * sum(
        ffn_flops(cfg, i) for i in range(layers))


def head_flops(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg):
    P = cfg["recipe_env"]["prompt_len"]
    return pass_flops(cfg, P - 1, lambda c: expanded_flops(cfg, c))


def decode_flops(cfg):
    e = cfg["recipe_env"]
    P, T = e["prompt_len"], e["length"]
    layers = cfg["num_hidden_layers"]
    return sum(layers * latent_flops(cfg, P + t) for t in range(T)) + T * (
        sum(ffn_flops(cfg, i) for i in range(layers)) + head_flops(cfg))


def objective_forward_flops(cfg):
    e = cfg["recipe_env"]
    S = e["prompt_len"] + e["length"]
    return pass_flops(cfg, S, lambda c: expanded_flops(cfg, c)) \
        + (e["length"] + 1) * head_flops(cfg)


def flops_per_traj(cfg):
    return prefill_flops(cfg) + decode_flops(cfg) \
        + 3 * objective_forward_flops(cfg)
