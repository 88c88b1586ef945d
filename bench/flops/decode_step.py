"""``decode_step`` (``decode_step_pallas``): one fused serving step per
lane: the new token's keys and values in every layer, the latent query
through every layer against the cache, the readout over the A forward
actions, and the masked Gumbel-max draw.

Operations (2 per multiply-add, 4 per action for the log-softmax and the
draw): ``ops`` from the operands as the kernel receives them (lengths,
slots and temperatures (B,), new-token embeddings (B, D), key and value
caches (nl, B, C, D), Gumbel noise and mask (B, A), the head map, then
the stacked weights, ff1 being (nl, D, F)), with attention over the whole
cache capacity C as the kernel reads it; ``model_flops_per_sample`` with
attention over a sample's live slots only.
"""


def row_flops(nl, D, F, A, slots):
    return nl * (4 * D * D + 2 * D * D + 4 * slots * D + 2 * D * D
                 + 4 * D * F) + 2 * D * A + 4 * A


def ops(operands):
    (nl, B, C, D), (_, A) = operands[4], operands[6]
    F = operands[21][-1]
    return B * row_flops(nl, D, F, A, C)


def model_flops_per_sample(cfg):
    """A served sample's L steps, attention over BOS and its tokens."""
    e, p = cfg["env"], cfg["policy"]
    L = e["n"] // e["k"]
    A = L * 2 ** e["k"]
    return sum(row_flops(p["num_layers"], p["dim"], p["ff_dim"], A, t + 1)
               for t in range(L))
