"""Model operations of one trajectory of TB training on the decode
transformer (``bitseq120``), from the configuration's shapes.

Counted: matrix multiplications at 2 operations per multiply-add, in

- the rollout's sampling forward: per step, the new token's keys and
  values in every layer, the latent query's layers (query, attention over
  the live slots, projection, feed-forward) and the readout;
- the objective's log-prob pass over the T+1 stored states (keys and
  values of every slot of the padded bank, the query path and the
  readout), forward and backward (backward = 2 x forward).

Not counted: element-wise work, softmax, the optimizer, and nothing is
recomputed.  The programs compute in float32; shares are taken against
the chip's bf16 peak, so they are lower bounds.
"""


def widths(cfg):
    e, p = cfg["env"], cfg["policy"]
    L = e["n"] // e["k"]
    A = L * 2 ** e["k"]
    return L, A, p["dim"], p["ff_dim"], p["num_layers"]


def query_flops(cfg, slots):
    """One latent query through every layer against ``slots`` live
    slots, plus the readout (A forward logits and the flow head)."""
    L, A, D, F, nl = widths(cfg)
    per_layer = 2 * D * D + 4 * slots * D + 2 * D * D + 4 * D * F
    return nl * per_layer + 2 * D * (A + 1)


def kv_flops(cfg, tokens):
    L, A, D, F, nl = widths(cfg)
    return nl * tokens * 2 * D * 2 * D


def rollout_flops(cfg):
    L = widths(cfg)[0]
    # step t appends one token and queries BOS plus t tokens
    return sum(kv_flops(cfg, 1) + query_flops(cfg, t + 1) for t in range(L))


def objective_forward_flops(cfg):
    L = widths(cfg)[0]
    return (L + 1) * (kv_flops(cfg, L + 1) + query_flops(cfg, L + 1))


def flops_per_traj(cfg):
    return rollout_flops(cfg) + 3 * objective_forward_flops(cfg)
