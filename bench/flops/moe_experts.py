"""The held experts' grouped matmul (``models/moe.held_experts``: XLA's
TPU ``ragged-dot`` kernels, forward and both gradient products).

A call multiplies rows of a (M, a) or (M, b) operand, grouped by expert,
by a (G, a, b) stack of held experts' matrices (or, for the weights'
gradient, yields that stack): 2 x rows x a x b operations.  M is the
buffer of every (token, choice) pair; only the pairs routed to held
experts are grouped, and the kernel visits only their rows.  Under even
routing that is ``experts_held / n_routed_experts`` of the buffer, the
share these functions count: rows for operations, and the bytes of the
(M, .) operands and results, with the (G, a, b) stack read or written
whole.
"""


def held_share(cfg):
    return cfg["experts_held"] / cfg["n_routed_experts"]


def cost(results, operands, cfg):
    """(operations, HBM bytes) of one call from its results' and operands'
    (dtype, shape, in_hbm) entries (``bench.trace.custom_call_types``)."""
    from bench import trace
    share = held_share(cfg)
    arrays = [t for t in results + operands if len(t[1]) >= 2]
    stack = [t for t in arrays if len(t[1]) == 3]
    rows = [t for t in arrays if len(t[1]) == 2]
    if len(stack) != 1 or not rows:
        return 0.0, 0.0
    _, (_, a, b), _ = stack[0]
    m = rows[0][1][0] * share
    return 2.0 * m * a * b, trace.hbm_bytes(stack) + share * trace.hbm_bytes(rows)
