"""Model operations of one trajectory of SubTB training on the hypergrid
MLP (``hypergrid20x4``), from the configuration's shapes.

Counted: the MLP's matrix multiplications (2 operations per
multiply-add) over the program's fixed shapes: the rollout's T sampling
forwards (T = d (H-1) + 1, every trajectory's scan runs all T steps) and
the objective's T+1 stored states, forward and backward (backward = 2 x
forward).  Steps after a trajectory's stop are counted because the
program computes them; element-wise work, the SubTB sum and the
optimizer are not.  Shares are taken against the bf16 peak.
"""


def forward_flops(cfg):
    e = cfg["env"]
    dims = [e["dim"] * e["side"]] + list(cfg["policy"]["hidden"]) \
        + [e["dim"] + 2]
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def flops_per_traj(cfg):
    e = cfg["env"]
    T = e["dim"] * (e["side"] - 1) + 1
    return (T + 3 * (T + 1)) * forward_flops(cfg)
