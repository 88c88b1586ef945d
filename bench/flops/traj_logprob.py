"""``traj_logprob`` (``kernels/traj_logprob.py``): masked log-softmax,
action gather and trajectory sum over (B, T, A) logits; TB calls it once
for the forward policy (A = 2^k L) and once for the backward one (A = L).
Operands: logits (B, T, A), actions (B, T, 1), mask (B, T, A), valid
(B, T, 1).

Operations: 4 per logit (mask select, max, subtract-exp, add).
"""


def ops(operands):
    B, T, A = operands[0]
    return 4 * B * T * A
