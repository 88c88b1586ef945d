"""``subtb_loss`` (``kernels/subtb_loss.py``): the lambda-weighted sum over
all sub-trajectory pairs j < k of (phi_j - phi_k)^2 per trajectory.
Operands: lengths (B,), and the (B, N, 1) and (B, 1, N) views of the
potentials, N the trajectory's T+1 states padded to the lane tile.

Operations: 6 per pair (difference, square, weight, two sums, weight
sum) over the N (N - 1) / 2 pairs the kernel evaluates.
"""


def ops(operands):
    (B,), (_, N, _), _ = operands
    return 6 * B * N * (N - 1) // 2
