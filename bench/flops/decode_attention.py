"""``decode_attention`` (``kernels/decode_attention.py``): one latent query
per row against a key/value cache.  Operands as the kernel receives them
(heads merged): valid counts (B,), queries (B, D), keys and values
(B, S, D), the (D, D) head map.

Operations: 2 S D for the scores and 2 S D for the weighted values per
row (the head map's matmul is the kernel's device, not the algorithm's).
"""


def ops(operands):
    _, (B, D), (_, S, _), _, _ = operands
    return 4 * B * S * D
