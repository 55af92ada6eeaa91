"""Operations and bytes of the post-LN transformer families, from shapes.
A multiply-add counts as two operations.  What the algorithm needs is
counted once; recomputation is never counted."""


def layer_matmul_flops_per_token(units, hidden):
    """Forward: QKV (U x 3U), output projection (U x U), FFN (U x H, H x U)."""
    return 2 * (3 * units * units + units * units + 2 * units * hidden)


def attention_flops(q_len, k_len, units):
    """Forward, all heads of one sequence: Q K^T and P V, 2 * q * k * U each."""
    return 4 * q_len * k_len * units


def attention_fwd_bytes(q_len, k_len, units, itemsize):
    """Forward, all heads of one sequence, what a fused kernel has to move:
    read Q, K and V once, write the output once."""
    return (2 * q_len + 2 * k_len) * units * itemsize
