"""Operations and bytes from shapes: what the algorithm requires, never
what a compiler reports of a program (recomputation does not count).

A multiply-add is two operations. Family modules under ``reference/``
add these up into ``train_flops_per_sample``; per-kernel readers under
``layer_metrics/`` use them for rooflines.
"""


def matmul(m: int, k: int, n: int) -> float:
    """[m,k] @ [k,n]."""
    return 2.0 * m * k * n


def conv2d(out_h: int, out_w: int, kh: int, kw: int, c_in: int,
           c_out: int) -> float:
    """One image through a convolution with that output size."""
    return 2.0 * out_h * out_w * kh * kw * c_in * c_out


def causal_attention(seq: int, heads: int, head_dim: int,
                     backward: bool = False) -> float:
    """One sequence through causal softmax attention: QK^T and PV over
    the lower triangle (diagonal included). The backward pass needs
    dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q: four such
    products, and none of the recomputation a flash kernel adds."""
    pairs = seq * (seq + 1) / 2.0
    forward = 2 * (2.0 * pairs * head_dim) * heads
    return 2.0 * forward if backward else forward


def attention_bytes(seq: int, heads: int, head_dim: int, itemsize: int,
                    backward: bool = False) -> float:
    """Least bytes to and from memory: forward reads Q, K, V and writes
    O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    one = float(seq) * heads * head_dim * itemsize
    return 8.0 * one if backward else 4.0 * one
