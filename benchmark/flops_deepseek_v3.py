"""Operations and bytes, from shapes, of the attention the
``deepseek_v3`` family adds (``flops.py`` has the rules: what the
algorithm requires, a multiply-add is two operations, recomputation
does not count).
"""


def mla_attention(seq: int, heads: int, qk_dim: int, v_dim: int,
                  backward: bool = False) -> float:
    """One sequence through causal softmax attention whose scores are
    ``qk_dim`` deep and whose values are ``v_dim`` wide: Q K^T and P V
    over the lower triangle (diagonal included), ``2 pairs (qk_dim +
    v_dim)`` a head. The backward pass needs dP = dO V^T and dV = P^T dO
    (``v_dim``), dQ = dS K and dK = dS^T Q (``qk_dim``): twice the
    forward. No lane padding of ``qk_dim``, no recomputed S, no tile
    above the diagonal."""
    pairs = seq * (seq + 1) / 2.0
    forward = 2.0 * pairs * (qk_dim + v_dim) * heads
    return 2.0 * forward if backward else forward


def mla_attention_bytes(seq: int, heads: int, qk_dim: int, rope_dim: int,
                        v_dim: int, itemsize: int,
                        backward: bool = False) -> float:
    """Least bytes to and from memory of latent attention in its
    expanded form: Q at ``qk_dim`` a head, the key's per-head part at
    ``qk_dim - rope_dim`` a head, its rotary part ONCE for all heads
    (``rope_dim``, one head), V and O at ``v_dim`` a head. Backward
    reads those and dO and writes dQ, dK's two parts and dV. The count
    is the algorithm's: it is the same whether a kernel reads the
    shared part as an operand of its own or laid out beside every
    head's."""
    row = float(seq) * itemsize
    q = row * heads * qk_dim
    k = row * (heads * (qk_dim - rope_dim) + rope_dim)
    v = row * heads * v_dim
    if backward:        # reads Q, K, V, O, dO; writes dQ, dK, dV
        return 2.0 * q + 2.0 * k + 4.0 * v
    return q + k + 2.0 * v      # reads Q, K, V; writes O
