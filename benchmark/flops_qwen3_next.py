"""Operations and bytes, from shapes, of the kernels the ``qwen3_next``
family adds (``flops.py`` has the rules: what the algorithm requires,
a multiply-add is two operations, recomputation does not count).
"""


def gated_delta(seq: int, heads: int, key_dim: int, value_dim: int,
                backward: bool = False) -> float:
    """One sequence through the gated delta rule at its RECURRENT count:
    per token and head the read ``S^T k``, the write ``k d^T`` and the
    output ``S^T q`` (a multiply-add per element of the state each) and
    the decay of the state (a multiply): ``7 dk dv``. The chunked form
    does more (the chunk-local products and the triangular solve); none
    of that is required. The backward pass is counted as twice the
    forward, as for every other product."""
    forward = 7.0 * seq * heads * key_dim * value_dim
    return 2.0 * forward if backward else forward


def gated_delta_bytes(seq: int, heads: int, key_dim: int, value_dim: int,
                      itemsize: int, backward: bool = False) -> float:
    """Least bytes to and from memory: forward reads q, k (key size), v
    (value size) and writes o, with g and beta in float32; backward reads
    those, o's cotangent, and writes the five cotangents. The state
    never leaves the chip."""
    rows = float(seq) * heads
    inputs = rows * (2 * key_dim + value_dim) * itemsize + rows * 2 * 4
    out = rows * value_dim * itemsize
    return 2.0 * inputs + out if backward else inputs + out


def gqa_attention_bytes(seq: int, q_heads: int, kv_heads: int,
                        head_dim: int, itemsize: int,
                        backward: bool = False) -> float:
    """Least bytes of causal grouped-query attention: K and V counted
    with THEIR heads (``flops.attention_bytes`` counts them with the
    query's). Forward reads Q, K, V and writes O; backward reads Q, K, V,
    O, dO and writes dQ, dK, dV."""
    q = float(seq) * q_heads * head_dim * itemsize
    kv = float(seq) * kv_heads * head_dim * itemsize
    return 4.0 * q + 4.0 * kv if backward else 2.0 * q + 2.0 * kv


def expert_matmul(assignments: float, d_model: int, d_expert: int,
                  backward: bool = False) -> float:
    """``assignments`` (token, expert) pairs through a gated expert: the
    gate, up and down products. Backward: the cotangent of the rows and
    of the weights, twice the forward."""
    forward = 3 * 2.0 * assignments * d_model * d_expert
    return 2.0 * forward if backward else forward


def expert_matmul_bytes(held: int, assignments: float, d_model: int,
                        d_expert: int, weight_itemsize: int,
                        itemsize: int, backward: bool = False) -> float:
    """Least bytes of the grouped products: the held experts' three
    matrices read once a pass at the width they are stored in, the
    routed rows in and out; backward reads the weights and both sets of
    rows again and writes the weights' and the rows' cotangents."""
    weights = 3.0 * held * d_model * d_expert * weight_itemsize
    rows = 2.0 * assignments * d_model * itemsize
    return 2.0 * (weights + rows) if backward else weights + rows
