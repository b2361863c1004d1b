"""The grouped-query flash kernels' share of their roofline, from the
device trace of one whole epoch.

Time: the ops NAMED as the configuration's ``kernels.gqa_attn.ops`` says
— by name only, so that another Pallas kernel of the same run is never
counted (``flash_attn_roofline`` also takes every ``tpu_custom_call``).
Least time: causal attention over ``q_heads`` heads of ``head_dim`` for
every train step's forward and backward and every validation batch's
forward, every attention layer and sequence; the bytes count K and V
with ``kv_heads`` heads (``flops_qwen3_next.gqa_attention_bytes``)."""


def read(run, metric):
    kernels = (run.config.get('kernels') or {}).get('gqa_attn')
    if not kernels:
        return None
    from benchmark import flops, flops_qwen3_next as more
    from benchmark.kernel_metrics import epoch_sequences, roofline_share
    train, valid, _ = epoch_sequences(run)
    seq = int(run.cell['data']['seq_len'])
    heads, kv_heads = int(kernels['q_heads']), int(kernels['kv_heads'])
    head_dim, layers = int(kernels['head_dim']), int(
        kernels['attention_layers'])
    fwd = flops.causal_attention(seq, heads, head_dim)
    bwd = flops.causal_attention(seq, heads, head_dim, backward=True)
    size = (seq, heads, kv_heads, head_dim, 2)
    need_flops = layers * (train * (fwd + bwd) + valid * fwd)
    need_bytes = layers * (
        (train + valid) * more.gqa_attention_bytes(*size)
        + train * more.gqa_attention_bytes(*size, backward=True))
    return roofline_share(run, metric, set(kernels['ops']), need_flops,
                          need_bytes)
