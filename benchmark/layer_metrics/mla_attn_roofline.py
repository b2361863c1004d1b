"""The latent-attention flash kernels' share of their roofline, from
the device trace of one whole epoch.

Time: the ops NAMED as the configuration's ``kernels.mla_attn.ops``
says (the flash forward and the one backward kernel inside the named
scope ``mla_attn``) — by name only. Least time: causal attention over
``heads`` heads with scores ``qk_head_dim`` deep and values
``v_head_dim`` wide for every train step's forward and backward and
every validation batch's forward, every attention layer and sequence
(``flops_deepseek_v3.mla_attention``); the bytes count the key's rotary
part once for all heads (``mla_attention_bytes``, operands of 2 bytes).
Under ``remat`` with the kernel's results held the forward runs once a
step. ``None`` where the configuration names no such kernel or the
trace holds no such op (a program without the op: the parent of the PR
that added it)."""


def read(run, metric):
    kernels = (run.config.get('kernels') or {}).get('mla_attn')
    if not kernels:
        return None
    from benchmark import flops_deepseek_v3 as more
    from benchmark.kernel_metrics import epoch_sequences, roofline_share
    train, valid, _ = epoch_sequences(run)
    seq = int(run.cell['data']['seq_len'])
    heads, layers = int(kernels['heads']), int(kernels['attention_layers'])
    qk, v = int(kernels['qk_head_dim']), int(kernels['v_head_dim'])
    fwd = more.mla_attention(seq, heads, qk, v)
    bwd = more.mla_attention(seq, heads, qk, v, backward=True)
    size = (seq, heads, qk, int(kernels['qk_rope_head_dim']), v, 2)
    need_flops = layers * (train * (fwd + bwd) + valid * fwd)
    need_bytes = layers * (
        (train + valid) * more.mla_attention_bytes(*size)
        + train * more.mla_attention_bytes(*size, backward=True))
    return roofline_share(run, metric, set(kernels['ops']), need_flops,
                          need_bytes)
