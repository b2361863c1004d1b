"""The flash attention kernels' share of their roofline, from the
device trace of one whole epoch.

Time: the summed device durations of the Pallas kernels' events: the
ops whose OWN name (what stands before `` = `` in the HLO text, less its
``.N``) is one of the configuration's ``kernels.flash_attn`` names, or
whose text names the target ``tpu_custom_call`` (forward, dq, dk/dv). Least time: what causal attention
requires over the same epoch — every train step's forward and backward
and every validation batch's forward, for every layer and sequence —
as the larger of FLOPs over the peak FLOP/s and bytes over the peak
bytes/s, both from shapes (``flops.py``). Under ``remat`` the forward
kernel runs twice a step and is required once: recomputation does not
count, so it costs roofline share. Which of the two bounds it is
printed on standard error.

The attention's shape is the configuration's: its ``kernels`` block
gives ``q_heads``, ``head_dim`` and ``attention_layers`` where the head
size is not ``d_model / n_heads`` or only some layers hold attention;
a key it leaves out is derived from the model's (``n_heads``,
``d_model // n_heads``, ``n_layers``). K and V are counted with the
query's heads, so with fewer key-value heads the bytes are an upper
estimate — which matters only where the kernels are bandwidth-bound."""


def attention_shape(kernels: dict, model: dict):
    """(query heads, head size, layers that hold attention)."""
    heads = int(kernels.get('q_heads') or model['n_heads'])
    head_dim = int(kernels.get('head_dim')
                   or int(model['d_model']) // int(model['n_heads']))
    layers = int(kernels.get('attention_layers') or model['n_layers'])
    return heads, head_dim, layers


def read(run, metric):
    reduced = run.reduced()
    kernels = run.config.get('kernels') or {}
    names = kernels.get('flash_attn')
    if not reduced or not names or run.peaks is None:
        return None
    from benchmark.trace_reduce import op_base_name
    seconds = sum(s for s, _, text in reduced['op_table'].values()
                  if op_base_name(text) in names
                  or 'custom_call_target="tpu_custom_call"' in text)
    if seconds <= 0:
        return None
    from benchmark import flops
    from benchmark.steady import job_spec
    job = job_spec(run.cell, run.config, run.seed)
    model, data = job['model'], run.cell['data']
    seq, batch = int(data['seq_len']), job['batch_size']
    heads, head_dim, layers = attention_shape(kernels, model)
    train = run.steps_per_epoch * batch * layers
    valid = -(-int(data['valid_rows']) // batch) * batch * layers
    fwd = flops.causal_attention(seq, heads, head_dim)
    bwd = flops.causal_attention(seq, heads, head_dim, backward=True)
    need_flops = train * (fwd + bwd) + valid * fwd
    need_bytes = (train + valid) * flops.attention_bytes(
        seq, heads, head_dim, 2) + train * flops.attention_bytes(
        seq, heads, head_dim, 2, backward=True)
    by_flops = need_flops / run.peaks['bf16_flops_per_s']
    by_bytes = need_bytes / run.peaks['hbm_bytes_per_s']
    run.note(f'{metric}: kernels {seconds:.4f} s in the traced epoch; '
             f'least {by_flops:.4f} s by FLOPs, {by_bytes:.4f} s by '
             f'bytes -> bound by '
             f'{"compute" if by_flops >= by_bytes else "bandwidth"}')
    return 100.0 * max(by_flops, by_bytes) / seconds
