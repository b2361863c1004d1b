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
printed on standard error."""


def read(run, metric):
    reduced = run.reduced()
    names = (run.config.get('kernels') or {}).get('flash_attn')
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
    seq, heads = int(data['seq_len']), int(model['n_heads'])
    head_dim = int(model['d_model']) // heads
    batch, layers = job['batch_size'], int(model['n_layers'])
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
