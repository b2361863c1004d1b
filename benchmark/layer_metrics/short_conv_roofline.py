"""The gated short-convolution kernels' share of their roofline, from
the device trace of one whole epoch.

Time: the ops NAMED as the configuration's ``kernels.short_conv.ops``
says (the Pallas calls ``short_conv_fwd`` and ``short_conv_bwd``) — by
name only. Least time: ``C * conv(B * X)`` over ``channels`` channels
with ``taps`` taps for every train step's forward and backward and every
validation batch's forward, every conv layer and token
(``flops_lfm2.short_conv`` / ``short_conv_bytes``, operands of 2 bytes).
The op holds no matrix product, so the bytes bound it. Under ``remat``
the forward kernel runs twice a step and is required once. ``None``
where the configuration names no such kernel or the trace holds no such
op (a program without the op: the parent of the PR that added it)."""


def read(run, metric):
    kernels = (run.config.get('kernels') or {}).get('short_conv')
    if not kernels:
        return None
    from benchmark import flops_lfm2
    from benchmark.kernel_metrics import epoch_sequences, roofline_share
    train, valid, _ = epoch_sequences(run)
    seq = int(run.cell['data']['seq_len'])
    shape = (seq, int(kernels['channels']), int(kernels['taps']))
    layers = int(kernels['conv_layers'])
    fwd = flops_lfm2.short_conv(*shape)
    bwd = flops_lfm2.short_conv(*shape, backward=True)
    need_flops = layers * (train * (fwd + bwd) + valid * fwd)
    need_bytes = layers * (
        (train + valid) * flops_lfm2.short_conv_bytes(*shape, 2)
        + train * flops_lfm2.short_conv_bytes(*shape, 2, backward=True))
    return roofline_share(run, metric, set(kernels['ops']), need_flops,
                          need_bytes)
