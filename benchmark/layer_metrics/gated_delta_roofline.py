"""The gated-delta-rule kernels' share of their roofline, from the
device trace of one whole epoch.

Time: the ops NAMED as the configuration's ``kernels.gated_delta.ops``
says (the Pallas calls ``gated_delta_fwd`` and ``gated_delta_bwd_scan``:
the recurrence from chunk to chunk). Least time: the delta rule at its
recurrent count (``flops_qwen3_next.gated_delta``) for every train
step's forward and backward and every validation batch's forward, every
linear layer and sequence. The chunk-local products and the triangular
solve run as XLA fusions outside these ops and are not required work:
their time is in ``step_device_ms`` and costs ``step_mfu_pct``, not this
share. Under ``remat`` the forward kernel runs twice a step and is
required once."""


def read(run, metric):
    kernels = (run.config.get('kernels') or {}).get('gated_delta')
    if not kernels:
        return None
    from benchmark import flops_qwen3_next as more
    from benchmark.kernel_metrics import epoch_sequences, roofline_share
    train, valid, _ = epoch_sequences(run)
    shape = (int(run.cell['data']['seq_len']), int(kernels['heads']),
             int(kernels['key_dim']), int(kernels['value_dim']))
    layers = int(kernels['linear_layers'])
    fwd, bwd = more.gated_delta(*shape), more.gated_delta(
        *shape, backward=True)
    need_flops = layers * (train * (fwd + bwd) + valid * fwd)
    need_bytes = layers * (
        (train + valid) * more.gated_delta_bytes(*shape, 2)
        + train * more.gated_delta_bytes(*shape, 2, backward=True))
    return roofline_share(run, metric, set(kernels['ops']), need_flops,
                          need_bytes)
