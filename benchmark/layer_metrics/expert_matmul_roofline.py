"""The grouped expert products' share of their roofline, from the
device trace of one whole epoch.

Time: the ops NAMED as the configuration's ``kernels.expert_matmul.ops``
says (the megablox ``gmm`` and ``tgmm`` Pallas calls). Least time: the
gate, up and down products of the (token, expert) pairs that landed on
the held experts — the share the program's own counter
``moe.local_assign_share`` read over the window, the even share
``held / of`` where it wrote none — for every train step's forward and
backward and every validation batch's forward, every layer; the bytes
read the held experts' weights once a pass at the width they are
stored in (``flops_qwen3_next.expert_matmul_bytes``)."""


def read(run, metric):
    kernels = (run.config.get('kernels') or {}).get('expert_matmul')
    if not kernels:
        return None
    from benchmark import flops_qwen3_next as more
    from benchmark.kernel_metrics import (
        epoch_sequences, roofline_share, window_series_mean)
    train, valid, batch = epoch_sequences(run)
    held, d_model = int(kernels['held']), int(kernels['d_model'])
    d_expert, layers = int(kernels['d_expert']), int(kernels['moe_layers'])
    share = window_series_mean(run, 'moe.local_assign_share')
    if share is None:
        share = held / float(kernels['of'])
    pairs = int(run.cell['data']['seq_len']) * int(kernels['top_k']) * share
    fwd = more.expert_matmul(pairs, d_model, d_expert)
    bwd = more.expert_matmul(pairs, d_model, d_expert, backward=True)
    need_flops = layers * (train * (fwd + bwd) + valid * fwd)
    # the weights are read once a PASS over a batch, not once a sequence
    size = (held, pairs * batch, d_model, d_expert,
            int(kernels['weight_itemsize']), 2)
    need_bytes = layers * (
        (train + valid) / batch * more.expert_matmul_bytes(*size)
        + train / batch * more.expert_matmul_bytes(*size, backward=True))
    return roofline_share(run, metric, set(kernels['ops']), need_flops,
                          need_bytes)
