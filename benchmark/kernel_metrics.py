"""What the per-kernel and per-counter readers under ``layer_metrics/``
share: the sequences a traced epoch works through, a kernel's share of
its roofline from the ops that carry its NAME, and the mean of one of
the program's per-epoch series over the window.
"""


def epoch_sequences(run):
    """(sequences the traced epoch trains on, sequences it validates,
    the batch): every train step's batch, and every validation batch
    padded to the batch size."""
    from benchmark.steady import job_spec
    batch = job_spec(run.cell, run.config, run.seed)['batch_size']
    valid = -(-int(run.cell['data']['valid_rows']) // batch) * batch
    return run.steps_per_epoch * batch, valid, batch


def kernel_seconds(run, names):
    """Device seconds, in the traced epoch, of the ops whose OWN name
    (``trace_reduce.op_base_name``) is one of ``names``; None where
    there is no trace or no such op."""
    reduced = run.reduced()
    if not reduced or not names:
        return None
    from benchmark.trace_reduce import op_base_name
    seconds = sum(s for s, _, text in reduced['op_table'].values()
                  if op_base_name(text) in names)
    return seconds if seconds > 0 else None


def roofline_share(run, metric, names, need_flops, need_bytes):
    """100 x the least time the chip could take (the larger of FLOPs
    over peak FLOP/s and bytes over peak bytes/s) over the kernels'
    device time. None, never 0, where there is nothing to read."""
    seconds = kernel_seconds(run, names)
    if seconds is None or run.peaks is None:
        return None
    by_flops = need_flops / run.peaks['bf16_flops_per_s']
    by_bytes = need_bytes / run.peaks['hbm_bytes_per_s']
    run.note(f'{metric}: ops {sorted(names)} {seconds:.4f} s in the '
             f'traced epoch; least {by_flops:.4f} s by FLOPs, '
             f'{by_bytes:.4f} s by bytes -> bound by '
             f'{"compute" if by_flops >= by_bytes else "bandwidth"}')
    return 100.0 * max(by_flops, by_bytes) / seconds


def window_series_mean(run, name):
    """Mean over the window's epochs (epoch 0 is set-up) of a series the
    program writes once an epoch; None where it wrote none."""
    values = [v for step, v, _ in run.series(name) if step >= 1]
    return sum(values) / len(values) if values else None
