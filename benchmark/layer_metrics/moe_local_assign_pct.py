"""The share of all (token, expert) assignments that landed on the
experts this program holds: 100 x the window's mean of the program's
counter ``moe.local_assign_share`` (the epoch's mean over its steps and
layers). Even routing reads ``100 * held / n_experts``."""


def read(run, metric):
    from benchmark.kernel_metrics import window_series_mean
    share = window_series_mean(run, 'moe.local_assign_share')
    dropped = window_series_mean(run, 'moe.dropped')
    if dropped is not None:
        run.note(f'{metric}: moe.dropped reads {dropped} a step')
    return None if share is None else 100.0 * share
