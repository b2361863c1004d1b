"""Where the looped model's exit distribution puts its weight: the
window's mean of the program's counter ``loop.expected_exit`` (the mean
over a step's tokens of ``sum_t t p(t)``, the epoch's mean over its
steps). Between 1 (every token leaves at the first exit) and the number
of recurrent steps (every token runs them all). ``None`` where the
program writes no such series (a program without the looped model)."""


def read(run, metric):
    from benchmark.kernel_metrics import window_series_mean
    rows = window_series_mean(run, 'loop.layer_rows')
    if rows is not None:
        run.note(f'{metric}: loop.layer_rows reads {rows} a step')
    return window_series_mean(run, 'loop.expected_exit')
