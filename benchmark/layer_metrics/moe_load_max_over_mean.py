"""How unevenly the router loads the held experts: the window's mean of
the program's counter ``moe.load_max_over_mean`` (the largest held
expert's token count over the mean of the held, by step and layer)."""


def read(run, metric):
    from benchmark.kernel_metrics import window_series_mean
    return window_series_mean(run, 'moe.load_max_over_mean')
