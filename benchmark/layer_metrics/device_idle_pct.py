"""1 - device busy over the traced epoch, in per cent. The window is
bounded by the runner's two host marks around one whole epoch (from
the end of the epoch before to this epoch's end), never by the first
and last op."""


def read(run, metric):
    reduced = run.reduced()
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced['busy_s'] / reduced['window_s'])
