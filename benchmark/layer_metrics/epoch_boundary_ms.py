"""Host time an epoch in which nothing is queued on the device, from the
program's own span rows of the measured task: ``train.epoch.begin``
(the epoch's step rows, the shuffle, up to the first dispatch) +
``train.epoch.report`` (series rows, gauges, flush, log line, scores:
all after validation's device->host pull) + ``train.epoch.checkpoint``
(absent, so 0 s, where the cell saves nothing), as the mean over the
window's epochs 1..K. The epoch the benchmark's profiler was open in is
left out, as ``quiet_rate`` leaves it out. Between two ``train.epoch``
spans lies only the executor's ``fault_point`` seam."""


def read(run, metric):
    from benchmark import program_spans
    t0, t1 = run.window
    opened = run.extra.get('trace_open_s')
    quiet = []
    for epoch in program_spans.epochs(
            program_spans.rows(run, run.task_id)):
        end = epoch['started'] + epoch['duration']
        traced = opened is not None and end > opened
        if epoch['started'] < t0 or end > t1 or traced \
                or not epoch['children']:
            continue
        quiet.append(sum(epoch['children'].get(name, 0.0)
                         for name in program_spans.BOUNDARY))
    if not quiet:
        return None
    return 1e3 * sum(quiet) / len(quiet)
