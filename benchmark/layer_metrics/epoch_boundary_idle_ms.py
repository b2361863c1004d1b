"""The device's idle time in the traced epoch that falls under the
program's ``train.epoch*`` annotations on the trace's host line (the
program's spans, on the device events' clock), in ms. Idle and window
are ``device_idle_pct``'s: the gaps of the union of the ``XLA Ops``
intervals between the runner's two marks. None, never 0, where the
trace holds no such annotation. The split by span (host ms, device-idle
ms under it), what lies under ``train.epoch`` itself between its
phases, and the idle under no annotation (the ``fault_point`` seam
between two epochs) are printed on standard error, with the number of
gaps under each span and the longest: thousands of short ones under
``drain`` are the device's own, between the ops of queued steps while
the host only waits; the few long ones are the host's."""


def read(run, metric):
    from benchmark import program_spans
    trace = program_spans.load_trace(run)
    split = trace and program_spans.idle_by_annotation(trace)
    if not split:
        return None

    def ms(host, idle):
        return f'host {host * 1e3:.3f} ms, device idle {idle * 1e3:.3f} ms'

    for name, (host, idle, count, longest) in split['spans'].items():
        run.note(f'{metric}: {name}: {ms(host, idle)} in {count:.0f} '
                 f'gaps, longest {longest * 1e3:.3f} ms')
    run.note(f'{metric}: {program_spans.EPOCH} outside its phases: '
             f'{ms(*split["self_s"])}')
    run.note(f'{metric}: under no annotation '
             f'{split["unannotated_s"] * 1e3:.3f} ms of '
             f'{split["idle_s"] * 1e3:.3f} ms idle in a window of '
             f'{split["window_s"]:.4f} s; {split["events"]} annotation '
             f'events in the traced epoch')
    return 1e3 * (split['idle_s'] - split['unannotated_s'])
