"""The program's own spans, read from the two places they land.

``JaxTrain`` opens a span around each phase of a job (``train.setup.*``,
and per epoch ``train.epoch`` with its children ``begin``, ``steps``,
``drain``, ``valid``, ``report``, ``checkpoint``). Each is

- a row of the program's ``telemetry_span`` table, for every epoch of
  every job of a run (``rows``, ``epochs``), and
- while a ``jax.profiler`` trace is open, an annotation of the same
  name on a host line of that trace, on the device events' clock
  (``annotations``, ``idle_by_annotation``).

A program without these spans (an older commit) gives no rows and no
annotations: every function here then returns an empty or ``None``
result, and the readers report nothing. The interval arithmetic is
``trace_reduce``'s; the traces are its plain form.
"""

import json

from . import trace_reduce

EPOCH = 'train.epoch'
#: the phases of an epoch in which nothing is queued on the device:
#: after validation's pull (report, checkpoint) and before the next
#: epoch's first dispatch (begin)
BOUNDARY = (f'{EPOCH}.begin', f'{EPOCH}.report', f'{EPOCH}.checkpoint')


def rows(run, task_id=None):
    """The finished spans of one task, or of every task in the run's
    DB (a run's DB is made anew, so that is the run's jobs), oldest
    first, ``tags`` parsed."""
    where, args = ('where task = ? ', (task_id,)) \
        if task_id is not None else ('', ())
    found = run.query(
        'select span_id, parent_id, task, name, started, duration, tags '
        f'from telemetry_span {where}order by started, id', args)
    return [dict(r, tags=json.loads(r['tags'] or '{}')) for r in found
            if r['duration'] is not None]


def epochs(span_rows):
    """The ``train.epoch`` rows among ``span_rows``, oldest first, each
    with the seconds of its children by name under ``children``."""
    by_parent = {}
    for row in span_rows:
        by_parent.setdefault(row['parent_id'], []).append(row)
    out = []
    for row in span_rows:
        if row['name'] != EPOCH:
            continue
        children = {}
        for child in by_parent.get(row['span_id'], ()):
            children[child['name']] = \
                children.get(child['name'], 0.0) + child['duration']
        out.append(dict(row, children=children))
    return out


# ------------------------------------------------------------- the trace
def load_trace(run):
    """The traced epoch in ``trace_reduce``'s plain form; None in an
    untraced run, or where the window is not the runner's two marks."""
    source = run.extra.get('trace_source')
    if not run.trace or source is None or source[1] is not None:
        return None
    return trace_reduce.load_xplane(trace_reduce.find_xplane(source[0]))


def annotations(trace, lo, hi, prefix=EPOCH):
    """[(name, lo_ns, hi_ns)] of the host-line events named ``prefix``
    or ``prefix.<phase>``, clipped to the window."""
    out = []
    for plane in trace['planes']:
        if plane['name'].startswith('/device:'):
            continue
        for line in plane['lines']:
            for name, start, dur in line['events']:
                if (name == prefix or name.startswith(prefix + '.')) \
                        and start + dur > lo and start < hi:
                    out.append((name, max(start, lo), min(start + dur, hi)))
    return sorted(out, key=lambda n: n[1])


def _overlap(gaps, intervals):
    """The parts of ``gaps`` that lie inside ``intervals``: their
    lengths."""
    parts = (min(b, hi) - max(a, lo)
             for a, b in gaps for lo, hi in intervals)
    return [p for p in parts if p > 0]


def idle_by_annotation(trace, window=None, prefix=EPOCH):
    """Where the device's idle time of the window falls, by the
    program's annotation the host was under: ``{'idle_s', 'window_s',
    'spans': {name: [host_s, idle_s, gaps, longest_gap_s]}, 'self_s':
    [host_s, idle_s], 'unannotated_s', 'events'}``. Idle is what
    ``trace_reduce.reduce`` calls idle: the gaps of the union of the
    ``XLA Ops`` intervals, the mean over the device planes. ``spans``
    are the phases (``prefix.<phase>``) with the number of gaps that
    reach into each and the longest of them — many short gaps are the
    device's own, between the ops of queued programs, a few long ones
    the host's; ``self_s`` is what lies under ``prefix`` itself and
    under none of its phases. None where the trace holds no device
    plane or no such annotation."""
    lo, hi = window or trace_reduce.marks(trace)
    planes = trace_reduce.device_planes(trace)
    notes = annotations(trace, lo, hi, prefix)
    if not planes or not notes:
        return None
    phases = {}
    for name, a, b in notes:
        if name != prefix:
            phases.setdefault(name, []).append((a, b))
    covered, under_any = trace_reduce.union([(a, b) for _, a, b in notes])
    spans = {name: [sum(b - a for a, b in ivs), 0, 0, 0]
             for name, ivs in phases.items()}
    idle = annotated = 0
    for plane in planes:
        ops = trace_reduce.line_events(plane, trace_reduce.OP_LINE)
        _, merged = trace_reduce.union(
            trace_reduce.clip([(s, s + d) for _, s, d in ops], lo, hi))
        gaps = trace_reduce.gaps(merged, lo, hi)
        idle += sum(b - a for a, b in gaps)
        annotated += sum(_overlap(gaps, under_any))
        for name, ivs in phases.items():
            parts = _overlap(gaps, ivs)
            row = spans[name]
            row[1] += sum(parts)
            row[2] += len(parts)
            row[3] = max([row[3]] + parts)
    n = len(planes)
    in_phases = sum(row[0] for row in spans.values())
    idle_in_phases = sum(row[1] for row in spans.values())
    return {
        'idle_s': idle / n / 1e9, 'window_s': (hi - lo) / 1e9,
        'spans': {name: [host / 1e9, idle_ns / n / 1e9, count / n,
                         longest / 1e9]
                  for name, (host, idle_ns, count, longest)
                  in spans.items()},
        'self_s': [(covered - in_phases) / 1e9,
                   (annotated - idle_in_phases) / n / 1e9],
        'unannotated_s': (idle - annotated) / n / 1e9,
        'events': len(notes)}
