"""From a ``jax.profiler`` trace to numbers, with the window passed in.

A copy of the interval arithmetic of the program's
``telemetry/trace_parse.py`` (union of intervals, op base names), with
the one change that matters: the window is NOT [first op, last op] of
what was captured — which cuts off exactly the host gaps an idle share
exists to show — but the two host marks the runner put into the trace
(``bench_window_open`` / ``bench_window_close`` annotations), or any
``(lo, hi)`` the caller gives.

The functions below work on plain data, ``{'planes': [{'name', 'lines':
[{'name', 'events': [[name, start_ns, dur_ns], ...]}]}]}``, so that they
can be checked on a small recorded trace without a chip;
``load_xplane`` makes that from the ``.xplane.pb`` the profiler writes.

What a real v5e trace looks like is written down in ``PERF.md`` (§3).
"""

import glob
import os
import re

OPEN_MARK, CLOSE_MARK = 'bench_window_open', 'bench_window_close'
_SUFFIX_RE = re.compile(r'\.\d+$')

#: lines of a device plane that hold one event per HLO op / per program
OP_LINE, MODULE_LINE = 'XLA Ops', 'XLA Modules'


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return files[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({'name': line.name, 'events': events})
        planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def describe(trace: dict, top=8) -> list:
    """A by-hand look: every plane and line with its event count, span
    and most frequent names."""
    out = []
    for plane in trace['planes']:
        for line in plane['lines']:
            evs = line['events']
            if not evs:
                continue
            names = {}
            for name, _, dur in evs:
                row = names.setdefault(op_base_name(name), [0, 0])
                row[0] += 1
                row[1] += dur
            ranked = sorted(names.items(), key=lambda kv: -kv[1][1])
            if line['name'] == OP_LINE:
                calls = {}
                for name, _, dur in evs:
                    if 'custom-call' in op_base_name(name):
                        row = calls.setdefault(str(name)[:700], [0, 0])
                        row[0] += 1
                        row[1] += dur
                out.append({'plane': plane['name'],
                            'line': 'custom calls of ' + line['name'],
                            'events': len(calls), 'first_ns': 0,
                            'last_ns': 0,
                            'top': [[n, c, d] for n, (c, d) in sorted(
                                calls.items(), key=lambda kv: -kv[1][1])
                                    [:12]]})
            out.append({
                'plane': plane['name'], 'line': line['name'],
                'events': len(evs),
                'first_ns': min(e[1] for e in evs),
                'last_ns': max(e[1] + e[2] for e in evs),
                'top': [[n, c, d] for n, (c, d) in ranked[:top]]})
    return out


# ------------------------------------------------------- interval algebra
def union(intervals):
    """(total length, merged list) of possibly overlapping intervals."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return sum(hi - lo for lo, hi in merged), merged


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(merged, lo, hi):
    """The parts of [lo, hi] no merged interval covers."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


#: ops that only enclose other ops of the same line (a scan over layers
#: is a ``while`` whose body's ops are events of their own)
WRAPPERS = ('while', 'conditional', 'call')


def op_base_name(name: str) -> str:
    """A TPU trace names an op by its whole HLO text, ``fusion.12 =
    bf16[..]{..} fusion(...)``: the name is what stands before `` = ``,
    without its ``.N``."""
    head = str(name).split(' = ', 1)[0].strip().lstrip('%')
    return _SUFFIX_RE.sub('', head)


def op_label(name: str) -> str:
    """Base name plus the shape of the result, which says far more than
    ``fusion``: ``copy u8[50000,32,32,3]``."""
    text = str(name)
    base = op_base_name(text)
    if ' = ' not in text:
        return base
    shape = text.split(' = ', 1)[1].lstrip('(').split('{', 1)[0]
    return f'{base} {shape.split(" ")[0][:40]}'


# ------------------------------------------------------------- the window
def device_planes(trace: dict):
    return [p for p in trace['planes']
            if p['name'].startswith('/device:')
            and any(line['name'] == OP_LINE and line['events']
                    for line in p['lines'])]


def line_events(plane: dict, name: str):
    for line in plane['lines']:
        if line['name'] == name:
            return line['events']
    return []


def marks(trace: dict):
    """(open_ns, close_ns) of the runner's two host marks, on the
    trace's clock: the window starts where the open mark starts and
    ends where the close mark ends."""
    lo = hi = None
    for plane in trace['planes']:
        for line in plane['lines']:
            for name, start, dur in line['events']:
                if name == OPEN_MARK:
                    lo = start if lo is None else min(lo, start)
                elif name == CLOSE_MARK:
                    end = start + dur
                    hi = end if hi is None else max(hi, end)
    if lo is None or hi is None or hi <= lo:
        raise ValueError('the trace does not hold both window marks')
    return lo, hi


def reduce(trace: dict, window=None, phases=None, top=10) -> dict:
    """Busy and idle inside the window, the ops that took most time and
    the longest idle gaps.

    ``phases``: [(lo_ns, hi_ns, name)] on the trace's clock — what the
    host was doing (the runner knows: a train step's dispatches, an
    epoch boundary); each idle gap is named by the phase that holds
    most of it."""
    lo, hi = window or marks(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError('no device plane with XLA ops in the trace')
    busy_total, op_table, gap_rows, modules = 0.0, {}, [], {}
    for plane in planes:
        ops = line_events(plane, OP_LINE)
        inside = clip([(s, s + d) for _, s, d in ops], lo, hi)
        busy, merged = union(inside)
        busy_total += busy
        for name, start, dur in ops:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a and op_base_name(name) not in WRAPPERS:
                row = op_table.setdefault(op_label(name), [0, 0, name])
                row[0] += b - a
                row[1] += 1
        for name, start, dur in line_events(plane, MODULE_LINE):
            if start >= lo and start + dur <= hi:
                row = modules.setdefault(name, [0, 0])
                row[0] += dur
                row[1] += 1
        for a, b in gaps(merged, lo, hi):
            gap_rows.append((b - a, a, b))
    named = {}
    for length, a, b in gap_rows:
        named.setdefault(_phase_of(a, b, phases), []).append(length)
    idle_gaps = sorted(
        ([f'{name} (x{len(v)}, longest {max(v) / 1e6:.3f} ms)',
          sum(v) / 1e9 / len(planes)] for name, v in named.items()),
        key=lambda r: -r[1])[:top]
    device_ops = sorted(
        ([name, ns / 1e9 / len(planes)]
         for name, (ns, _, _) in op_table.items()),
        key=lambda r: -r[1])[:top]
    return {
        'window_s': (hi - lo) / 1e9,
        'busy_s': busy_total / 1e9 / len(planes),
        'devices': len(planes),
        'device_ops': device_ops,
        'idle_gaps': idle_gaps,
        'op_table': {n: [ns / 1e9 / len(planes), c, text]
                     for n, (ns, c, text) in op_table.items()},
        'modules': {n: [ns / 1e9 / len(planes), c / len(planes)]
                    for n, (ns, c) in modules.items()},
    }


def _phase_of(a, b, phases):
    best, best_len = 'unattributed', 0
    for lo, hi, name in phases or ():
        overlap = min(b, hi) - max(a, lo)
        if overlap > best_len:
            best, best_len = name, overlap
    return best
