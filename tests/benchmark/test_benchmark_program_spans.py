"""The readers of the program's own spans (``program_spans.py`` and the
three files under ``layer_metrics/`` that use it): device idle by the
annotation it falls under, on a small recorded trace with hand-computed
gaps; the span-row metrics on a seeded ``telemetry_span`` table; and the
manifest's side — every new entry finds its reader, and nothing the
accepted benchmark had was edited to make room."""

import copy
import json
import os

import pytest

import accepted
from benchmark import program_spans, trace_reduce
from benchmark.manifest import Manifest
from benchmark.run import Run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = 'resnet18-cifar10.steady'
NEW = ['epoch_boundary_ms.images', 'epoch_boundary_ms.tokens',
       'epoch_boundary_idle_ms.images', 'epoch_boundary_idle_ms.tokens',
       'setup_span_s.data', 'setup_span_s.state',
       'setup_span_s.introspect', 'setup_span_s.epoch0']


@pytest.fixture(scope='module')
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope='module')
def trace():
    with open(os.path.join(HERE, 'data', 'span_trace.json')) as fh:
        return json.load(fh)


def without_annotations(trace):
    bare = copy.deepcopy(trace)
    for plane in bare['planes']:
        for line in plane['lines']:
            line['events'] = [e for e in line['events']
                              if not e[0].startswith('train.')]
    return bare


# ------------------------------------------------------------- the trace
def test_idle_by_annotation_is_the_hand_computed_split(trace):
    split = program_spans.idle_by_annotation(trace)
    # the same idle and window as the accepted reduction
    reduced = trace_reduce.reduce(trace)
    assert split['window_s'] == pytest.approx(reduced['window_s'])
    assert split['idle_s'] == pytest.approx(
        reduced['window_s'] - reduced['busy_s']) == pytest.approx(4.0e-3)
    by_hand = {     # host ms, device-idle ms under it, gaps, longest
        'train.epoch.begin': (0.5, 0.4, 1, 0.4),
        'train.epoch.steps': (3.39, 0.1, 1, 0.1),
        'train.epoch.drain': (0.99, 0.1, 1, 0.1),
        'train.epoch.valid': (1.99, 0.29, 2, 0.19),
        'train.epoch.report': (2.78, 2.78, 1, 2.78)}
    assert list(split['spans']) == list(by_hand)
    for name, (host, idle, gaps, longest) in by_hand.items():
        assert split['spans'][name] == [
            pytest.approx(host * 1e-3), pytest.approx(idle * 1e-3),
            gaps, pytest.approx(longest * 1e-3)], name
    assert split['self_s'] == [pytest.approx(0.05e-3),
                               pytest.approx(0.03e-3)]
    assert split['unannotated_s'] == pytest.approx(0.3e-3)
    assert split['events'] == 6
    # every idle nanosecond is under a phase, the epoch itself, or none
    assert sum(row[1] for row in split['spans'].values()) \
        + split['self_s'][1] \
        + split['unannotated_s'] == pytest.approx(split['idle_s'])


def test_a_window_cuts_the_annotations(trace):
    # the second half of the window: valid from its start, report whole
    split = program_spans.idle_by_annotation(trace, (5_000_000, 10_000_000))
    assert list(split['spans']) == ['train.epoch.valid',
                                    'train.epoch.report']
    assert split['idle_s'] == pytest.approx(3.3e-3)
    assert split['spans']['train.epoch.valid'][1] == pytest.approx(0.29e-3)
    assert split['unannotated_s'] == pytest.approx(0.2e-3)


def test_no_annotation_is_none_never_zero(trace):
    bare = without_annotations(trace)
    assert program_spans.annotations(bare, 0, 10_000_000) == []
    assert program_spans.idle_by_annotation(bare) is None
    # nor without a device plane (a rehearsal on the CPU)
    host_only = {'planes': trace['planes'][1:]}
    assert program_spans.idle_by_annotation(host_only) is None


class TracedRun:
    """What the idle reader needs of a run, with the recorded trace in
    the place of the profiler's file."""
    trace = True

    def __init__(self, trace):
        self.extra = {'trace_source': ('unused', None)}
        self.notes = []
        self._trace = trace

    def note(self, text):
        self.notes.append(text)


def test_idle_reader_reports_ms_and_prints_the_split(
        manifest, trace, monkeypatch):
    read = manifest.reader('epoch_boundary_idle_ms.images')
    run = TracedRun(trace)
    monkeypatch.setattr(program_spans, 'load_trace', lambda r: r._trace)
    assert read(run, 'epoch_boundary_idle_ms.images') == \
        pytest.approx(3.7)
    text = '\n'.join(run.notes)
    assert ('train.epoch.report: host 2.780 ms, device idle 2.780 ms in '
            '1 gaps, longest 2.780 ms') in text
    assert 'under no annotation 0.300 ms of 4.000 ms idle' in text
    assert '6 annotation events' in text
    bare = TracedRun(without_annotations(trace))
    assert read(bare, 'epoch_boundary_idle_ms.images') is None
    assert bare.notes == []


def test_load_trace_only_where_the_window_is_the_runners_marks():
    class Untraced:
        trace, extra = False, {'trace_source': ('x', None)}

    class WholeChild:
        trace, extra = True, {'trace_source': ('x', 12.5)}

    class NoTrace:
        trace, extra = True, {}

    for run in (Untraced, WholeChild, NoTrace):
        assert program_spans.load_trace(run) is None


# ----------------------------------------------------------- the span rows
WARM, MEASURED, OLDER = 1, 2, 3


def seed_spans(session):
    """Two jobs as a ``steady`` run leaves them. The measured job's
    epochs end at 110, 120, 130, 140 s: the window is 110..140, and the
    profiler opens at 130.2, so epoch 3 is the traced one."""
    from mlcomp_tpu.db.providers import TelemetrySpanProvider
    rows, ids = [], iter(range(1, 1000))

    def add(task, name, started, duration, parent=None, tags=None):
        span_id = f's{next(ids)}'
        rows.append((span_id, parent, task, name, started, duration,
                     'ok', json.dumps(tags) if tags else None, None,
                     'train'))
        return span_id

    def job(task, t, setup, epochs):
        work = add(task, 'train.work', t, 1000.0)
        for name, seconds in setup.items():
            add(task, f'train.setup.{name}', t, seconds, work)
            t += seconds
        for number, (length, phases) in enumerate(epochs):
            epoch = add(task, 'train.epoch', t, length, work,
                        {'epoch': number, 'stage': 'stage1'})
            at = t
            for name, seconds in phases.items():
                add(task, f'train.epoch.{name}', at, seconds, epoch)
                at += seconds
            t += length + 0.001         # the seam

    job(WARM, 10.0, {'data': 3.0, 'state': 2.0, 'introspect': 4.0},
        [(20.0, {'begin': 0.004, 'steps': 19.0}),
         (1.0, {'begin': 0.004, 'steps': 0.9})])
    phases = [
        {'begin': 0.5, 'steps': 4.0, 'report': 0.5},
        {'begin': 0.003, 'steps': 9.0, 'drain': 0.5, 'valid': 0.4,
         'report': 0.020},
        {'begin': 0.005, 'steps': 9.0, 'drain': 0.5, 'valid': 0.4,
         'report': 0.030, 'checkpoint': 0.010},
        {'begin': 0.050, 'steps': 9.0, 'drain': 0.5, 'valid': 0.3,
         'report': 0.100}]
    job(MEASURED, 99.0, {'data': 2.5, 'state': 1.5, 'introspect': 1.0},
        [(5.999, phases[0])] + [(9.999, p) for p in phases[1:]])
    # a job of a program without the phases: one row an epoch, no child
    job(OLDER, 500.0, {}, [(5.0, {}), (5.0, {})])
    TelemetrySpanProvider(session).add_many(rows)


@pytest.fixture()
def run(manifest, session, tmp_path):
    seed_spans(session)
    run = Run(manifest, CELL, seed=1, seconds=20, trace=0,
              out=str(tmp_path))
    run.task_id = MEASURED
    run.window = (110.0, 140.0)
    return run


def test_rows_and_epochs(run):
    rows = program_spans.rows(run, MEASURED)
    assert {r['task'] for r in rows} == {MEASURED}
    assert len(program_spans.rows(run)) > len(rows)
    epochs = program_spans.epochs(rows)
    assert [e['tags']['epoch'] for e in epochs] == [0, 1, 2, 3]
    assert epochs[2]['children'] == {
        'train.epoch.begin': 0.005, 'train.epoch.steps': 9.0,
        'train.epoch.drain': 0.5, 'train.epoch.valid': 0.4,
        'train.epoch.report': 0.030, 'train.epoch.checkpoint': 0.010}


def test_epoch_boundary_ms_is_the_mean_over_the_windows_epochs(
        manifest, run):
    read = manifest.reader('epoch_boundary_ms.images')
    # epochs 1..3 lie in the window; a missing checkpoint span is 0 s
    assert read(run, 'epoch_boundary_ms.images') == pytest.approx(
        1e3 * ((0.003 + 0.020) + (0.005 + 0.030 + 0.010)
               + (0.050 + 0.100)) / 3)
    # a traced run leaves out the epoch the profiler was open in
    run.extra['trace_open_s'] = 130.2
    assert read(run, 'epoch_boundary_ms.images') == pytest.approx(
        1e3 * (0.023 + 0.045) / 2)
    # any task, any window: the warm job's two epochs
    run.task_id, run.window = WARM, (0.0, 1000.0)
    assert read(run, 'epoch_boundary_ms.images') == pytest.approx(4.0)
    # a program without the phases (the parent commit) reports nothing,
    # and so does a task without spans
    for task in (OLDER, 99):
        run.task_id = task
        assert read(run, 'epoch_boundary_ms.images') is None


def test_setup_span_s_sums_both_jobs(manifest, run):
    read = manifest.reader('setup_span_s.data')
    assert read(run, 'setup_span_s.data') == pytest.approx(5.5)
    assert read(run, 'setup_span_s.state') == pytest.approx(3.5)
    assert read(run, 'setup_span_s.introspect') == pytest.approx(5.0)
    assert read(run, 'setup_span_s.epoch0') == pytest.approx(30.999)
    assert read(run, 'setup_span_s.nothing_of_that_name') is None


# ------------------------------------------------------------ the manifest
def test_every_new_entry_resolves_to_its_reader(manifest):
    """PR 25's eight are there, in their order, each with its reader and
    listed for both cells the benchmark had then. Where later entries
    stand is ``accepted.py``'s to say, not this test's."""
    entries = {m['name']: m for m in manifest.data['per_layer']}
    for name in NEW:
        entry = entries[name]
        assert callable(manifest.reader(name))
        assert entry['source'] in ('program_span', 'device_trace')
        e2e = {m['name'] for cell in entry['workloads']
               for m in manifest.metrics('end_to_end', cell)}
        assert entry['moves'] in e2e
    assert [name for name in entries if name in NEW] == NEW
    for cell in ('resnet18-cifar10.steady', 'olmo-1b.steady'):
        names = [m['name'] for m in manifest.metrics('per_layer', cell)]
        assert len([n for n in names if n in NEW]) == 6, cell


def test_the_accepted_benchmark_is_byte_for_byte_as_it_was():
    """New files, new entries after the accepted ones, a cell's name
    appended to the lists of the metrics it reports, and nothing else
    (``accepted.as_accepted`` says it in full). A ``benchmark`` PR that
    edits an accepted file or entry runs ``data/make_accepted.py``."""
    snapshot = accepted.load_snapshot(ROOT)
    assert accepted.as_accepted(ROOT, snapshot) == []
    # the record holds what it is asked about, whatever their number
    held = snapshot['manifest']
    assert set(held) == set(accepted.TOP_LEVEL)
    assert all(path.split('/')[0] in ('benchmark', 'tests')
               for path in snapshot['files'])
    assert {c['file'] for c in held['configs']} <= set(snapshot['files'])
    assert {f'benchmark/workloads/{c["name"]}.json'
            for c in held['workloads']} <= set(snapshot['files'])
