#!/usr/bin/env python3
"""One cell of ``BENCHMARK.json``, once, in a fresh process.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in
a traced run), with the numbers ``correct`` compared, each beside its
limit, under ``compared`` as its last key; the same numbers are the last
lines of standard error. Anything else worth keeping goes on earlier
lines, or under the run's own directory ``.bench_out/<cell>/``.

Fails (non-zero, no result line) where jax finds no TPU, fewer chips
than the cell asks for, or a device kind that ``peaks.json`` does not
know; where a compile lands inside the measured window; where a job
does not end ``Success``.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Run:
    """The record of one run, filled by the job kind and read by the
    per-layer readers."""

    def __init__(self, manifest, workload, seed, seconds, trace, out):
        self.manifest = manifest
        self.workload = workload
        self.cell = manifest.cell(workload)
        self.config = manifest.config(self.cell['config'])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.out = out
        self.t_start = T_START
        self.require_chip = True
        self.device = None
        self.peaks = None
        self.window = None          # (t0, t1) host seconds
        self.task_id = None
        self.rate = None            # samples per second, whole window
        self.quiet_rate = None      # same, epochs with no profiler open
        self.attempted = self.failed = 0
        self.memory_peak = None
        self.correct = False
        self.compared = []
        self.extra = {}             # per-kind numbers for the readers
        self._reduced = None

    def note(self, text):
        print(f'[{self.workload}] {text}', file=sys.stderr, flush=True)

    def mark(self, what, at=None):
        """A line of the run's timeline: seconds since process start."""
        at = time.time() if at is None else at
        self.note(f'+{at - self.t_start:8.2f} s  {what}')

    # ------------------------------------------------------------ program
    def query(self, sql, args=()):
        """Rows of the program's own tables (spans, metric series)."""
        import contextlib
        import sqlite3
        import mlcomp_tpu
        path = os.path.join(mlcomp_tpu.DB_FOLDER, 'sqlite.db')
        with contextlib.closing(sqlite3.connect(path, timeout=30)) as db:
            db.row_factory = sqlite3.Row
            return db.execute(sql, args).fetchall()

    def series(self, name, task_id=None):
        """[(step, value, time)] of one metric series of the measured
        task, in step order."""
        return [(r['step'], r['value'], r['time']) for r in self.query(
            'select step, value, time from metric where task = ? and '
            'name = ? order by step, id',
            (task_id or self.task_id, name))]

    def note_spans(self, task_id):
        """The program's own spans of one task, for the set-up's
        breakdown (``task.load``, ``task.create_executor``, ...)."""
        rows = self.query(
            'select name, duration from telemetry_span where task = ? '
            'order by started', (task_id,))
        self.note(f'spans of task {task_id}: ' + ', '.join(
            f'{r["name"]} {r["duration"]:.2f}' for r in rows
            if r['duration'] is not None and r['duration'] >= 0.5))

    # -------------------------------------------------------------- trace
    def reduced(self):
        """The traced window, reduced (``trace_reduce.reduce``); None in
        an untraced run. The job kind leaves ``extra['trace_source']``:
        the trace's folder and either None — the window is the runner's
        two marks inside the trace — or the seconds of the runner's own
        clock around the whole child that wrote it."""
        source = self.extra.get('trace_source')
        if not self.trace or source is None or self._reduced is not None:
            return self._reduced
        from benchmark import trace_reduce
        folder, wall = source
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(folder))
        with open(os.path.join(self.out, 'trace_look.json'), 'w') as fh:
            json.dump(trace_reduce.describe(trace), fh, indent=1)
        planes = trace_reduce.device_planes(trace)
        if not planes and self.peaks is None:
            return None         # a CPU rehearsal has no device plane
        if wall is None:
            lo, hi = trace_reduce.marks(trace)
            # host seconds -> the trace's clock, by the open mark
            shift = lo - self.extra['trace_open_s'] * 1e9
            phases = [(a * 1e9 + shift, b * 1e9 + shift, name)
                      for a, b, name in self.extra.get('phases', ())]
            self._reduced = trace_reduce.reduce(trace, (lo, hi), phases)
            return self._reduced
        ops = [e for p in planes for e in trace_reduce.line_events(
            p, trace_reduce.OP_LINE)]
        lo = min(e[1] for e in ops)
        hi = max(e[1] + e[2] for e in ops)
        out = trace_reduce.reduce(
            trace, (lo, hi), [(lo, hi, 'inside the traced epoch')])
        out['idle_gaps'].insert(0, [
            'outside the traced epoch: interpreter, imports, DB, TPU '
            'client, data, cache loads, checkpoint, export',
            wall - (hi - lo) / 1e9])
        out['window_s'] = wall
        self._reduced = out
        return out

    # ------------------------------------------------------------ correct
    def check_training(self, job, program, feeds, param_spec, dataset):
        """Follow the timed job's first three steps with the plain
        reference and judge (``correct.py``)."""
        import numpy as np
        from benchmark import correct, weights
        from benchmark.reference import common
        family = self.manifest.reference(self.config['reference'])
        spec = family.param_spec(job['model'])
        theirs = {p: (tuple(s), str(np.dtype(d)))
                  for p, (s, d) in param_spec.items()}
        ours = {p: (tuple(s), str(np.dtype(d)))
                for p, (s, d) in spec.items()}
        if ours != theirs:
            odd = sorted(set(ours.items()) ^ set(theirs.items()))[:6]
            raise RuntimeError(
                f'the program runs other parameters than the '
                f'configuration states: {odd}')
        self.extra['reference_inputs'] = (job, feeds, dataset)
        params, feeds = reference_inputs(family, self.seed, job, feeds,
                                         dataset)
        ref = family.train(job, params, feeds, steps=len(feeds))
        opt = job['optimizer']
        gaps = correct.training_gaps(
            program, ref, lambda m: common.first_gradient(opt, m))
        self.correct, self.compared = correct.judge(
            gaps, self.cell['limits'])
        self.extra['gaps'] = gaps
        self.note(f'losses program {program["loss"]} reference '
                  f'{ref["loss"]}; worst leaves {gaps["where"]}')


    def check_task(self, job, losses, checkpoint, ref):
        """A short task's per-step losses and last checkpoint against
        the reference child's: the loss of every step, the momentum
        after the last step (under ``grad_gap*``) and the parameters'
        change over all of them."""
        import numpy as np
        from benchmark import correct, weights
        start = weights.make_params(self.seed, {
            k: (v.shape, v.dtype) for k, v in checkpoint['params'].items()})
        norm = lambda a: float(np.sqrt(np.sum(np.square(  # noqa: E731
            np.asarray(a, np.float64)))))
        program = {
            'loss': [float(v) for v in losses],
            'moment_norm': {k: norm(v)
                            for k, v in checkpoint['moment'].items()},
            'delta_norm': {k: norm(np.asarray(v, np.float64)
                                   - np.asarray(start[k], np.float64))
                           for k, v in checkpoint['params'].items()}}
        reference = dict(ref, grad_norm=ref['moment_norm'])
        gaps = correct.training_gaps(program, reference, lambda m: m)
        self.correct, self.compared = correct.judge(
            gaps, self.cell['limits'])
        self.extra['gaps'] = gaps
        self.note(f'losses program {program["loss"]} reference '
                  f'{ref["loss"]}; worst leaves {gaps["where"]}')


def reference_inputs(family, seed, job, feeds, dataset):
    """(seeded weights, feeds with the resident rows) as a family's
    ``train`` takes them — for the reference, the control and faults."""
    from benchmark import weights
    params = weights.make_params(seed, family.param_spec(job['model']))
    resident = family.resident(dataset) \
        if hasattr(family, 'resident') else {}
    return params, [dict(resident, feed=f) for f in feeds]


def device_record(chips: int, manifest):
    """The device as jax reports it; fails where it is not a TPU the
    table of peaks knows, or holds fewer chips than the cell asks."""
    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != 'tpu':
        raise SystemExit(
            f'no accelerator: jax reports {first.platform} '
            f'({first.device_kind}); this benchmark measures a TPU')
    if len(devices) < chips:
        raise SystemExit(
            f'the cell asks for {chips} chips, jax finds {len(devices)}')
    peaks = manifest.peaks(first.device_kind)
    return {'platform': first.platform, 'kind': first.device_kind,
            'count': len(devices)}, peaks


def result_line(run: Run) -> dict:
    group = 'per_layer' if run.trace else 'end_to_end'
    metrics = {}
    for entry in run.manifest.metrics(group, run.workload):
        name = entry['name']
        if group == 'end_to_end':
            value = run.extra['end_to_end'].get(name)
        else:
            value = run.manifest.reader(name)(run, name)
        if value is not None:
            metrics[name] = {'value': value, 'unit': entry['unit']}
    device = dict(run.device, memory_peak_bytes=run.memory_peak)
    line = {'correct': bool(run.correct), 'attempted': run.attempted,
            'failed': run.failed, 'metrics': metrics, 'device': device}
    reduced = run.reduced()
    if reduced:
        device['busy_s'] = reduced['busy_s']
        device['window_s'] = reduced['window_s']
        line['breakdown'] = {'device_ops': reduced['device_ops'],
                             'idle_gaps': reduced['idle_gaps']}
    line['compared'] = run.compared
    return line


def run_cell(manifest, workload, seed, seconds, trace, out,
             require_chip=True):
    """Drive one run; returns the result object (not printed)."""
    import importlib
    run = Run(manifest, workload, seed, seconds, trace, out)
    run.require_chip = require_chip
    chips = int(run.cell['entry']['chips'])
    kind = importlib.import_module(f'benchmark.{run.cell["kind"]}')
    if not getattr(kind, 'HOLDS_CHIP', True):
        pass        # the kind's children hold the chip and report it
    elif require_chip:
        run.device, run.peaks = device_record(chips, manifest)
    else:                        # a rehearsal: never a device metric
        import jax
        first = jax.devices()[0]
        run.device = {'platform': first.platform,
                      'kind': first.device_kind,
                      'count': len(jax.devices())}
    kind.run(run)
    run.extra.setdefault('end_to_end', {})
    run.extra['end_to_end'].setdefault(
        'setup_s', run.window[0] - run.t_start)
    line = result_line(run)
    for name, value, limit in run.compared:
        print(f'compared {name} {value} limit {limit}',
              file=sys.stderr, flush=True)
    return line


def place_run(root: str, workload: str):
    """The run's own directory and the program's root inside it, both
    inside the checkout and made anew; the compile cache at its fixed
    place. All before anything imports jax or the program."""
    out = os.path.join(root, '.bench_out', workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    os.environ['MLCOMP_TPU_ROOT'] = os.path.join(out, 'root')
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                          os.path.join(root, '.jax_cache'))
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    os.environ.setdefault('CONSOLE_LOG_LEVEL', 'WARNING')
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark.manifest import Manifest
    manifest = Manifest(ROOT)
    manifest.workload(args.workload)            # unknown cell: KeyError
    out = place_run(ROOT, args.workload)
    if not os.path.isdir(os.path.join(ROOT, 'mlcomp_tpu')):
        raise SystemExit('the system under test (mlcomp_tpu/) is not '
                         'in this directory')
    line = run_cell(manifest, args.workload, args.seed, args.seconds,
                    args.trace, out)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
