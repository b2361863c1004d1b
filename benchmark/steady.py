"""Job kind ``steady``: the runner's own process holds the chip and
drives ``jax_train`` through the path ``mlcomp_tpu execute`` takes
(``_dag(config, debug=True)`` -> DB rows -> ``execute_by_id`` ->
``ExecuteBuilder`` -> ``JaxTrain.work``), twice:

1. a **warm job** of one (short) epoch: fills the compile cache,
   compiles the train and eval steps, gives the seconds per epoch;
2. the **measured job** of ``1 + K`` epochs through the same entry.
   Its epoch 0 re-traces (set-up) and its first three steps are what
   ``correct`` compares; the window runs from the end of epoch 0 to the
   end of epoch K on the host clock. Every epoch end follows
   validation's device->host pull, so the window is closed by finished
   work, and everything between the two instants counts.

Between the two jobs the warm job's device state is gone (checked:
``jax.live_arrays()`` is empty).
"""

import copy
import gc
import json
import os

from . import hooks, traffic


#: the reference follows the timed job's first steps: this many
COMPARE_STEPS = 3


class MeasurementFault(Exception):
    """The run cannot stand as a measurement (a compile inside the
    window, a job that did not end Success, device state left over)."""


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base or {})
    for key, value in (over or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], value)
        else:
            out[key] = copy.deepcopy(value)
    return out


def program_seed(seed: int) -> int:
    """``jax_train`` seeds numpy with ``seed * 1000 + epoch``, which has
    to stay under 2**32."""
    return int(seed) % 1000003


def job_spec(cell: dict, config: dict, seed: int) -> dict:
    """The ``jax_train`` keys the reference needs of a job: model,
    optimizer, augment, batch, the program's seed."""
    ex = merge(config['executor'], cell.get('executor'))
    return {'model': ex['model'], 'optimizer': ex['optimizer'],
            'augment': ex.get('augment'), 'loss': ex['loss'],
            'batch_size': int(ex['batch_size']),
            'seed': program_seed(seed)}


def dag_config(cell, config, name, dataset, epochs, seed, extra=None):
    """The DAG file of one job: one ``jax_train`` executor."""
    ex = merge(config['executor'], cell.get('executor'))
    optimizer = ex.pop('optimizer')
    ex.update({
        'type': 'jax_train', 'cores': int(cell['entry']['chips']),
        'dataset': dataset, 'seed': program_seed(seed),
        'stages': [{'name': 'stage1', 'epochs': int(epochs),
                    'optimizer': optimizer}]})
    ex = merge(ex, extra)
    return {'info': {'name': name, 'project': name},
            'executors': {'train': ex}}


def run_job(folder, dag, probe):
    """Write the DAG file and run its one task through the entry
    ``mlcomp_tpu execute`` uses. Returns the task's id."""
    import yaml
    from mlcomp_tpu.__main__ import _dag
    from mlcomp_tpu.db.enums import TaskStatus
    from mlcomp_tpu.db.providers import TaskProvider
    from mlcomp_tpu.worker.storage import link_project_folders
    from mlcomp_tpu.worker.tasks import execute_by_id

    os.makedirs(folder, exist_ok=True)
    path = os.path.join(folder, 'config.yml')
    with open(path, 'w') as fh:
        yaml.safe_dump(dag, fh, sort_keys=False)
    session, _, tasks, cfg = _dag(path, debug=True)
    link_project_folders(folder, cfg['info']['project'])
    (task_id,) = [tid for ids in tasks.values() for tid in ids]
    with hooks.installed(probe):
        execute_by_id(task_id, exit=False, folder=folder, session=session)
    status = TaskProvider(session).by_id(task_id).status
    if status != int(TaskStatus.Success):
        raise MeasurementFault(f'task {task_id} ended {status}')
    return task_id


def drop_device_state(note=print):
    """After a job: nothing of it may stay on the device. What is left
    over is named (shape, dtype, who refers to it); more than 2% of
    the device's memory is a fault of the measurement."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    live = [a for a in jax.live_arrays() if a.nbytes > 4096]
    left = sum(a.nbytes for a in live)
    if left <= 1 << 20:
        return left
    for a in sorted(live, key=lambda a: -a.nbytes)[:6]:
        holders = [type(r).__name__ for r in gc.get_referrers(a)
                   if r is not live]
        note(f'left on the device: {a.shape} {a.dtype} held by '
             f'{holders[:6]}')
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get('bytes_limit', 0)
    note(f'{left} bytes of device arrays outlive the job; in use '
         f'{stats.get("bytes_in_use")} of {limit}')
    if not limit or left > 0.02 * limit:
        raise MeasurementFault(
            f'{left} bytes of device arrays outlive the job')
    return left


def device_peak(ctx, in_use: int) -> int:
    """The peak on the chip. The v5e runtime's ``peak_bytes_in_use``
    counts arrays only: a running program's temporaries are reserved
    apart ("at the bottom of memory", as its own out-of-memory message
    says) and show in no allocator reading. So the peak is the larger
    of the allocator's peak and the arrays in use during the window
    plus the temporaries of the train step, which the program's own
    ``memory.attribution`` row gives (XLA's analysis of the compiled
    step). Where no such row exists, the allocator's peak alone."""
    import jax
    peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
               for d in jax.local_devices())
    temp = step_temporaries(ctx, ctx.task_id)
    ctx.note(f'memory: allocator peak {peak}, in use during the window '
             f'{in_use}, train step temporaries {temp}')
    return int(max(peak, in_use + temp))


def step_temporaries(ctx, task_id) -> int:
    """``temp_bytes`` of a task's ``memory.attribution`` row (0 where
    the program wrote none, as on the CPU)."""
    rows = ctx.query("select tags from metric where task = ? and "
                     "name = 'memory.attribution'", (task_id,))
    return max((json.loads(r['tags'] or '{}').get('temp_bytes', 0)
                for r in rows), default=0)


def run(ctx):
    """One run of a ``steady`` cell. ``ctx`` is the harness's record of
    the run (``run.py``); this fills it."""
    import jax
    import numpy as np

    cell, config, seed = ctx.cell, ctx.config, ctx.seed
    data = cell['data']
    steps_per_epoch = int(data['train_rows']) // int(
        merge(config['executor'], cell.get('executor'))['batch_size'])
    spec = job_spec(cell, config, seed)
    compiles = hooks.CompileLog().install()

    # ---- traffic, from the seed
    dataset = traffic.write(data, seed, os.path.join(ctx.out, 'data'))
    warm_data = merge(data, cell.get('warm_data'))
    warm_dataset = dataset if warm_data == data else traffic.write(
        warm_data, seed, os.path.join(ctx.out, 'data_warm'))
    warm_steps = int(warm_data['train_rows']) // spec['batch_size']

    # ---- warm job
    extra = {}
    if ctx.trace:
        # the program's own sampled profiler would collide with the
        # benchmark's trace of a whole epoch
        extra = merge(extra, {'telemetry': {'profile_every': 0}})
    ctx.mark('data written')
    warm = hooks.Probe(seed)
    run_job(os.path.join(ctx.out, 'warm'),
            dag_config(cell, config, 'warm', warm_dataset, 2, seed,
                       extra), warm)
    # the warm job's second epoch has nothing left to compile or load:
    # its length, scaled to the measured job's steps (the boundary is
    # scaled with it, which only makes K smaller)
    epoch_s = (warm.epoch_ends[1] - warm.epoch_ends[0]) \
        * steps_per_epoch / warm_steps
    k = max(1, round(ctx.seconds / epoch_s))
    if ctx.trace:
        k = max(k, 2)           # the last epoch traced, the others not
    ctx.note(f'warm job: second epoch of {warm_steps} steps -> '
             f'{epoch_s:.2f} s an epoch of {steps_per_epoch}, K={k}')
    ctx.mark('warm job done')
    del warm
    drop_device_state(ctx.note)

    # ---- measured job: epoch 0 is set-up, epochs 1..K the window
    probe = hooks.Probe(
        seed, capture_steps=COMPARE_STEPS,
        trace_dir=os.path.join(ctx.out, 'trace') if ctx.trace else None,
        trace_epoch=k if ctx.trace else None)
    task_id = run_job(os.path.join(ctx.out, 'measured'),
                      dag_config(cell, config, 'measured', dataset,
                                 1 + k, seed, extra), probe)
    ends = probe.epoch_ends
    ctx.mark('window opened', ends[0] if ends else None)
    if len(ends) != 1 + k:
        raise MeasurementFault(f'{len(ends)} epoch ends for {1 + k}')
    t0, t1 = ends[0], ends[-1]
    inside = compiles.inside(t0, t1)
    if inside:
        raise MeasurementFault(
            f'{len(inside)} backend compiles inside the window: '
            f'{[round(b - a, 3) for a, b in inside]} s')

    rows_per_epoch = steps_per_epoch * spec['batch_size']
    per_row = int(cell.get('samples_per_row', 1))
    periods = [b - a for a, b in zip(ends, ends[1:])]
    ctx.window = (t0, t1)
    ctx.task_id = task_id
    ctx.rate = k * rows_per_epoch * per_row / (t1 - t0)
    # the rate of the epochs no profiler was open in (a traced run's
    # mfu is taken from these)
    quiet = [p for i, p in enumerate(periods, start=1)
             if i != probe.trace_epoch]
    ctx.quiet_rate = len(quiet) * rows_per_epoch * per_row / sum(quiet)
    ctx.steps_per_epoch = steps_per_epoch
    ctx.attempted, ctx.failed = k, 0
    ctx.extra['end_to_end'] = {cell['rate_metric']: ctx.rate}
    if probe.trace_marks:
        ctx.extra['trace_source'] = (probe.trace_dir, None)
        ctx.extra['trace_open_s'] = probe.trace_marks[0]
        e, d = probe.trace_epoch, probe.dispatch
        first, last = d[e * steps_per_epoch], d[(e + 1) * steps_per_epoch - 1]
        ctx.extra['phases'] = [
            (ends[e - 1], first, 'epoch_start: shuffle, first dispatch'),
            (first, last, 'train_steps: input and dispatch'),
            (last, ends[e], 'epoch_boundary: last steps drain, '
             'validation, metric pull, series write')]
        ctx.extra['traced_steps'] = steps_per_epoch
    ctx.mark('window closed')
    ctx.note_spans(task_id)
    ctx.memory_peak = device_peak(ctx, max(probe.in_use, default=0))

    # ---- what `correct` compares: pull the few numbers, free the rest
    program = {
        'loss': [float(v) for v in probe.losses],
        'moment_norm': {k_: float(v)
                        for k_, v in probe.moment_norm.items()},
        'delta_norm': {k_: float(v)
                       for k_, v in probe.delta_norm.items()},
    }
    feeds = [np.asarray(f) for f in probe.feeds]
    param_spec = probe.param_spec
    del probe
    drop_device_state(ctx.note)
    ctx.check_training(spec, program, feeds, param_spec, dataset)
    ctx.mark('reference done')
