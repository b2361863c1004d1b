"""Job kind ``task``: short ``jax_train`` tasks, each a child process
``python -m mlcomp_tpu execute <config>``, one at a time — one cell of a
hyperparameter grid as a grid's user pays for it: interpreter and
imports, DB, TPU client start, dataset build, compile-cache loads, the
introspection compile, a few steps, validation, checkpoint and export.

The runner stays off the chip: it pins its OWN jax to the CPU (to make
the seeded weights and to read the checkpoint back) and gives the
children an environment without that pin.

One **warm task** first (set-up: it fills the compile cache, as the
first cell of a grid does for the rest), then measured tasks back to
back until the first one that ends after ``--seconds``; at least one
always completes. ``task_wall_s`` is the window (first measured child's
start to the last one's exit) over the tasks completed. A task that does
not end ``Success`` is ``failed``.

``correct``: every task trains from the benchmark's seeded weights
(handed over through the program's own ``params_file``) on the
benchmark's rows in the order the configuration's seed gives; the first
measured task's per-step losses and its last checkpoint (the momentum
and the parameters after all of its steps) are compared with the plain
reference following every step — run as a child too, on the chip, once
the window has closed.
"""

import json
import os
import subprocess
import sys
import time

from . import traffic
from .steady import (
    MeasurementFault, dag_config, job_spec, merge, step_temporaries,
)

HOLDS_CHIP = False


def child_env(ctx):
    env = dict(os.environ)
    env.pop('JAX_PLATFORMS', None)
    env.update(ctx.cell.get('child_env') or {})
    env['PYTHONUNBUFFERED'] = '1'
    return env


def run_child(ctx, argv, log, timeout=900):
    """One child to its end. Returns (start, end, rc, output)."""
    path = os.path.join(ctx.out, log)
    start = time.time()
    with open(path, 'w') as fh:
        proc = subprocess.Popen(
            [sys.executable] + argv, cwd=ctx.manifest.root, stdout=fh,
            stderr=subprocess.STDOUT, env=child_env(ctx),
            start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise MeasurementFault(f'{argv[:3]} ran over {timeout} s')
    end = time.time()
    with open(path, errors='replace') as fh:
        return start, end, rc, fh.read()


def last_json(text):
    for line in reversed(text.strip().splitlines()):
        if line.startswith('{'):
            return json.loads(line)
    return None


def run_task(ctx, name, dag):
    """One ``mlcomp_tpu execute`` child. Returns its record."""
    import yaml
    folder = os.path.join(ctx.out, name)
    os.makedirs(folder, exist_ok=True)
    path = os.path.join(folder, 'config.yml')
    with open(path, 'w') as fh:
        yaml.safe_dump(dag, fh, sort_keys=False)
    start, end, rc, out = run_child(
        ctx, ['-m', 'mlcomp_tpu', 'execute', path], f'{name}.log')
    statuses = last_json(out) if rc == 0 else None
    row = ctx.query('select id from task order by id desc limit 1')
    return {'name': name, 'start': start, 'end': end, 'rc': rc,
            'ok': statuses == {'train': 'Success'},
            'task_id': row[0]['id'] if row else None, 'log': out[-2000:]}


def logged_json(ctx, task_id, tag):
    rows = ctx.query(
        'select message from log where task = ? and message like ? '
        'order by id limit 1', (task_id, f'%{tag}: {{%'))
    if not rows:
        return None
    return json.loads(rows[0]['message'].split(f'{tag}: ', 1)[1])


def write_weights(ctx, spec_model, path):
    """The seeded weights in the program's ``params_file`` npz layout
    (``params/<path>``)."""
    import numpy as np
    from . import weights
    family = ctx.manifest.reference(ctx.config['reference'])
    values = weights.make_params(ctx.seed, family.param_spec(spec_model))
    np.savez(path, **{f'params/{k}': np.asarray(v)
                      for k, v in values.items()})


def read_checkpoint(folder):
    """{'params': {path: array}, 'trace': {path: array}} of a task's
    last checkpoint (a flat msgpack of the train state)."""
    from flax import serialization
    with open(os.path.join(folder, 'last.msgpack'), 'rb') as fh:
        state = serialization.msgpack_restore(fh.read())

    def flat(tree, prefix=()):
        out = {}
        for key, value in tree.items():
            if isinstance(value, dict):
                if set(value) == {'value'}:      # a boxed leaf
                    out['/'.join(prefix + (key,))] = value['value']
                else:
                    out.update(flat(value, prefix + (key,)))
            else:
                out['/'.join(prefix + (key,))] = value
        return out

    def find(node, name):
        if isinstance(node, dict):
            if name in node:
                return node[name]
            for child in node.values():
                got = find(child, name)
                if got is not None:
                    return got
        return None

    moment = find(state['opt_state'], 'trace')
    if moment is None:
        moment = find(state['opt_state'], 'mu')
    return {'params': flat(state['params']), 'moment': flat(moment)}


def _task_folder(task_id):
    import mlcomp_tpu
    return os.path.join(mlcomp_tpu.TASK_FOLDER, str(task_id))


def run(ctx):
    os.environ['JAX_PLATFORMS'] = 'cpu'      # the runner's own jax only
    cell, config, seed = ctx.cell, ctx.config, ctx.seed
    spec = job_spec(cell, config, seed)
    steps = int(cell['data']['train_rows']) // spec['batch_size']
    dataset = traffic.write(cell['data'], seed,
                            os.path.join(ctx.out, 'data'))
    weights_path = os.path.join(ctx.out, 'weights.npz')
    write_weights(ctx, spec['model'], weights_path)
    extra = {'model': {'params_file': weights_path}}

    def dag(name, traced=False):
        more = merge(extra, {'profile': {'epoch': 0}}) if traced else extra
        return dag_config(cell, config, name, dataset, 1, seed, more)

    warm = run_task(ctx, 'warm', dag('warm'))
    if not warm['ok']:
        raise MeasurementFault(f'the warm task failed:\n{warm["log"]}')
    where = logged_json(ctx, warm['task_id'], 'devices')
    ctx.device = {'platform': where['platform'], 'kind': where['kind'],
                  'count': len(where['ids'])}
    if ctx.require_chip:
        if where['platform'] != 'tpu':
            raise SystemExit(f'no accelerator: the task ran on {where}')
        ctx.peaks = ctx.manifest.peaks(where['kind'])

    tasks, t0 = [], time.time()
    while True:
        tasks.append(run_task(ctx, f'task{len(tasks)}',
                              dag(f'task{len(tasks)}', ctx.trace)))
        if tasks[-1]['end'] - t0 >= ctx.seconds:
            break
    t1 = tasks[-1]['end']
    done = [t for t in tasks if t['ok']]
    ctx.window = (t0, t1)
    ctx.attempted, ctx.failed = len(tasks), len(tasks) - len(done)
    if not done:
        raise MeasurementFault(f'no task ended Success:\n'
                               f'{tasks[-1]["log"]}')
    ctx.task_id = done[0]['task_id']
    ctx.extra['tasks'] = tasks
    ctx.note_spans(ctx.task_id)
    ctx.extra['train_rows'] = steps * spec['batch_size']
    ctx.extra['end_to_end'] = {'task_wall_s': (t1 - t0) / len(done)}
    if ctx.trace:
        ctx.extra['trace_source'] = (
            os.path.join(_task_folder(ctx.task_id), 'checkpoints',
                         'profile'), done[0]['end'] - done[0]['start'])
    # the child's own allocator readings plus its train step's
    # temporaries, which no allocator reading holds (steady.device_peak)
    used = [v for t in done
            for name in ('device0.hbm_peak', 'device0.hbm_used')
            for _, v, _ in ctx.series(name, t['task_id'])]
    temp = step_temporaries(ctx, ctx.task_id)
    ctx.note(f'memory: child allocator peak {max(used, default=0)}, '
             f'train step temporaries {temp}')
    ctx.memory_peak = int(max(used, default=0) + temp)

    # ---- correct: the first measured task against the reference child
    losses = [v for _, v, _ in ctx.series('loss', ctx.task_id)]
    ckpt = read_checkpoint(os.path.join(
        _task_folder(ctx.task_id), 'checkpoints'))
    job_path = os.path.join(ctx.out, 'reference_job.json')
    with open(job_path, 'w') as fh:
        json.dump({'workload': ctx.workload, 'seed': seed, 'job': spec,
                   'dataset': dataset, 'steps': steps,
                   'tiny': getattr(ctx.manifest, '_tiny', None)}, fh)
    _, _, rc, out = run_child(
        ctx, [os.path.join(ctx.manifest.home, 'task_reference.py'),
              job_path], 'reference.log')
    ref = last_json(out) if rc == 0 else None
    if ref is None:
        raise MeasurementFault(f'the reference child failed:\n'
                               f'{out[-2000:]}')
    ctx.check_task(spec, losses, ckpt, ref)
