#!/usr/bin/env python3
"""Bring-up proof: the main path of mlcomp_tpu, once, on the TPU chip.

    python chip_smoke.py              # one chip   (what the driver runs)
    python chip_smoke.py --multichip  # four chips (a 2x2 v5e host)

YAML DAG -> ``python -m mlcomp_tpu dag`` -> supervisor places tasks on
TPU cores -> worker daemon runs each task in a fresh ``run-task``
subprocess -> ``jax_train`` -> export -> ``python -m mlcomp_tpu.server
serve``, at the full width of CIFAR-10 ResNet-18 (bf16, batch 512,
HBM-resident uint8 dataset), then the LM flagship shape so the flash
kernel runs fwd+bwd inside the real train step. Evidence that the
system STARTS on the chip and computes the right thing there — not a
benchmark: every figure it prints is labelled with the device it ran
on and none is a performance claim.

Process model: THIS process never imports jax (nor
``mlcomp_tpu.train/models/ops``). A chip belongs to one process at a
time, so every phase that needs the chip is a child — started, waited
on with a timeout and reaped before the next one starts. Any failed
phase prints the failing task's log tail and exits non-zero; the last
line ``{"ok": true, "device": {...}}`` is printed only when every
phase passed. State lives under ``chiprun_out/chip_smoke/`` (an
isolated ``MLCOMP_TPU_ROOT``), nothing ignored by git is needed, and
the native helper is rebuilt from source at the start.

The phases are plain functions over a ``Ctx`` so the CPU rehearsal
(tests/test_chip_smoke.py) can call them at a tiny size.
"""

import argparse
import contextlib
import json
import math
import os
import signal
import socket
import sqlite3
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: db/enums.py TaskStatus
FAILED, SUCCESS = 3, 6
#: max |p_served - p_infer| for the same rows: both run the same bf16
#: export, at different batch shapes
PROB_ATOL = 0.03


class SmokeFailure(Exception):
    """A phase's check did not hold; the message says which."""


def say(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class Ctx:
    """Where a run keeps its state and what it expects of the device.

    ``platform`` is what every device-touching child must report;
    ``env`` is the environment of every child (the isolated root and,
    for a test's steering, whatever the test adds)."""

    def __init__(self, out, platform='tpu', env=None):
        self.out = os.path.abspath(out)
        self.root = os.path.join(self.out, 'root')
        self.platform = platform
        os.makedirs(self.root, exist_ok=True)
        self.env = dict(os.environ)
        self.env['MLCOMP_TPU_ROOT'] = self.root
        self.env['WEB_HOST'] = '127.0.0.1'
        self.env['PYTHONUNBUFFERED'] = '1'
        self.env.update(env or {})
        self.device = None      # filled by phase_device

    @property
    def label(self):
        """How every printed figure names its device."""
        if self.device is None:
            return self.platform
        return (f'{self.device["platform"]} {self.device["kind"]} '
                f'x{self.device["count"]}')

    def cpu_env(self):
        """Environment of a child that must stay off the chip."""
        return dict(self.env, JAX_PLATFORMS='cpu')

    def db(self):
        conn = sqlite3.connect(
            os.path.join(self.root, 'db', 'sqlite.db'), timeout=30)
        conn.row_factory = sqlite3.Row
        return conn

    def query(self, sql, args=()):
        with contextlib.closing(self.db()) as conn:
            return conn.execute(sql, args).fetchall()

    def path(self, *parts):
        path = os.path.join(self.out, *parts)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        return path


# ------------------------------------------------------------ processes
def free_port():
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def tail(path, n=60):
    try:
        with open(path, errors='replace') as fh:
            return ''.join(fh.readlines()[-n:])
    except OSError as e:
        return f'<{path}: {e}>'


def run_child(ctx, argv, log, timeout, env=None):
    """Run one child to its end; output to ``log``. Raises with the log
    tail on a non-zero exit or a timeout (the child is killed and
    reaped first). Returns the log text."""
    log = ctx.path('logs', log)
    with open(log, 'w') as fh:
        proc = subprocess.Popen(
            [sys.executable] + list(argv), cwd=HERE, stdout=fh,
            stderr=subprocess.STDOUT, env=env or ctx.env,
            start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            stop_group(proc, grace=10)
            raise SmokeFailure(
                f'{argv[:4]} still running after {timeout}s:\n'
                f'{tail(log)}')
    check(rc == 0, f'{argv[:4]} exited {rc}:\n{tail(log)}')
    with open(log, errors='replace') as fh:
        return fh.read()


def last_json(text):
    """The last line of a child's output that is a JSON object (the
    runtime may log after it)."""
    for line in reversed(text.strip().splitlines()):
        if line.startswith('{'):
            return json.loads(line)
    raise SmokeFailure(f'no JSON line in:\n{text[-2000:]}')


def group_alive(pgid):
    try:
        os.killpg(pgid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def stop_group(proc, grace=60):
    """SIGTERM the whole process group of ``proc`` (it was started in a
    session of its own, so task subprocesses belong to it), reap
    ``proc`` and wait until EVERY process of the group is gone;
    SIGKILL what outlives ``grace``. Returns proc's exit code."""
    pgid = proc.pid
    if group_alive(pgid):
        with contextlib.suppress(ProcessLookupError):
            os.killpg(pgid, signal.SIGTERM)
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        if proc.poll() is not None and not group_alive(pgid):
            return proc.returncode
        time.sleep(0.2)
    with contextlib.suppress(ProcessLookupError):
        os.killpg(pgid, signal.SIGKILL)
    proc.wait(timeout=30)
    deadline = time.monotonic() + 30
    while group_alive(pgid) and time.monotonic() < deadline:
        time.sleep(0.2)
    check(not group_alive(pgid),
          f'process group {pgid} survived SIGKILL')
    return proc.returncode


# PyYAML directly, not mlcomp_tpu.utils.io: importing the package here
# would bootstrap a root folder for THIS process's environment
def write_yaml(path, data):
    import yaml
    with open(path, 'w') as fh:
        yaml.safe_dump(data, fh, sort_keys=False)
    return path


def read_yaml(path):
    import yaml
    with open(path) as fh:
        return yaml.safe_load(fh)


# --------------------------------------------------------------- device
def phase_device(ctx):
    """A child asks jax what it runs on; anything but ``ctx.platform``
    fails the script. The values go into the last line."""
    out = run_child(ctx, ['-c', (
        'import json, jax\n'
        'd = jax.devices()\n'
        'print(json.dumps({"platform": d[0].platform, '
        '"kind": d[0].device_kind, "count": len(d)}))\n')],
        'device.log', timeout=300)
    device = last_json(out)
    check(device['platform'] == ctx.platform,
          f'jax found platform {device["platform"]!r}, not '
          f'{ctx.platform!r} — no accelerator, nothing to prove: '
          f'{device}')
    ctx.device = device
    say(f'device: {json.dumps(device)}')
    return device


def rebuild_native(ctx):
    """The ignored ``_mlcomp_native.so`` of a working tree is judged
    stale by mtime, which a copy does not preserve: always rebuild."""
    run_child(ctx, ['-c', 'from mlcomp_tpu import native; '
                          'print(native.build(force=True))'],
              'native.log', timeout=300, env=ctx.cpu_env())


# ------------------------------------------------------------------ dag
def resnet_config():
    """``examples/cifar10/config.yml`` as it stands (synthetic
    CIFAR-shaped data, the full 50k set, batch 512, bf16, on-device
    pad-crop/flip, ``cores: 1`` on the tasks that run the model) with
    the stage cut to 1 epoch and a device-profiler window every 20
    steps."""
    config = read_yaml(
        os.path.join(HERE, 'examples', 'cifar10', 'config.yml'))
    train = config['executors']['train']
    train['stages'][0]['epochs'] = 1
    train['telemetry'] = {'profile_every': 20}
    return config


class Deployment:
    """``python -m mlcomp_tpu.server start N`` as a child in a session
    of its own: site + supervisor + worker-supervisor + N workers."""

    def __init__(self, ctx, n_workers, name='server'):
        self.ctx = ctx
        self.log = ctx.path('logs', f'{name}.log')
        self.env = dict(ctx.env, WEB_PORT=str(free_port()))
        self._fh = open(self.log, 'w')
        self.proc = subprocess.Popen(
            [sys.executable, '-m', 'mlcomp_tpu.server', 'start',
             str(n_workers)],
            cwd=HERE, stdout=self._fh, stderr=subprocess.STDOUT,
            env=self.env, start_new_session=True)

    def check_alive(self):
        """The group restarts a dead child for ever: a child that keeps
        dying (a worker that cannot get the chip) must fail the wait,
        by name, not outlast it."""
        check(self.proc.poll() is None,
              f'`server start` exited {self.proc.returncode}:\n'
              f'{tail(self.log)}')
        deaths = {}
        with open(self.log, errors='replace') as fh:
            for line in fh:
                if line.startswith('child ') and ' exited ' in line:
                    spec = line.split(' exited ')[0]
                    deaths[spec] = deaths.get(spec, 0) + 1
        for spec, count in deaths.items():
            check(count < 3, f'{spec} died {count} times:\n'
                             f'{tail(self.log)}')

    def wait_computer(self, timeout=300):
        """Until the worker-supervisor's probe registered the host."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                rows = self.ctx.query('select name, cores from computer')
            except sqlite3.OperationalError:
                rows = []
            if rows:
                return dict(rows[0])
            time.sleep(1)
        raise SmokeFailure(
            f'no computer row after {timeout}s (the core probe never '
            f'finished):\n{tail(self.log)}')

    def submit(self, config_path, name):
        run_child(self.ctx, ['-m', 'mlcomp_tpu', 'dag', config_path],
                  f'{name}.submit.log', timeout=300,
                  env=dict(self.env, JAX_PLATFORMS='cpu'))
        return self.ctx.query('select max(id) from dag')[0][0]

    def wait_dag(self, dag, timeout):
        """Poll the DAG's tasks to a terminal state; returns them by
        name. A Failed/Stopped/Skipped task raises with its log."""
        deadline = time.monotonic() + timeout
        while True:
            self.check_alive()
            tasks = self.ctx.query(
                'select * from task where dag = ? order by id', (dag,))
            if tasks and all(t['status'] >= FAILED for t in tasks):
                break
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f'dag {dag} not finished after {timeout}s: '
                    f'{[(t["name"], t["status"]) for t in tasks]}\n'
                    f'{task_log(self.ctx, tasks)}\n{tail(self.log)}')
            time.sleep(1)
        bad = [t for t in tasks if t['status'] != SUCCESS]
        check(not bad,
              f'dag {dag}: '
              f'{[(t["name"], t["status"], t["failure_reason"]) for t in bad]}'
              f'\n{task_log(self.ctx, bad)}')
        return {t['name']: dict(t) for t in tasks}

    def stop(self):
        """Stop the group and wait until every process of it is gone."""
        try:
            stop_group(self.proc, grace=90)
        finally:
            self._fh.close()


def task_log(ctx, tasks, n=40):
    out = []
    for t in tasks:
        rows = ctx.query(
            'select message from log where task = ? order by id desc '
            'limit ?', (t['id'], n))
        out.append(f'--- task {t["id"]} {t["name"]} '
                   f'(status {t["status"]}) ---')
        out.extend(r['message'] for r in reversed(rows))
    return '\n'.join(out)


def series(ctx, task_id, name):
    """One metric series of a task: [(step, value)] in step order."""
    return [(r['step'], r['value']) for r in ctx.query(
        'select step, value from metric where task = ? and name = ? '
        'order by step, id', (task_id, name))]


def logged_json(ctx, task_id, tag):
    """The JSON of the ``<tag>: {...}`` line jax_train logs once:
    ``devices`` (where it runs) or ``placement`` (how the mesh splits a
    batch and the most-split parameter)."""
    rows = ctx.query(
        'select message from log where task = ? and message like ? '
        'order by id limit 1', (task_id, f'%{tag}: {{%'))
    check(rows, f'task {task_id} logged no {tag!r} line')
    return json.loads(rows[0]['message'].split(f'{tag}: ', 1)[1])


def check_train_task(ctx, task, cores, expect_series):
    """What the DB must show of a train task that ran on the chip."""
    check(json.loads(task['cores_assigned'] or '[]') == cores,
          f'task {task["name"]} ran on cores {task["cores_assigned"]}, '
          f'expected {cores}')
    where = logged_json(ctx, task['id'], 'devices')
    check(where['platform'] == ctx.platform,
          f'task {task["name"]} trained on {where}')
    loss = [v for _, v in series(ctx, task['id'], 'loss')]
    check(len(loss) >= 2, f'task {task["name"]}: loss series {loss}')
    check(all(math.isfinite(v) for v in loss),
          f'task {task["name"]}: non-finite loss {loss}')
    check(loss[-1] < loss[0],
          f'task {task["name"]}: loss did not fall '
          f'({loss[0]} -> {loss[-1]})')
    for name in expect_series:
        check(series(ctx, task['id'], name),
              f'task {task["name"]} wrote no {name!r} rows')
    return {'steps': len(loss), 'loss_first': loss[0],
            'loss_last': loss[-1], 'where': where}


#: series only a backend with memory stats / a real device timeline
#: writes; their absence on the chip is a silent fallback
HBM_SERIES = ('device0.hbm_limit', 'device0.hbm_used')
TPU_SERIES = HBM_SERIES + ('devtime.busy_frac', 'memory.attribution')


def phase_dag(ctx, config, expect_cores=1, expect_series=TPU_SERIES,
              timeout=900):
    """``server start 1`` + ``dag`` with per-task subprocesses and no
    core-count override; the assertions are read from the DB."""
    path = write_yaml(ctx.path('configs', config['info']['name'],
                               'config.yml'), config)
    dep = Deployment(ctx, 1)
    try:
        computer = dep.wait_computer()
        check(computer['cores'] == expect_cores,
              f'the core probe registered {computer}, expected '
              f'{expect_cores} cores')
        t0 = time.monotonic()
        tasks = dep.wait_dag(dep.submit(path, 'dag'), timeout)
        wall = time.monotonic() - t0
    finally:
        dep.stop()
    train = check_train_task(ctx, tasks['train'], [0], expect_series)
    check(tasks['valid']['score'] is not None,
          f'valid wrote no score: {tasks["valid"]}')
    compile_ms = sum(v for _, v in series(
        ctx, tasks['train']['id'], 'compile.backend_ms'))
    hbm = [v for _, v in series(ctx, tasks['train']['id'],
                                'device0.hbm_used')]
    say(f'dag [{ctx.label}]: train {train["steps"]} steps, loss '
        f'{train["loss_first"]:.4f} -> {train["loss_last"]:.4f}, '
        f'valid score {tasks["valid"]["score"]:.4f}, compile '
        f'{compile_ms / 1e3:.1f}s, submit-to-done {wall:.1f}s, hbm in '
        f'use max {max(hbm) / 2**30 if hbm else float("nan"):.2f} GiB, '
        f'devices {train["where"]}')
    return tasks


# ---------------------------------------------------------------- serve
def http_json(url, body=None, timeout=60):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={'Authorization': 'token',
                                 'Content-Type': 'application/json'})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def valid_rows(ctx, dataset, n):
    """The first ``n`` validation rows of a registered dataset, made
    by a CPU-only child (``mlcomp_tpu.train`` imports jax)."""
    out = ctx.path('serve_rows.npy')
    run_child(ctx, ['-c', (
        'import sys, json, numpy as np\n'
        'from mlcomp_tpu.train.data import create_dataset\n'
        'data = create_dataset(**json.loads(sys.argv[1]))\n'
        'np.save(sys.argv[2], data["x_valid"][:int(sys.argv[3])])\n'),
        json.dumps(dataset), out, str(n)],
        'rows.log', timeout=300, env=ctx.cpu_env())
    import numpy as np
    return np.load(out)


def phase_serve(ctx, model='cifar10_resnet18', project='cifar10',
                dataset=None, batches=3, batch_size=64):
    """Serve the export the dag phase trained, as a child of its own;
    its answers must equal the infer task's saved predictions."""
    import numpy as np
    rows = valid_rows(ctx, dataset or {'name': 'cifar10'},
                      batches * batch_size)
    want = np.load(os.path.join(
        ctx.root, 'data', project, 'pred', f'{model}.npy'))[:len(rows)]
    port = free_port()
    log = ctx.path('logs', 'serve.log')
    t0 = time.monotonic()
    with open(log, 'w') as fh:
        proc = subprocess.Popen(
            [sys.executable, '-m', 'mlcomp_tpu.server', 'serve', model,
             '--project', project, '--port', str(port),
             '--batch-size', str(batch_size), '--activation', 'softmax'],
            cwd=HERE, stdout=fh, stderr=subprocess.STDOUT, env=ctx.env,
            start_new_session=True)
        try:
            base = f'http://127.0.0.1:{port}'
            deadline = time.monotonic() + 600
            while True:
                check(proc.poll() is None,
                      f'serve exited {proc.returncode}:\n{tail(log)}')
                try:
                    health = http_json(f'{base}/health', timeout=5)
                    break
                except (OSError, urllib.error.URLError):
                    check(time.monotonic() < deadline,
                          f'serve not up after 600s:\n{tail(log)}')
                    time.sleep(0.5)
            up_s = time.monotonic() - t0
            check(health['platform'] == ctx.platform,
                  f'/health says {health["platform"]!r}: {health}')
            got = np.concatenate([
                np.asarray(http_json(
                    f'{base}/predict',
                    {'x': rows[i:i + batch_size].tolist()})['y'])
                for i in range(0, len(rows), batch_size)])
        except BaseException:
            stop_group(proc, grace=10)
            raise
        rc = stop_group(proc, grace=60)     # SIGTERM: drain, then exit
    check(rc == 0, f'serve exited {rc} on SIGTERM:\n{tail(log)}')
    check(got.shape == want.shape,
          f'served {got.shape}, infer saved {want.shape}')
    diff = float(np.abs(got - want).max())
    check(diff <= PROB_ATOL,
          f'served probabilities differ from the infer task\'s by '
          f'{diff} (> {PROB_ATOL})')
    # a near-tie inside the tolerance may fall either way
    top2 = np.sort(want, axis=1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * PROB_ATOL
    agree = got.argmax(1) == want.argmax(1)
    check(bool(agree[clear].all()),
          f'served argmax differs from the infer task\'s on rows '
          f'{np.flatnonzero(clear & ~agree).tolist()}')
    say(f'serve [{ctx.label}]: /health platform '
        f'{health["platform"]}, {len(rows)} rows in {batches} '
        f'requests, argmax equal on {int(agree.sum())}/{len(rows)} '
        f'({int(clear.sum())} clear of a tie), max |dp| {diff:.2e}, '
        f'up in {up_s:.1f}s')
    return health


# ------------------------------------------------------------------- lm
#: the LM flagship shape: d=1024, 8 layers, 16 heads, d_ff 4096, vocab
#: 32768, T=8192, bf16, batch 1; attn_impl left at ``auto``. The
#: synthetic token stream draws from the first 1024 ids only — its
#: generator holds a V x V transition table, which at V=32768 would be
#: 8.6 GB of host memory for no difference to what the chip executes
LM_MODEL = {'name': 'transformer_lm', 'vocab_size': 32768,
            'd_model': 1024, 'n_layers': 8, 'n_heads': 16, 'd_ff': 4096,
            'max_seq_len': 8192, 'dtype': 'bfloat16'}
LM_OPTIMIZER = {'name': 'adamw', 'lr': 0.0003,
                'schedule': {'name': 'warmup_cosine'}}
LM_STEPS = 6


def lm_config():
    return {
        'info': {'name': 'lm_flagship', 'project': 'lm_flagship'},
        'executors': {'train': {
            'type': 'jax_train', 'model': dict(LM_MODEL),
            'dataset': {'name': 'synthetic_lm', 'n_train': LM_STEPS,
                        'n_valid': 2,
                        'seq_len': LM_MODEL['max_seq_len'],
                        'vocab_size': 1024},
            'loss': 'lm_ce', 'batch_size': 1, 'mesh': {'dp': 1},
            'main_metric': 'loss', 'minimize': True,
            'checkpoint_every': 0,
            'stages': [{'name': 'stage1', 'epochs': 1,
                        'optimizer': dict(LM_OPTIMIZER)}]}}}


def phase_lm(ctx):
    """The flagship through ``python -m mlcomp_tpu execute`` (a child),
    then ONE child that lowers the same train step and checks the
    TPU-only kernels against their jnp references on the chip."""
    path = write_yaml(ctx.path('configs', 'lm_flagship', 'config.yml'),
                      lm_config())
    t0 = time.monotonic()
    out = run_child(ctx, ['-m', 'mlcomp_tpu', 'execute', path],
                    'lm.execute.log', timeout=900)
    statuses = last_json(out)
    task = dict(ctx.query(
        'select * from task order by id desc limit 1')[0])
    check(statuses == {'train': 'Success'},
          f'execute ended {statuses}:\n{task_log(ctx, [task])}')
    where = logged_json(ctx, task['id'], 'devices')
    check(where['platform'] == ctx.platform, f'lm trained on {where}')
    loss = [v for _, v in series(ctx, task['id'], 'loss')]
    check(len(loss) >= 4 and all(math.isfinite(v) for v in loss),
          f'lm loss series {loss}')
    say(f'lm [{ctx.label}]: {len(loss)} steps at '
        f'T={LM_MODEL["max_seq_len"]}, loss '
        f'{loss[0]:.4f} -> {loss[-1]:.4f}, execute '
        f'{time.monotonic() - t0:.1f}s')
    out = run_child(ctx, [os.path.join(HERE, 'chip_smoke.py'),
                          '--child', 'kernels'],
                    'lm.kernels.log', timeout=900)
    for line in out.splitlines():
        if line.startswith('kernels:'):
            say(f'{line} [{ctx.label}]')


def child_kernels():
    """Runs IN A CHILD on the chip (the only place of this file that
    imports jax): the flagship train step as the executor builds it
    must hold Pallas kernel calls — ``auto`` did not quietly pick the
    dense path — and each kernel behind a TPU-only branch must match
    its own jnp reference on seeded inputs, compiled, not interpreted."""
    import mlcomp_tpu  # noqa: F401 — first: places the compile cache
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.parallel import mesh_from_spec
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step,
    )
    from mlcomp_tpu.train.optim import make_optimizer

    assert jax.default_backend() == 'tpu', jax.devices()
    mesh = mesh_from_spec({'dp': 1}, devices=jax.devices()[:1])
    model = create_model(mesh=mesh, **LM_MODEL)
    optimizer, _ = make_optimizer(LM_OPTIMIZER, LM_STEPS)
    tokens = jnp.zeros((1, LM_MODEL['max_seq_len']), jnp.int32)
    state = create_train_state(model, optimizer, tokens,
                               jax.random.PRNGKey(0), mesh=mesh,
                               with_dropout_rng=True)
    step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                           mesh=mesh, self_supervised=True)
    text = step.lower(state, tokens, None).compile().as_text()
    calls = text.count('tpu_custom_call')
    assert calls >= 3, f'{calls} kernel calls in the LM train step'
    print(f'kernels: LM train step holds {calls} tpu_custom_call')
    del state, step, text

    def close(name, got, want, tol):
        """Max error over the reference's max magnitude — the measure
        tests/test_ops.py holds the bf16 kernels to."""
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.isfinite(got).all(), f'{name}: non-finite'
        err = float(np.abs(got - want).max()
                    / (np.abs(want).max() + 1e-9))
        assert err < tol, f'{name}: error {err} of max |ref| >= {tol}'
        return err

    from mlcomp_tpu.ops.flash_attention import (
        fused_attention, reference_attention,
    )
    key = jax.random.PRNGKey(21)
    q, k, v = (jax.random.normal(kk, (1, 2048, 16, 64), jnp.bfloat16)
               for kk in jax.random.split(key, 3))

    def loss_of(attn):
        return lambda q, k, v: (
            attn(q, k, v).astype(jnp.float32) ** 2).sum()

    def flash(q, k, v):
        return fused_attention(q, k, v, causal=True, impl='pallas')

    def dense(q, k, v):     # the reference in f32, as the tests do
        return reference_attention(
            *(a.astype(jnp.float32) for a in (q, k, v)), causal=True)

    errs = [close('flash fwd', jax.jit(flash)(q, k, v),
                  jax.jit(dense)(q, k, v), 3e-2)]
    got = jax.jit(jax.grad(loss_of(flash), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss_of(dense), (0, 1, 2)))(q, k, v)
    for name, g, w in zip('qkv', got, want):
        errs.append(close(f'flash d{name}', g, w, 5e-2))
    print(f'kernels: flash [1,2048,16,64] fwd+grads vs '
          f'reference_attention, max error {max(errs):.3e} of max |ref|')

    from mlcomp_tpu.ops.fused_norm import (
        fused_norm_act, reference_norm_act,
    )
    kx, kg, kb = jax.random.split(jax.random.PRNGKey(22), 3)
    x = jax.random.normal(kx, (524288, 64), jnp.bfloat16) \
        + jnp.arange(64, dtype=jnp.bfloat16) / 16
    gamma = 1 + 0.1 * jax.random.normal(kg, (64,), jnp.float32)
    beta = 0.1 * jax.random.normal(kb, (64,), jnp.float32)
    got = jax.jit(lambda *a: fused_norm_act(
        *a, 1e-5, True, 'pallas'))(x, gamma, beta)
    want = jax.jit(reference_norm_act)(x, gamma, beta)
    errs = [close(f'fused_norm[{i}]', g, w, 2e-2)
            for i, (g, w) in enumerate(zip(got, want))]
    print(f'kernels: fused_norm_act [524288,64] vs reference_norm_act, '
          f'max error {max(errs):.3e} of max |ref|')

    from mlcomp_tpu.ops.serving_stack import (
        reference_stack, serving_stack,
    )
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(23), 3)
    x = jax.random.normal(kx, (64, 8192), jnp.bfloat16)
    w = jax.random.randint(kw, (8, 8192, 8192), -127, 128, jnp.int8)
    scales = jax.random.uniform(ks, (8, 8192), jnp.float32,
                                0.5, 1.5) / (127 * 8192 ** 0.5)
    got = jax.jit(serving_stack)(x, w, scales)
    want = jax.jit(reference_stack)(x, w, scales)
    err = close('serving_stack', got, want, 3e-2)
    print(f'kernels: serving_stack int8 [64,8192]x[8,8192,8192] vs '
          f'reference_stack, max error {err:.3e} of max |ref|')


# ------------------------------------------------------------- multichip
def first_loss(ctx, task_id):
    loss = series(ctx, task_id, 'loss')
    check(loss, f'task {task_id} wrote no loss series')
    return loss[0][1]


def resnet_train_only():
    """The ResNet-18 train spec with nothing downstream of training
    (no export, no report images): what the four-chip comparisons run."""
    train = resnet_config()['executors']['train']
    train.pop('model_name')
    train.pop('report_imgs')
    return train


def phase_fanout(ctx, dep, timeout=900):
    """BASELINE's north star: a 4-cell grid of the ResNet-18 config on
    four workers, one chip each, side by side."""
    config = {'info': {'name': 'cifar10_grid', 'project': 'cifar10'},
              'executors': {'train': dict(
                  resnet_train_only(), grid=[{'seed': [0, 1, 2, 3]}])}}
    path = write_yaml(ctx.path('configs', 'cifar10_grid', 'config.yml'),
                      config)
    tasks = dep.wait_dag(dep.submit(path, 'grid'), timeout)
    cells = sorted(tasks.values(), key=lambda t: t['id'])
    check(len(cells) == 4, f'grid made {len(cells)} cells')
    info = [check_train_task(ctx, t, json.loads(t['cores_assigned']),
                             HBM_SERIES) for t in cells]
    cores = [tuple(json.loads(t['cores_assigned'])) for t in cells]
    check(len(set(cores)) == 4 and all(len(c) == 1 for c in cores),
          f'cells ran on cores {cores}')
    chips = [i['where']['visible_chips'] for i in info]
    check(len(set(chips)) == 4, f'cells logged chips {chips}')
    latest_start = max(t['started'] for t in cells)
    earliest_end = min(t['finished'] for t in cells)
    check(latest_start < earliest_end,
          f'cells did not overlap: '
          f'{[(t["started"], t["finished"]) for t in cells]}')
    say(f'fan-out [{ctx.label}]: 4 cells on cores {cores}, chips '
        f'{chips}, jax ids {[i["where"]["ids"] for i in info]}, all '
        f'running between {latest_start} and {earliest_end}; final '
        f'loss {[round(i["loss_last"], 4) for i in info]}')
    return cells[0], info[0]


def single_task(ctx, dep, name, train, timeout=900):
    """One jax_train task as its own DAG; returns its finished row."""
    config = {'info': {'name': name, 'project': name},
              'executors': {'train': train}}
    path = write_yaml(ctx.path('configs', name, 'config.yml'), config)
    tasks = dep.wait_dag(dep.submit(path, name), timeout)
    # a multi-core task fans out to a service child that does the work
    return max(tasks.values(), key=lambda t: t['id'])


def close_loss(what, a, b, rtol=2e-2):
    check(abs(a - b) <= rtol * max(abs(a), abs(b)),
          f'{what}: {a} vs {b} (rtol {rtol})')


def phase_multichip(ctx):
    """Four chips: fan-out, then one task over four chips (dp=4, and
    sp=2 x tp=2), each against the same seeded run on one chip."""
    dep = Deployment(ctx, 4)
    try:
        computer = dep.wait_computer()
        check(computer['cores'] == 4,
              f'the core probe registered {computer}, expected 4')
        cell, cell_info = phase_fanout(ctx, dep)

        base = dict(resnet_train_only(), seed=0)
        alone = single_task(ctx, dep, 'resnet_alone', dict(base))
        alone_info = check_train_task(ctx, alone, [0], HBM_SERIES)
        close_loss('grid cell seed 0 vs the same run alone, final loss',
                   cell_info['loss_last'], alone_info['loss_last'],
                   rtol=5e-2)

        dp4 = single_task(ctx, dep, 'resnet_dp4', dict(
            base, cores=4, mesh={'dp': 4}))
        dp4_info = check_train_task(ctx, dp4, [0, 1, 2, 3],
                                    HBM_SERIES)
        place = logged_json(ctx, dp4['id'], 'placement')
        check(place['batch_devices'] == 4
              and place['batch_local_frac'] < 1,
              f'dp=4 batch placement {place}')
        check(series(ctx, dp4['id'], 'comm.bytes_per_step'),
              'dp=4 task wrote no comm.* rows')
        close_loss('dp=4 vs one chip, first-step loss',
                   first_loss(ctx, dp4['id']),
                   first_loss(ctx, alone['id']))
        say(f'dp4 [{ctx.label}]: {dp4_info["steps"]} steps, '
            f'{dp4_info["where"]}, placement {place}, first loss '
            f'{first_loss(ctx, dp4["id"]):.4f} vs one chip '
            f'{first_loss(ctx, alone["id"]):.4f}')

        lm = read_yaml(os.path.join(
            HERE, 'examples', 'lm_long_context', 'config.yml'))
        lm = lm['executors']['train']
        lm['dataset'].update(n_train=32, n_valid=8)     # 4 steps
        lm['checkpoint_every'] = 0
        one = single_task(ctx, dep, 'lm_one_chip', dict(
            lm, cores=1, mesh={'dp': 1}))
        four = single_task(ctx, dep, 'lm_sp2_tp2', dict(lm))
        place = logged_json(ctx, four['id'], 'placement')
        check(place['batch_devices'] == 4
              and place['batch_local_frac'] < 1
              and place['param_devices'] == 4
              and place['param_local_frac'] < 1,
              f'sp=2 x tp=2 placement {place}')
        check(series(ctx, four['id'], 'comm.bytes_per_step'),
              'sp=2 x tp=2 task wrote no comm.* rows')
        close_loss('sp=2 x tp=2 vs one chip, first-step loss',
                   first_loss(ctx, four['id']),
                   first_loss(ctx, one['id']))
        say(f'sp2xtp2 [{ctx.label}]: {logged_json(ctx, four["id"], "devices")}, '
            f'placement {place}, first loss '
            f'{first_loss(ctx, four["id"]):.4f} vs one chip '
            f'{first_loss(ctx, one["id"]):.4f}')
    finally:
        dep.stop()


# ----------------------------------------------------------------- main
def timed(name, fn, *args, **kwargs):
    t0 = time.monotonic()
    result = fn(*args, **kwargs)
    say(f'phase {name}: ok in {time.monotonic() - t0:.1f}s')
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--multichip', action='store_true',
                        help='the four-chip phases only (a 2x2 host)')
    parser.add_argument('--child', help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child == 'kernels':
        return child_kernels()

    import shutil
    out = os.path.join(HERE, 'chiprun_out', 'chip_smoke')
    shutil.rmtree(out, ignore_errors=True)
    ctx = Ctx(out)
    try:
        device = timed('device', phase_device, ctx)
        check(device['count'] == (4 if args.multichip else 1),
              f'this mode needs {4 if args.multichip else 1} chip(s), '
              f'jax found {device["count"]}')
        timed('native', rebuild_native, ctx)
        if args.multichip:
            timed('multichip', phase_multichip, ctx)
        else:
            timed('dag', phase_dag, ctx, resnet_config())
            timed('serve', phase_serve, ctx)
            timed('lm', phase_lm, ctx)
    except SmokeFailure as e:
        say(f'FAILED: {e}')
        return 1
    finally:
        # what comes back from the chip's machine is capped: keep the
        # logs and the DB, drop datasets, checkpoints and exports
        for name in os.listdir(ctx.root):
            if name not in ('db', 'logs'):
                shutil.rmtree(os.path.join(ctx.root, name),
                              ignore_errors=True)
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
