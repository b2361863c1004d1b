"""Worker daemons (parity: reference worker/__main__.py).

- ``worker N``            — task consumer #N: claims execute/kill messages
  from its queues (``{host}_{docker}``, ``{host}_{docker}_{N}``) and runs
  each task in a fresh subprocess (the reference's per-task
  ``os._exit(0)`` hygiene, worker/tasks.py:279, as process isolation that
  doesn't tear down THIS daemon's state). ``--in-process`` keeps the task
  in the daemon instead — avoids re-initialising the TPU runtime per task,
  but the daemon then OWNS its chips for life: one such worker per chip.
- ``worker-supervisor``   — registers Computer+Docker rows, heartbeats,
  dead-pid reaper (reference worker/__main__.py:64-88), usage telemetry
  (psutil + TPU HBM when available, reference worker/__main__.py:91-127),
  data sync loop.
- ``start``               — process manager: spawns worker-supervisor +
  N workers as child processes with autorestart (supervisord parity,
  reference worker/__main__.py:184-224).
- ``run-task ID``         — internal: execute one task in this process.
"""

import json
import os
import socket
import subprocess
import sys
import time
import traceback

import click

from mlcomp_tpu import (
    CAN_PROCESS_TASKS, DOCKER_IMG, QUEUE_POLL_INTERVAL, ROOT_FOLDER,
    SYNC_WITH_THIS_COMPUTER, WORKER_USAGE_INTERVAL,
)
from mlcomp_tpu.db.core import Session
from mlcomp_tpu.db.enums import ComponentType, TaskStatus
from mlcomp_tpu.db.migration import migrate
from mlcomp_tpu.db.models import Computer, Docker
from mlcomp_tpu.db.providers import (
    ComputerProvider, DockerProvider, QueueProvider, TaskProvider,
)
from mlcomp_tpu.utils.logging import create_logger
from mlcomp_tpu.utils.misc import disk, memory, now

from mlcomp_tpu.utils.misc import hostname as _hostname
HOSTNAME = _hostname()


@click.group()
def main():
    pass


class CoreProbeError(RuntimeError):
    """The host's TPU chips could not be counted."""


#: the probe child asks for the TPU backend BY NAME: jax.devices()
#: would quietly answer with the CPU when the TPU runtime cannot start
_PROBE_SRC = (
    'import jax\n'
    'try:\n'
    '    print(len(jax.devices("tpu")))\n'
    'except RuntimeError as e:\n'
    '    if "Unknown backend" not in str(e):\n'
    '        raise\n'
    '    print(0)\n')


def _tpu_core_count() -> int:
    """TPU chips on this host. ``MLCOMP_TPU_CORES`` overrides (tests,
    clusters); a host forced onto the CPU backend has none; otherwise
    one jax probe, run ONCE at daemon start and before any worker can
    claim work.

    The probe is a child that exits: a chip belongs to one process at a
    time, so a daemon that initialized a jax client would own the chip
    for its whole lifetime and every task process would fail to load
    the TPU runtime. For the same reason the probe cannot succeed while
    a task holds the chip — a probe that fails raises
    ``CoreProbeError`` naming the child's stderr and is never read as
    "0 cores"."""
    env = os.environ.get('MLCOMP_TPU_CORES')
    if env is not None:
        return int(env)
    if os.environ.get('JAX_PLATFORMS') == 'cpu':
        return 0
    try:
        out = subprocess.run(
            [sys.executable, '-c', _PROBE_SRC],
            capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired as e:
        raise CoreProbeError(
            f'TPU core probe timed out after {e.timeout:.0f}s; '
            f'stderr: {(e.stderr or "")[-2000:]}') from e
    lines = out.stdout.strip().splitlines()
    if out.returncode != 0 or not lines or not lines[-1].isdigit():
        raise CoreProbeError(
            f'TPU core probe failed (rc={out.returncode}) — is another '
            f'process holding the chip? Set JAX_PLATFORMS=cpu or '
            f'MLCOMP_TPU_CORES to run this host without probing. '
            f'stderr: {out.stderr[-2000:]}')
    return int(lines[-1])


def _host_cores(session, logger) -> int:
    """The probed chip count — or, when the probe fails on a host that
    is ALREADY registered (a worker-supervisor restarted while a task
    owns the chip), the registered count with the failure logged. A
    failed probe never writes a 0 over the row; on a host with no row
    it is fatal."""
    try:
        return _tpu_core_count()
    except CoreProbeError as e:
        row = ComputerProvider(session).by_name(HOSTNAME)
        if row is None:
            raise
        logger.error(f'{e}\nkeeping the registered {row.cores} cores',
                     ComponentType.WorkerSupervisor, HOSTNAME)
        return row.cores


def register_computer(session, cores: int = None):
    """Register/refresh this host's Computer row
    (reference worker/__main__.py:231-260)."""
    import multiprocessing
    provider = ComputerProvider(session)
    computer = Computer(
        name=HOSTNAME,
        cores=cores if cores is not None else _tpu_core_count(),
        cpu=multiprocessing.cpu_count(),
        memory=memory()[0],
        disk=disk(ROOT_FOLDER)[0],
        ip=os.environ.get('IP', 'localhost'),
        port=int(os.environ.get('PORT', 22)),
        user=os.environ.get('USER', 'root'),
        can_process_tasks=CAN_PROCESS_TASKS,
        sync_with_this_computer=SYNC_WITH_THIS_COMPUTER,
    )
    provider.create_or_update(computer, 'name')
    return computer


def queue_names(index: int = None):
    base = f'{HOSTNAME}_{DOCKER_IMG}'
    queues = [base]
    if index is not None:
        queues.append(f'{base}_{index}')
    return queues


# --------------------------------------------------------------- consumer
def _run_subprocess(task_id: int, index: int, logger, session,
                    trace_id: str = None) -> int:
    """Execute a task in a child process; returns the exit status
    (0 = success; negative = killed by that signal)."""
    env = dict(os.environ)
    # exec-time marker read back via /proc/<pid>/environ by kill_task's
    # pid-reuse guard
    env['MLCOMP_TASK_ID'] = str(task_id)
    from mlcomp_tpu.telemetry import PROCESS_ROLE_ENV, TRACE_ID_ENV
    if trace_id:
        # queue payload → task environment: the child's spans join the
        # submission's trace with no plumbing inside the task code
        from mlcomp_tpu.telemetry import trace_context_env
        env.update(trace_context_env(trace_id=trace_id,
                                     process_role='worker'))
    else:
        # no trace on this dispatch: strip anything inherited from the
        # daemon's own environment so a PREVIOUS task's trace id can't
        # mislabel this child's spans
        env.pop(TRACE_ID_ENV, None)
        env.pop(PROCESS_ROLE_ENV, None)
    # a task the supervisor gave no TPU cores must not reach for a chip
    # that a task beside it owns: pin it to the CPU backend
    task = TaskProvider(session).by_id(task_id)
    if task is not None and task.cores_assigned in (None, '', '[]'):
        env['JAX_PLATFORMS'] = 'cpu'
    cmd = [sys.executable, '-m', 'mlcomp_tpu.worker', 'run-task',
           str(task_id), '--index', str(index)]
    proc = subprocess.Popen(cmd, env=env)
    proc.wait()
    return proc.returncode


def _consume_one(session, queue_provider, logger, index: int,
                 in_process: bool) -> bool:
    me = f'{HOSTNAME}:{index}'
    claim = queue_provider.claim(queue_names(index), me)
    if claim is None:
        return False
    msg_id, payload = claim
    action = payload.get('action')
    task_id = payload.get('task_id')
    trace_id = payload.get('trace_id')
    try:
        if action == 'execute':
            if in_process:
                from mlcomp_tpu.worker.tasks import execute_by_id
                execute_by_id(task_id, exit=False, worker_index=index,
                              session=session, trace_id=trace_id)
                ok = True
                # this process holds the live TPU client — it is the
                # only one that can report HBM telemetry (worker_usage
                # preserves this field, see its docstring)
                if 'jax' in sys.modules:
                    try:
                        ComputerProvider(session).update_usage_fields(
                            HOSTNAME, {'tpu': _tpu_usage()})
                    except Exception:
                        pass
            else:
                returncode = _run_subprocess(task_id, index, logger,
                                             session, trace_id=trace_id)
                ok = returncode == 0
            # completion is pinned to THIS claim (worker=me): if the
            # lease expired mid-run and the message was reclaimed, the
            # conditional UPDATE loses cleanly instead of clobbering
            # the next claimant's in-flight execution
            if ok:
                queue_provider.complete(msg_id, worker=me)
            else:
                queue_provider.fail(
                    msg_id, f'subprocess failed (rc={returncode})',
                    worker=me)
                # the subprocess may have died before marking the task;
                # classify the death for the retry pass: a signal kill
                # (SIGTERM/SIGKILL) is a preemption and retries, a
                # crash that never wrote its own reason is worker-lost
                provider = TaskProvider(session)
                task = provider.by_id(task_id)
                if task is not None and \
                        task.status < int(TaskStatus.Failed):
                    from mlcomp_tpu.recovery import classify_returncode
                    provider.fail_with_reason(
                        task,
                        classify_returncode(returncode) or 'worker-lost')
        elif action == 'kill':
            from mlcomp_tpu.worker.tasks import kill_task
            kill_task(task_id, session=session)
            queue_provider.complete(msg_id, worker=me)
        else:
            queue_provider.fail(msg_id, f'unknown action {action!r}',
                                worker=me)
    except Exception:
        queue_provider.fail(msg_id, traceback.format_exc()[-4000:],
                            worker=me)
        logger.error(
            f'message {msg_id} ({action} task {task_id}) failed:\n'
            f'{traceback.format_exc()}',
            ComponentType.Worker, HOSTNAME, task_id)
    return True


#: wait horizon when the backend delivers cross-process wakeups
#: (Postgres LISTEN/NOTIFY) — purely a lost-wakeup backstop, NOT a
#: latency floor: enqueues interrupt the wait immediately
EVENT_WAIT_BACKSTOP_S = 5.0

#: ceiling for the worker loop's exponential error backoff — a sick DB
#: must not spin the log at 1 Hz forever, but recovery should be
#: noticed within a minute
ERROR_BACKOFF_MAX_S = 60.0


def _error_backoff_delay(failures: int) -> float:
    """1, 2, 4, ... seconds for the Nth consecutive loop failure,
    capped at ERROR_BACKOFF_MAX_S."""
    return min(ERROR_BACKOFF_MAX_S, 2.0 ** (max(1, failures) - 1))


def _queue_channels(index: int):
    from mlcomp_tpu.db.events import queue_channel
    return [queue_channel(q) for q in queue_names(index)]


def _event_snapshot(session, index: int):
    """Channel-sequence snapshot taken BEFORE the claim attempt — an
    enqueue landing between an empty claim and the wait bumps past
    this snapshot and wakes the wait instantly (db/events.py)."""
    try:
        return session.event_snapshot(_queue_channels(index))
    except Exception:
        return None


def _idle_wait(session, index: int, snapshot=None):
    """Sleep until work may exist: wake on this worker's queue
    channels, falling back to the short poll where no cross-process
    wakeup can reach us (plain sqlite multi-process — the fallback
    row of the docs/control_plane.md matrix)."""
    timeout = EVENT_WAIT_BACKSTOP_S \
        if getattr(session, 'events_cross_process', False) \
        else QUEUE_POLL_INTERVAL
    try:
        session.wait_event(_queue_channels(index), timeout,
                           snapshot=snapshot)
    except Exception:
        time.sleep(QUEUE_POLL_INTERVAL)


@main.command()
@click.argument('index', type=int)
@click.option('--in-process', is_flag=True,
              help='run tasks inside the daemon (persistent TPU client)')
def worker(index, in_process):
    """Task consumer #INDEX (reference worker/__main__.py:130-144)."""
    session = Session.create_session(key=f'worker{index}')
    migrate(session)
    logger = create_logger(session)
    queue_provider = QueueProvider(session)
    logger.info(f'worker {index} consuming {queue_names(index)}',
                ComponentType.Worker, HOSTNAME)
    failures = 0
    while True:
        try:
            snapshot = _event_snapshot(session, index)
            if not _consume_one(session, queue_provider, logger, index,
                                in_process):
                _idle_wait(session, index, snapshot=snapshot)
            # THIS process runs the contended claim/complete loop the
            # busy-retry metric exists for — flush its own deltas (an
            # in-memory no-op when nothing retried since last flush)
            _flush_busy_retry_deltas(session)
            failures = 0
        except KeyboardInterrupt:
            break
        except Exception:
            # bounded exponential backoff (was a flat 1 s sleep): a
            # sick DB backs the loop off to ERROR_BACKOFF_MAX_S with
            # the reason in the log, instead of spinning at 1 Hz
            failures += 1
            delay = _error_backoff_delay(failures)
            logger.error(
                f'worker loop error (consecutive failure {failures}, '
                f'backing off {delay:.0f}s):\n{traceback.format_exc()}',
                ComponentType.Worker, HOSTNAME)
            # drop the cached singleton so a fresh connection is built
            Session.cleanup(f'worker{index}')
            session = Session.create_session(key=f'worker{index}')
            queue_provider = QueueProvider(session)
            logger = create_logger(session)
            time.sleep(delay)


@main.command(name='run-task')
@click.argument('task_id', type=int)
@click.option('--index', type=int, default=-1)
def run_task(task_id, index):
    """Execute one task in this process (internal)."""
    from mlcomp_tpu.worker.tasks import execute_by_id
    execute_by_id(task_id, exit=False, worker_index=index)


# --------------------------------------------------- worker supervisor
def stop_processes_not_exist(session, logger):
    """Dead-pid reaper (reference worker/__main__.py:64-88): fail
    InProgress tasks on this host whose pid vanished (30 s grace on
    last_activity)."""
    from mlcomp_tpu import native
    provider = TaskProvider(session)
    for task in provider.by_status(TaskStatus.InProgress,
                                   computer=HOSTNAME):
        if not task.pid or native.pid_exists(task.pid):
            continue
        grace_ok = True
        if task.last_activity:
            from mlcomp_tpu.utils.misc import parse_time
            age = (now() - parse_time(task.last_activity)).total_seconds()
            grace_ok = age > 30
        if grace_ok:
            logger.error(
                f'task {task.id}: pid {task.pid} no longer exists — '
                f'marking Failed (worker-lost)',
                ComponentType.WorkerSupervisor, HOSTNAME, task.id)
            # worker-lost is transient: the supervisor's retry pass
            # requeues it from the last checkpoint
            provider.fail_with_reason(task, 'worker-lost')


def worker_usage(session, logger):
    """Resource telemetry → computer row + usage history
    (reference worker/__main__.py:91-127; GPUtil/psutil there — here the
    framework's own native /proc sampler, mlcomp_tpu/native).

    The 'tpu' field is NOT sampled here: this daemon must never hold a
    TPU client (see _tpu_usage), so it preserves whatever the process
    that does hold one — an in-process worker, via
    update_usage_fields — last wrote."""
    import json as _json

    from mlcomp_tpu import native
    provider = ComputerProvider(session)
    row = provider.by_name(HOSTNAME)
    prev_tpu = []
    if row is not None and row.usage:
        try:
            prev_tpu = _json.loads(row.usage).get('tpu') or []
        except (ValueError, TypeError):
            pass
    usage = {
        'cpu': native.cpu_percent(),
        'memory': native.memory_percent(),
        'disk': native.disk_percent(ROOT_FOLDER),
        'tpu': prev_tpu or _tpu_usage(),
    }
    provider.current_usage(HOSTNAME, usage)
    provider.add_usage_history(HOSTNAME, usage)
    _flush_busy_retry_deltas(session)


#: watermark for _flush_busy_retry_deltas (this process only)
_BUSY_FLUSHED = {'retries': 0, 'gave_up': 0}


def _flush_busy_retry_deltas(session):
    """Feed this process's SQLITE_BUSY retry counters into the
    ``db.busy_retries`` series as DELTAS — same protocol as the
    supervisor's per-tick sampling, so ``mlcomp_db_busy_retries_total``
    (a plain SUM over the series) stays double-count-free. Called from
    the worker consume loop AND the host agent's usage loop (each in
    its own process, each covering only itself); an in-memory no-op
    when nothing retried since the last flush. Best-effort:
    observability must never fail the loop it rides."""
    from mlcomp_tpu.db.core import busy_retry_stats
    from mlcomp_tpu.utils.misc import now as _now
    stats = busy_retry_stats()
    rows = []
    for kind, series in (('retries', 'db.busy_retries'),
                         ('gave_up', 'db.busy_gave_up')):
        delta = stats[kind] - _BUSY_FLUSHED[kind]
        if delta > 0:
            rows.append((None, series, 'counter', None, float(delta),
                         _now(), 'worker_supervisor', None))
    if not rows:
        return
    try:
        from mlcomp_tpu.db.providers.telemetry import MetricProvider
        MetricProvider(session).add_many(rows)
        _BUSY_FLUSHED.update(
            {k: stats[k] for k in ('retries', 'gave_up')})
    except Exception:
        pass


def _tpu_usage():
    """Per-chip HBM occupancy when a jax client is alive in this process
    (TPU analogue of GPUtil load/memory, reference
    worker/__main__.py:111-117).

    Never INITIALIZES a client: a chip belongs to one process at a
    time, so a daemon that took it would make every task process fail
    to load the TPU runtime. Telemetry reports HBM only when this
    process already trains (in-process workers)."""
    if 'jax' not in sys.modules:
        return []
    try:
        import jax
        out = []
        for d in jax.devices():
            if d.platform == 'cpu':
                continue
            stats = {}
            try:
                stats = d.memory_stats() or {}
            except Exception:
                pass
            out.append({
                'id': d.id,
                'kind': getattr(d, 'device_kind', str(d)),
                'hbm_used': stats.get('bytes_in_use', 0),
                'hbm_limit': stats.get('bytes_limit', 0),
            })
        return out
    except Exception:
        return []


def consume_control_queue(session, logger):
    """Drain the host agent's control queue
    (``{host}_{docker}_supervisor``): kill actions routed here drain even
    when every worker is blocked on a running task."""
    queue_provider = QueueProvider(session)
    queue = f'{HOSTNAME}_{DOCKER_IMG}_supervisor'
    me = f'{HOSTNAME}:supervisor'
    while True:
        # batched drain: a pile of routed kills (a gang abort fans one
        # per rank) comes back in ONE conditional claim statement
        claims = queue_provider.claim_many([queue], me, 32)
        if not claims:
            return
        for msg_id, payload in claims:
            action = payload.get('action')
            task_id = payload.get('task_id')
            try:
                if action == 'kill':
                    from mlcomp_tpu.worker.tasks import kill_task
                    kill_task(task_id, session=session)
                    queue_provider.complete(msg_id, worker=me)
                else:
                    queue_provider.fail(
                        msg_id, f'unknown action {action!r}', worker=me)
            except Exception:
                queue_provider.fail(
                    msg_id, traceback.format_exc()[-4000:], worker=me)
                logger.error(
                    f'control message {msg_id} ({action} task {task_id}) '
                    f'failed:\n{traceback.format_exc()}',
                    ComponentType.WorkerSupervisor, HOSTNAME, task_id)


@main.command(name='worker-supervisor')
@click.option('--cores', type=int, default=None,
              help='override detected TPU core count')
def worker_supervisor(cores):
    """Host agent: registration, heartbeats, reaper, telemetry, sync
    (reference worker/__main__.py:147-181)."""
    from mlcomp_tpu.utils.schedule import start_schedule
    from mlcomp_tpu.worker.sync import FileSync

    session = Session.create_session(key='worker_supervisor')
    migrate(session)
    logger = create_logger(session)
    if cores is None:
        cores = _host_cores(session, logger)
    register_computer(session, cores)
    docker_provider = DockerProvider(session)

    # warm the native library before the periodic loops need it — the
    # lazy path never blocks on g++, so build here where a one-time
    # compile is harmless
    try:
        from mlcomp_tpu import native
        native.build()
    except Exception:
        pass

    def heartbeat():
        docker_provider.heartbeat(HOSTNAME, DOCKER_IMG)

    def reaper():
        stop_processes_not_exist(session, logger)

    def usage():
        worker_usage(session, logger)

    def control():
        consume_control_queue(session, logger)

    file_sync = FileSync(session=session)
    heartbeat()
    start_schedule([
        (heartbeat, 5),
        (reaper, 10),
        (usage, WORKER_USAGE_INTERVAL),
        (file_sync.sync, 60),
        (control, 2),
    ], logger=logger)
    logger.info(f'worker-supervisor up on {HOSTNAME} '
                f'({cores} cores)',
                ComponentType.WorkerSupervisor, HOSTNAME)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


# ------------------------------------------------------------------ start
@main.command()
@click.argument('n_workers', type=int)
@click.option('--in-process', is_flag=True)
def start(n_workers, in_process):
    """Spawn worker-supervisor + N workers with autorestart
    (supervisord parity, reference worker/__main__.py:184-224)."""
    from mlcomp_tpu.utils.procgroup import run_process_group
    specs = [['-m', 'mlcomp_tpu.worker', 'worker-supervisor']] + [
        ['-m', 'mlcomp_tpu.worker', 'worker', str(i)]
        + (['--in-process'] if in_process else [])
        for i in range(n_workers)
    ]
    run_process_group(
        specs, banner=f'started worker-supervisor + {n_workers} workers')


@main.command()
def stop():
    """Stop daemons started by ``start`` (best effort, by cmdline)."""
    import psutil
    me = os.getpid()
    for proc in psutil.process_iter(['pid', 'cmdline']):
        cmd = ' '.join(proc.info.get('cmdline') or [])
        if 'mlcomp_tpu.worker' in cmd and proc.info['pid'] != me:
            try:
                proc.terminate()
            except psutil.Error:
                pass
    print('stopped')


if __name__ == '__main__':
    main()
