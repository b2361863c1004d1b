"""Worker task runtime (parity: reference worker/tasks.py:29-368).

``ExecuteBuilder`` is the per-task pipeline: fetch task+dag → check status →
mark InProgress (pid, worker index) → download code from the DB → pin TPU
cores → import the executor → run → store the result → handle multi-stage
requeue → Success. ``execute_by_id(id, exit=False)`` is the in-process
debug path used by ``mlcomp_tpu execute`` (reference __main__.py:90-123).

TPU specifics: instead of remapping ``CUDA_VISIBLE_DEVICES``
(reference worker/tasks.py:188-194) we pin the runtime to the assigned TPU
chips via ``TPU_VISIBLE_CHIPS`` and the process/chip bounds before the
jax backend starts (``chip_pin_env``), a task placed on TPU cores that
comes up without a TPU fails ``no-accelerator`` instead of training on
the host, and per-task process hygiene (reference ``os._exit(0)``,
worker/tasks.py:279) stays optional because TPU runtime init is expensive —
a persistent worker keeps the device client alive between tasks when
``exit=False``.
"""

import importlib
import json
import os
import sys
import traceback

from mlcomp_tpu import TASK_FOLDER
from mlcomp_tpu.db.core import Session
from mlcomp_tpu.db.enums import ComponentType, TaskStatus
from mlcomp_tpu.db.providers import (
    DagProvider, QueueProvider, TaskProvider
)
from mlcomp_tpu.utils.config import Config
from mlcomp_tpu.utils.io import yaml_load
from mlcomp_tpu.utils.logging import create_logger
from mlcomp_tpu.utils.misc import now, set_global_seed
from mlcomp_tpu.worker.storage import Storage


#: once-per-process guard for the crash-time telemetry drain
_crash_flush_installed = False


def _install_crash_flush(session):
    """Make the telemetry of a DYING task survive it: an atexit hook
    drains the span ring and every live MetricRecorder, and a SIGTERM
    handler converts the signal into SystemExit so ``finally`` blocks
    (span exits, recorder close) actually run before the drain. The
    spans of a failed/killed task are the ones the watchdog and the
    trace view most need — without this they die with the process,
    because SIGTERM's default disposition skips ``finally``."""
    global _crash_flush_installed
    if _crash_flush_installed:
        return
    _crash_flush_installed = True
    import atexit
    import signal
    import threading

    def _drain():
        from mlcomp_tpu.telemetry import (
            close_live_profilers, flush_live_recorders, flush_spans,
        )
        try:
            flush_spans(session)
        except Exception:
            pass
        try:
            # an open sampled trace window stops + parses so its
            # devtime.* rows land before the recorder flush below
            close_live_profilers()
        except Exception:
            pass
        try:
            flush_live_recorders()
        except Exception:
            pass

    atexit.register(_drain)
    if threading.current_thread() is not threading.main_thread():
        return                  # signal API is main-thread only
    try:
        previous = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            if callable(previous):
                try:
                    previous(signum, frame)
                except (SystemExit, KeyboardInterrupt):
                    raise
                except Exception:
                    pass
            raise SystemExit(143)

        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):
        pass


#: TPU_CHIPS_PER_PROCESS_BOUNDS for a process that takes n of a host's
#: chips (x,y,z extents of the block; jax's own multi-process TPU tests
#: use the same table)
_CHIP_BOUNDS = {1: '1,1,1', 2: '1,2,1', 4: '2,2,1', 8: '2,4,1'}

#: first port of the per-process TPU runtime endpoints: side-by-side
#: one-chip processes must not share libtpu's default port
_TPU_PROCESS_PORT_BASE = 8476


def chip_pin_env(cores, host_cores: int) -> dict:
    """Environment that restricts the TPU runtime to the chips
    ``cores`` of a host with ``host_cores`` chips; set before the jax
    backend starts. A task that owns the WHOLE host gets nothing — the
    runtime's defaults describe the host — and takes libtpu's
    host-wide lock, which is right: nobody else may use a chip of it.
    A subset is described as a one-process slice of that shape;
    libtpu then skips the host-wide lock, so processes on disjoint
    chips run side by side, each with its own runtime port."""
    cores = [int(c) for c in cores]
    if not cores or len(cores) >= int(host_cores or 0):
        return {}
    if len(cores) not in _CHIP_BOUNDS:
        raise ValueError(
            f'cannot describe {len(cores)} chips {cores} as a TPU '
            f'process block (supported: {sorted(_CHIP_BOUNDS)})')
    if len(cores) == 2 and (
            min(cores) % 2 or max(cores) != min(cores) + 1):
        # found on a 2x2 v5e host: [0,1] and [2,3] start (side by side,
        # too); [0,2] and [1,2] die at runtime start-up under 1,2,1
        raise ValueError(
            f'chips {cores} are not an aligned pair ([0,1], [2,3], ...): '
            f'the TPU runtime does not start on them as a 1,2,1 block')
    port = _TPU_PROCESS_PORT_BASE + min(cores)
    return {
        'TPU_VISIBLE_CHIPS': ','.join(str(c) for c in cores),
        'TPU_CHIPS_PER_PROCESS_BOUNDS': _CHIP_BOUNDS[len(cores)],
        'TPU_PROCESS_BOUNDS': '1,1,1',
        'TPU_PROCESS_ADDRESSES': f'localhost:{port}',
        'TPU_PROCESS_PORT': str(port),
    }


class ExecuteBuilder:
    def __init__(self, task_id: int, repeat_count: int = 1,
                 exit_on_finish: bool = False, worker_index: int = -1,
                 folder: str = None, session: Session = None,
                 trace_id: str = None):
        self.task_id = task_id
        self.repeat_count = repeat_count
        self.exit_on_finish = exit_on_finish
        self.worker_index = worker_index
        self.folder = folder  # pre-existing code folder (debug mode)
        self.trace_id = trace_id  # from the queue payload (else env/info)
        self.session = session or Session.create_session(key='worker')
        self.logger = create_logger(self.session)
        self.provider = TaskProvider(self.session)
        self.dag_provider = DagProvider(self.session)
        self.storage = Storage(self.session, self.logger)
        self.queue_provider = QueueProvider(self.session)

        self.task = None
        self.dag = None
        self.executor = None

    # ------------------------------------------------------------ pipeline
    def create_base(self):
        self.task = self.provider.by_id(self.task_id)
        if self.task is None:
            raise LookupError(f'task {self.task_id} not found')
        self.dag = self.dag_provider.by_id(self.task.dag)
        set_global_seed(self.task.id)
        # tame host-side BLAS threads; the math runs on TPU
        os.environ.setdefault('OMP_NUM_THREADS', '1')
        os.environ.setdefault('MKL_NUM_THREADS', '1')
        info = self.additional_info()
        for k, v in (info.get('env') or {}).items():
            os.environ[str(k)] = str(v)
        # join the submission's trace: payload arg wins (queued
        # dispatch), else the task's own additional_info (stored at
        # submission — covers the run-task subprocess AND debug
        # in-process mode). Deliberately NOT the process context as a
        # fallback: in a persistent in-process worker it may still
        # hold the PREVIOUS task's trace, and resurrecting it would
        # mislabel this task's spans. The context is resolved at span
        # EXIT, so the already-open task.pipeline root still lands in
        # the trace.
        from mlcomp_tpu.telemetry import (
            get_trace_context, set_trace_context,
        )
        trace_id = self.trace_id or info.get('trace_id')
        if trace_id:
            set_trace_context(trace_id,
                              get_trace_context()[1] or 'worker')
        else:
            # traceless task: clear any previous task's context (and
            # the exported env) so nothing inherits a stale trace
            set_trace_context(None)

    def additional_info(self) -> dict:
        if not self.task.additional_info:
            return {}
        return yaml_load(self.task.additional_info)

    def check_status(self):
        if self.task.status == int(TaskStatus.InProgress):
            raise RuntimeError(
                f'task {self.task.id} is already InProgress')
        if self.task.status > int(TaskStatus.InProgress):
            raise RuntimeError(
                f'task {self.task.id} is already finished: '
                f'{TaskStatus(self.task.status).name}')

    def mark_in_progress(self):
        self.task.pid = os.getpid()
        self.task.worker_index = self.worker_index
        self.provider.update(self.task, ['pid', 'worker_index'])
        self.provider.change_status(self.task, TaskStatus.InProgress)

    def download(self) -> str:
        if self.folder is not None:
            folder = self.folder
        else:
            folder = self.storage.download(self.task.id, dag=self.dag)
        os.makedirs(folder, exist_ok=True)
        return folder

    def assigned_cores(self) -> list:
        try:
            return list(json.loads(self.task.cores_assigned or '[]'))
        except (TypeError, ValueError):
            return []

    def pin_cores(self):
        """Restrict the TPU runtime to the assigned chips before jax init
        (TPU analogue of CUDA_VISIBLE_DEVICES remapping,
        reference worker/tasks.py:188-194)."""
        cores = self.assigned_cores()
        if not cores:
            return
        from mlcomp_tpu.db.providers import ComputerProvider
        from mlcomp_tpu.utils.misc import hostname
        host = ComputerProvider(self.session).by_name(
            self.task.computer_assigned or hostname())
        os.environ.update(chip_pin_env(
            cores, host.cores if host is not None else 0))

    def require_accelerator(self):
        """Hold a task the supervisor placed on TPU cores to them: jax
        falls back to the CPU backend when the TPU runtime cannot
        start, and such a task must fail (``no-accelerator``,
        permanent) rather than train on the host. Runs after the
        distributed join, which must precede the first backend use. An
        explicit ``JAX_PLATFORMS=cpu`` is the emulated-device mode of
        the tests and is left alone."""
        cores = self.assigned_cores()
        if not cores or os.environ.get('JAX_PLATFORMS') == 'cpu':
            return
        import jax
        if jax.default_backend() == 'cpu':
            from mlcomp_tpu.recovery import AcceleratorMissing
            raise AcceleratorMissing(
                f'task {self.task.id} was placed on TPU cores {cores} '
                f'of {self.task.computer_assigned} but its process came '
                f'up on the CPU backend (devices {jax.devices()}): the '
                f'TPU runtime did not start — is another process '
                f'holding the chip?')

    def init_distributed(self):
        """Join the multi-host job this service task belongs to
        (reference set_dist_env, catalyst.py:195-207): consume the
        supervisor-manufactured distr_info BEFORE the first jax backend
        use so jax.devices() becomes the global device list. The join
        is bounded (``join_timeout_s`` in distr_info): a rank whose
        peer died at dispatch raises ``GangPeerLost`` here instead of
        hanging, classified ``gang-peer-lost`` by the failure path
        below — transient gang collateral, so the supervisor's
        gang-atomic retry requeues the whole gang on the root cause."""
        distr_info = self.additional_info().get('distr_info')
        if distr_info:
            gang = distr_info.get('gang') or {}
            # chaos seam (mlcomp_tpu/testing/faults.py): kill one rank
            # AT BRING-UP — its peers strand at the coordinator until
            # the join timeout fails them fast as gang-peer-lost
            from mlcomp_tpu.testing.faults import fault_point
            fault_point('gang.rank_exit', phase='join',
                        rank=distr_info.get('process_index'),
                        gang=gang.get('id'), task=self.task.id)
            from mlcomp_tpu.parallel.distributed import (
                initialize_from_distr_info,
            )
            if initialize_from_distr_info(distr_info):
                self.logger.info(
                    f'task {self.task.id}: joined distributed job as '
                    f'process {distr_info.get("process_index")}/'
                    f'{distr_info.get("process_count")} '
                    f'(coordinator {distr_info.get("coordinator_address")}'
                    + (f', gang {gang.get("id")} generation '
                       f'{gang.get("generation")}' if gang else '')
                    + ')',
                    ComponentType.Worker, None, self.task.id)

    def create_executor(self, folder: str):
        config = Config.from_yaml(self.dag.config)
        info = self.additional_info()
        executor_name = self.task.executor
        executor_type = (
            config.get('executors', {})
            .get(executor_name, {})
            .get('type', executor_name))
        self.storage.import_executor(folder, executor_type)
        # deferred import: the executors package is only pulled once the
        # task actually runs (import_module, not dotted __import__ whose
        # return value is the top-level package)
        executors = importlib.import_module('mlcomp_tpu.worker.executors')
        self.executor = executors.Executor.from_config(
            executor_name, config, additional_info=info,
            session=self.session, logger=self.logger)

    def execute(self, folder: str):
        from mlcomp_tpu.testing.faults import fault_point
        fault_point('task.execute', task=self.task_id)
        cwd = os.getcwd()
        os.chdir(folder)
        try:
            result = self.executor(self.task, self.dag,
                                   session=self.session,
                                   logger=self.logger)
        finally:
            os.chdir(cwd)
        self.task.result = self.executor.result_serialize(result)
        self.provider.update(self.task, ['result'])

        # multi-stage requeue-to-same-worker
        # (reference worker/tasks.py:215-236)
        if isinstance(result, dict) and 'stage' in result \
                and 'stages' in result:
            stages = result['stages']
            stage = result['stage']
            idx = stages.index(stage) if stage in stages else -1
            if 0 <= idx < len(stages) - 1:
                info = self.additional_info()
                info['stage'] = stages[idx + 1]
                self._save_info(info)
                self.provider.change_status(self.task, TaskStatus.Queued)
                if self.task.queue_id is not None:
                    return self._requeue()
                # debug mode: loop stages in-process
                return self.build()
        # a supervisor verdict may have landed MID-RUN (sweep prune,
        # watchdog stall-kill) without a signal reaching us — in
        # in-process worker mode there is no subprocess to SIGTERM.
        # Re-read before the Success transition: a terminal verdict on
        # the row wins over this worker's late "it returned fine".
        current = self.provider.by_id(self.task.id)
        if current is not None and \
                current.status >= int(TaskStatus.Failed):
            return TaskStatus(current.status).name.lower()
        self.provider.change_status(self.task, TaskStatus.Success)
        return 'success'

    def personal_queue(self) -> str:
        import socket
        docker = self.task.docker_assigned or 'default'
        from mlcomp_tpu.utils.misc import hostname
        return f'{hostname()}_{docker}_{self.worker_index}'

    def _save_info(self, info: dict):
        from mlcomp_tpu.utils.io import yaml_dump
        self.task.additional_info = yaml_dump(info)
        self.provider.update(self.task, ['additional_info'])

    def _requeue(self) -> str:
        """Re-enqueue this task on THIS worker's personal queue and point
        the task at the NEW message so kill/revoke targets the pending
        dispatch, not the consumed one."""
        msg_id = self.queue_provider.enqueue(self.personal_queue(), {
            'action': 'execute', 'task_id': self.task.id})
        self.task.queue_id = msg_id
        self.provider.update(self.task, ['queue_id'])
        return 'requeued'

    def install_libraries(self):
        """Opt-in: install recorded DagLibrary versions and requeue ONCE
        so a fresh process imports them (reference
        worker/storage.py:206-215 + requeue at worker/tasks.py:170-183).
        Returns 'requeued' when the task was re-enqueued."""
        from mlcomp_tpu import INSTALL_LIBRARIES
        if not INSTALL_LIBRARIES:
            return None
        info = self.additional_info()
        if info.get('libraries_installed'):
            return None                 # the one allowed requeue is spent
        if info.get('distr_info'):
            # requeueing one process of a multi-host job would leave its
            # peers blocked at the coordinator until the join timeout —
            # provision distributed hosts up front instead
            self.logger.warning(
                f'task {self.task.id}: INSTALL_LIBRARIES skipped for a '
                f'distributed service task', ComponentType.Worker, None,
                self.task.id)
            return None
        installed = self.storage.install_libraries(self.dag.id)
        if not installed:
            return None
        self.logger.info(
            f'task {self.task.id}: installed {installed}; requeueing '
            f'for a fresh interpreter', ComponentType.Worker, None,
            self.task.id)
        if self.task.queue_id is not None:
            info['libraries_installed'] = True
            self._save_info(info)
            self.provider.change_status(self.task, TaskStatus.Queued)
            return self._requeue()
        # debug/in-process mode: no fresh interpreter to requeue into —
        # modules ALREADY imported keep their old version in this
        # process; don't spend the flag (a later queued dispatch still
        # gets its fresh-interpreter pass)
        self.logger.warning(
            f'task {self.task.id}: running in-process after install; '
            f'already-imported modules keep their previous versions',
            ComponentType.Worker, None, self.task.id)
        return None

    # ----------------------------------------------------------------- main
    def build(self):
        # each pipeline phase gets a telemetry span so "where did this
        # task's wall-clock go?" (code download vs executor import vs
        # the run itself) is answerable from GET /telemetry/spans
        from mlcomp_tpu.telemetry.spans import flush_spans, span
        _install_crash_flush(self.session)
        try:
            with span('task.pipeline', task=self.task_id):
                with span('task.load'):
                    self.create_base()
                    self.check_status()
                    self.mark_in_progress()
                with span('task.download'):
                    folder = self.download()
                with span('task.install_libraries'):
                    requeued = self.install_libraries()
                if requeued:
                    return requeued
                self.pin_cores()
                with span('task.init_distributed'):
                    self.init_distributed()
                self.require_accelerator()
                with span('task.create_executor',
                          tags={'executor': self.task.executor}):
                    self.create_executor(folder)
                with span('task.execute',
                          tags={'executor': self.task.executor}):
                    return self.execute(folder)
        except Exception as e:
            if self.task is not None:
                self.logger.error(
                    f'task {self.task_id} failed: '
                    f'{traceback.format_exc()}',
                    ComponentType.Worker, None, self.task_id)
                task = self.provider.by_id(self.task_id)
                if task is not None and task.status < int(
                        TaskStatus.Failed):
                    # classify for the supervisor's retry pass
                    # (mlcomp_tpu/recovery.py): a DB hiccup or
                    # connection drop retries from the last
                    # checkpoint, an executor bug fails for good. A
                    # gang rank (distr_info present) gets the
                    # distributed-runtime carve-out: a collective
                    # dying because a PEER vanished is gang-peer-lost
                    # collateral, not a permanent bug in this rank
                    from mlcomp_tpu.recovery import classify_exception
                    gang = False
                    try:
                        gang = bool((yaml_load(task.additional_info)
                                     or {}).get('distr_info')) \
                            if task.additional_info else False
                    except Exception:
                        pass
                    self.provider.fail_with_reason(
                        task, classify_exception(e, gang=gang))
            raise
        finally:
            try:
                flush_spans(self.session)
            except Exception:
                pass
            if self.exit_on_finish:
                os._exit(0)  # noqa — per-task process hygiene


def execute_by_id(task_id: int, exit: bool = False, folder: str = None,
                  worker_index: int = -1, session: Session = None,
                  trace_id: str = None):
    builder = ExecuteBuilder(
        task_id, exit_on_finish=exit, folder=folder,
        worker_index=worker_index, session=session, trace_id=trace_id)
    return builder.build()


def _pid_is_task_process(pid: int, task_id: int = None,
                         require_marker: bool = False) -> bool:
    """Guard against pid reuse: only SIGTERM a process that carries the
    MLCOMP_TASK_ID exec-time env marker for this task (set by the worker
    when spawning the task subprocess) or that is an mlcomp_tpu process
    (in-process worker daemon mode). ``require_marker`` disables the
    daemon-cmdline fallback — used for already-finished statuses where
    killing the persistent daemon itself would be worse than leaking
    the process."""
    try:
        import psutil
        proc = psutil.Process(pid)
        if task_id is not None:
            try:
                env = proc.environ()
            except (psutil.AccessDenied, psutil.ZombieProcess):
                env = {}
            marker = env.get('MLCOMP_TASK_ID')
            if marker is not None:
                # a marker naming a DIFFERENT task means the pid was
                # reused by another task's subprocess — never kill it
                return marker == str(task_id)
        if require_marker:
            return False
        # no marker readable: in-process daemon mode (the daemon itself
        # runs the task) — match on the daemon cmdline
        return 'mlcomp_tpu' in ' '.join(proc.cmdline())
    except Exception:
        return False


def kill_task(task_id: int, session: Session = None):
    """Stop a task: revoke its queue message if pending; kill its process
    tree if it runs on THIS host; otherwise route the kill through the
    owning host's queue, whose worker daemon handles the 'kill' action
    (reference worker/tasks.py:336-362 revokes via celery + kills via a
    task sent to the remote worker — a local os.kill on a foreign pid
    would hit an unrelated process)."""
    import socket
    session = session or Session.create_session(key='worker')
    provider = TaskProvider(session)
    task = provider.by_id(task_id)
    if task is None:
        return False
    if task.queue_id is not None:
        QueueProvider(session).revoke(task.queue_id)
    # Stopped/Failed included: a remote-routed kill arrives AFTER the
    # initiator already flipped the status — Stopped by a plain stop,
    # Failed by the watchdog's stall handling — but the process is
    # still alive. For Failed the pid-kill additionally requires the
    # MLCOMP_TASK_ID marker to name THIS task (no daemon-cmdline
    # fallback): a user stopping an already-failed task in in-process
    # daemon mode must not terminate the daemon.
    if task.status in (int(TaskStatus.InProgress),
                       int(TaskStatus.Stopped),
                       int(TaskStatus.Failed)) and task.pid:
        from mlcomp_tpu.utils.misc import hostname
        local = task.computer_assigned in (None, '', hostname())
        if local:
            if _pid_is_task_process(
                    task.pid, task.id,
                    require_marker=task.status ==
                    int(TaskStatus.Failed)):
                from mlcomp_tpu.utils.misc import kill_child_processes
                import signal
                kill_child_processes(task.pid)
                try:
                    os.kill(task.pid, signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        else:
            # route (and re-route on repeat calls — the first message may
            # have been lost) through the owning host's SUPERVISOR queue:
            # the host agent is never blocked on a running task, so the
            # kill drains even when every worker is busy (reference queue
            # naming {host}_{docker}_supervisor, worker/__main__.py:147-181)
            docker = task.docker_assigned or 'default'
            queue = f'{task.computer_assigned}_{docker}_supervisor'
            payload = {'action': 'kill', 'task_id': task.id}
            # HA supervisors: stamp the issuing leader's fencing epoch
            # into the routed kill so the control-queue log says WHICH
            # incarnation ordered it (the enqueue itself is already
            # epoch-fenced through the session — a zombie's kill never
            # reaches the queue; the stamp is forensics, not the
            # guard). Consumers ignore unknown payload fields.
            epoch = getattr(session, 'fence_epoch', None)
            if epoch is not None:
                payload['epoch'] = int(epoch)
            QueueProvider(session).enqueue(queue, payload)
    if task.status < int(TaskStatus.Failed):
        provider.change_status(task, TaskStatus.Stopped)
    return True


__all__ = ['ExecuteBuilder', 'execute_by_id', 'kill_task']
