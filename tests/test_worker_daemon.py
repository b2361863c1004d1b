"""Worker daemon paths that round 1 left untested (VERDICT weak #7):
subprocess execution mode, the dead-pid reaper, the autorestart process
group, and multi-stage requeue through a real queue consume cycle."""

import datetime
import os
import time

from mlcomp_tpu.db.enums import TaskStatus
from mlcomp_tpu.db.providers import QueueProvider, TaskProvider
from mlcomp_tpu.server.create_dags import dag_standard
from mlcomp_tpu.server.supervisor import SupervisorBuilder
from mlcomp_tpu.utils.logging import create_logger
from mlcomp_tpu.utils.misc import now
from test_supervisor import add_computer


def _dispatch(session, monkeypatch, config, folder=None):
    import mlcomp_tpu.worker.__main__ as wmain
    monkeypatch.setattr(wmain, 'HOSTNAME', 'host1')
    dag, tasks = dag_standard(session, config,
                              upload_folder=folder)
    add_computer(session, name='host1')
    SupervisorBuilder(session=session).build()
    return dag, tasks


class TestSubprocessExecution:
    def test_task_runs_in_real_subprocess(self, session, monkeypatch,
                                          tmp_path):
        """in_process=False spawns `python -m mlcomp_tpu.worker
        run-task` — the production path on a worker host."""
        import mlcomp_tpu.worker.__main__ as wmain
        folder = tmp_path / 'exp'
        folder.mkdir()
        (folder / 'executors.py').write_text(
            'import os\n'
            'from mlcomp_tpu.worker.executors import Executor\n'
            '@Executor.register\n'
            'class PidProbe(Executor):\n'
            '    def __init__(self, **kw):\n'
            '        pass\n'
            '    def work(self):\n'
            '        return {"pid": os.getpid()}\n')
        config = {
            'info': {'name': 'sub_dag', 'project': 'p_subproc'},
            'executors': {'probe': {'type': 'pid_probe'}},
        }
        # the subprocess imports mlcomp_tpu with test env vars set —
        # keep it from wiping the sandbox root this test runs in
        monkeypatch.setenv('MLCOMP_TPU_KEEP_ROOT', '1')
        monkeypatch.setenv('MLCOMP_TPU_ROOT',
                           __import__('mlcomp_tpu').ROOT_FOLDER)
        dag, tasks = _dispatch(session, monkeypatch, config, str(folder))
        logger = create_logger(session)
        qp = QueueProvider(session)
        consumed = wmain._consume_one(session, qp, logger, 0,
                                      in_process=False)
        assert consumed
        task = TaskProvider(session).by_id(tasks['probe'][0])
        assert task.status == int(TaskStatus.Success), task.result
        import json
        result = json.loads(task.result)
        assert result['pid'] != os.getpid()  # really another process

    def test_subprocess_failure_marks_failed(self, session, monkeypatch,
                                             tmp_path):
        import mlcomp_tpu.worker.__main__ as wmain
        folder = tmp_path / 'exp'
        folder.mkdir()
        (folder / 'executors.py').write_text(
            'from mlcomp_tpu.worker.executors import Executor\n'
            '@Executor.register\n'
            'class Exploder(Executor):\n'
            '    def __init__(self, **kw):\n'
            '        pass\n'
            '    def work(self):\n'
            '        raise RuntimeError("kaboom")\n')
        config = {
            'info': {'name': 'boom_dag', 'project': 'p_subproc_fail'},
            'executors': {'boom': {'type': 'exploder'}},
        }
        monkeypatch.setenv('MLCOMP_TPU_KEEP_ROOT', '1')
        monkeypatch.setenv('MLCOMP_TPU_ROOT',
                           __import__('mlcomp_tpu').ROOT_FOLDER)
        dag, tasks = _dispatch(session, monkeypatch, config, str(folder))
        logger = create_logger(session)
        qp = QueueProvider(session)
        wmain._consume_one(session, qp, logger, 0, in_process=False)
        task = TaskProvider(session).by_id(tasks['boom'][0])
        assert task.status == int(TaskStatus.Failed)
        assert qp.status(task.queue_id) == 'failed'


class TestReaper:
    def _in_progress_task(self, session, pid, age_seconds):
        from mlcomp_tpu.db.models import Task
        task = Task(name='t', executor='t', dag=self._dag(session),
                    status=int(TaskStatus.InProgress),
                    computer_assigned='host1', pid=pid,
                    last_activity=now() - datetime.timedelta(
                        seconds=age_seconds))
        TaskProvider(session).add(task)
        return task

    def _dag(self, session):
        from mlcomp_tpu.db.models import Dag
        from mlcomp_tpu.db.providers import ProjectProvider
        p = ProjectProvider(session).add_project('p_reaper')
        dag = Dag(name='d', config='', project=p.id, created=now())
        session.add(dag)
        return dag.id

    def test_dead_pid_past_grace_fails(self, session, monkeypatch):
        import mlcomp_tpu.worker.__main__ as wmain
        monkeypatch.setattr(wmain, 'HOSTNAME', 'host1')
        dead_pid = 2 ** 22 + 1234  # beyond pid_max defaults
        task = self._in_progress_task(session, dead_pid, age_seconds=120)
        wmain.stop_processes_not_exist(session, create_logger(session))
        assert TaskProvider(session).by_id(task.id).status == \
            int(TaskStatus.Failed)

    def test_dead_pid_within_grace_spared(self, session, monkeypatch):
        import mlcomp_tpu.worker.__main__ as wmain
        monkeypatch.setattr(wmain, 'HOSTNAME', 'host1')
        task = self._in_progress_task(session, 2 ** 22 + 99,
                                      age_seconds=5)
        wmain.stop_processes_not_exist(session, create_logger(session))
        assert TaskProvider(session).by_id(task.id).status == \
            int(TaskStatus.InProgress)

    def test_live_pid_spared(self, session, monkeypatch):
        import mlcomp_tpu.worker.__main__ as wmain
        monkeypatch.setattr(wmain, 'HOSTNAME', 'host1')
        task = self._in_progress_task(session, os.getpid(),
                                      age_seconds=120)
        wmain.stop_processes_not_exist(session, create_logger(session))
        assert TaskProvider(session).by_id(task.id).status == \
            int(TaskStatus.InProgress)


class TestProcessGroup:
    def test_child_restarts_after_exit(self):
        from mlcomp_tpu.utils.procgroup import run_process_group
        deadline = time.time() + 30
        specs = [['-c', 'import time; time.sleep(600)']]
        state = {}

        # drive the loop from a thread so we can kill the child
        import threading
        result = {}

        def run():
            result['children'] = run_process_group(
                specs, poll_interval=0.2, install_signal=False,
                should_stop=lambda: state.get('done', False)
                or time.time() > deadline)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        time.sleep(1.0)
        import psutil
        me = psutil.Process()

        def group_children(exclude_pid=None):
            out = []
            for c in me.children(recursive=True):
                try:
                    if 'time.sleep(600)' in ' '.join(c.cmdline()) \
                            and c.pid != exclude_pid \
                            and c.status() != 'zombie':
                        out.append(c)
                except (psutil.ZombieProcess, psutil.NoSuchProcess):
                    continue
            return out

        children = group_children()
        assert children, 'group child not spawned'
        first_pid = children[0].pid
        children[0].terminate()
        # wait for the autorestart (fast-exit backoff is ~2 s)
        fresh = []
        for _ in range(60):
            time.sleep(0.25)
            fresh = group_children(exclude_pid=first_pid)
            if fresh:
                break
        assert fresh, 'child was not restarted'
        state['done'] = True
        t.join(timeout=10)
        assert not t.is_alive()
        # group terminated its children on stop
        time.sleep(0.5)
        assert not group_children()


class TestStagePerDispatchRequeue:
    def test_two_stage_training_through_real_queue(self, session,
                                                   monkeypatch,
                                                   tmp_path):
        """Stage 1 runs, the task requeues itself on the worker's
        personal queue, stage 2 runs on the next consume, export
        happens at the end (reference worker/tasks.py:215-236)."""
        import mlcomp_tpu.worker.__main__ as wmain
        # NO hostname patch here: the requeue path computes the personal
        # queue from the REAL hostname (worker/tasks.py personal_queue),
        # so the consumer must listen under the real name too
        config = {
            'info': {'name': 'stage_dag', 'project': 'p_stagereq'},
            'executors': {
                'train': {
                    'type': 'jax_train',
                    'model': {'name': 'mlp', 'num_classes': 4,
                              'hidden': [16], 'dtype': 'float32'},
                    'dataset': {'name': 'synthetic_images',
                                'n_train': 128, 'n_valid': 32,
                                'image_size': 8, 'channels': 1,
                                'num_classes': 4},
                    'batch_size': 32,
                    'stage_per_dispatch': True,
                    'model_name': 'staged_model',
                    'stages': [
                        {'name': 's1', 'epochs': 1,
                         'optimizer': {'name': 'adam', 'lr': 3e-3}},
                        {'name': 's2', 'epochs': 1,
                         'optimizer': {'name': 'adam', 'lr': 1e-3}},
                    ],
                },
            },
        }
        dag, tasks = dag_standard(session, config)
        add_computer(session, name=wmain.HOSTNAME)
        SupervisorBuilder(session=session).build()
        tid = tasks['train'][0]
        logger = create_logger(session)
        qp = QueueProvider(session)
        tp = TaskProvider(session)

        assert wmain._consume_one(session, qp, logger, 0,
                                  in_process=True)
        task = tp.by_id(tid)
        # stage 1 done -> requeued, not finished
        assert task.status == int(TaskStatus.Queued)
        from mlcomp_tpu.utils.io import yaml_load
        assert yaml_load(task.additional_info)['stage'] == 's2'

        assert wmain._consume_one(session, qp, logger, 0,
                                  in_process=True)
        task = tp.by_id(tid)
        assert task.status == int(TaskStatus.Success)
        # final stage's dispatch exported the model
        from mlcomp_tpu import MODEL_FOLDER
        export = os.path.join(MODEL_FOLDER, 'p_stagereq',
                              'staged_model.msgpack')
        assert os.path.exists(export)


class TestChaos:
    """Fault injection the reference never had (SURVEY §4: 'no fault
    injection anywhere') — VERDICT r2 next-#10.

    A worker machine dying mid-task and the control-plane API dying
    under a remote worker are the two failure modes the recovery
    machinery (reaper + restart-with-resume, session-heal retry loop)
    exists for; these tests kill real processes and assert the recovery
    actually lands.
    """

    def test_sigkill_worker_mid_task_reaper_requeue_success(
            self, session, monkeypatch, tmp_path):
        """SIGKILL a real worker process (and its run-task child) mid-
        task -> reaper fails the orphaned task -> dag restart requeues
        it with resume info -> second attempt succeeds."""
        import signal
        import subprocess
        import sys

        import mlcomp_tpu
        import mlcomp_tpu.worker.__main__ as wmain
        from mlcomp_tpu.server.api import api_dag_start

        folder = tmp_path / 'exp'
        folder.mkdir()
        (folder / 'executors.py').write_text(
            'import os, time\n'
            'from mlcomp_tpu.worker.executors import Executor\n'
            '@Executor.register\n'
            'class CrashyThenFine(Executor):\n'
            '    def __init__(self, **kw):\n'
            '        pass\n'
            '    def work(self):\n'
            '        marker = os.path.join("data", "attempted")\n'
            '        if os.path.exists(marker):\n'
            '            return {"attempt": 2, "resumed": True}\n'
            '        open(marker, "w").write("1")\n'
            '        time.sleep(120)\n')
        config = {
            'info': {'name': 'chaos_dag', 'project': 'p_chaos'},
            'executors': {'crashy': {'type': 'crashy_then_fine'}},
        }
        monkeypatch.setenv('MLCOMP_TPU_KEEP_ROOT', '1')
        monkeypatch.setenv('MLCOMP_TPU_ROOT', mlcomp_tpu.ROOT_FOLDER)
        dag, tasks = _dispatch(session, monkeypatch, config, str(folder))
        tid = tasks['crashy'][0]
        tp = TaskProvider(session)

        env = dict(os.environ, MLCOMP_HOSTNAME='host1',
                   JAX_PLATFORMS='cpu')
        worker = subprocess.Popen(
            [sys.executable, '-m', 'mlcomp_tpu.worker', 'worker', '0'],
            env=env, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            # wait until the executor is genuinely MID-task: InProgress,
            # pid recorded, and the attempt marker written (killing
            # earlier would make attempt 2 re-run the sleep branch)
            marker = os.path.join(mlcomp_tpu.DATA_FOLDER, 'p_chaos',
                                  'attempted')
            deadline = time.time() + 60
            task = None
            while time.time() < deadline:
                task = tp.by_id(tid)
                if task.status == int(TaskStatus.InProgress) \
                        and task.pid and os.path.exists(marker):
                    break
                time.sleep(0.3)
            assert task is not None and task.pid \
                and os.path.exists(marker), \
                f'task never started: status={task and task.status}'

            # machine dies: SIGKILL the worker's whole process group
            # (worker + its run-task child share it)
            os.killpg(os.getpgid(worker.pid), signal.SIGKILL)
            worker.wait(timeout=30)
            # the SIGKILLed run-task child reparents to init and only
            # stops pid_exists()-ing once reaped — give a loaded CI
            # box real time, the kill itself is instant
            deadline = time.time() + 30
            from mlcomp_tpu import native
            while time.time() < deadline and native.pid_exists(task.pid):
                time.sleep(0.2)
            assert not native.pid_exists(task.pid)

            # task is orphaned InProgress; age it past the 30 s grace
            session.execute(
                'UPDATE task SET last_activity=? WHERE id=?',
                (now() - datetime.timedelta(seconds=90), tid))
            wmain.stop_processes_not_exist(session, create_logger(session))
            assert tp.by_id(tid).status == int(TaskStatus.Failed)

            # operator hits restart: Failed -> NotRan with resume info
            res = api_dag_start({'id': dag.id}, session)
            assert tid in res['restarted']
            restarted = tp.by_id(tid)
            assert restarted.status == int(TaskStatus.NotRan)
            from mlcomp_tpu.utils.io import yaml_load
            info = yaml_load(restarted.additional_info)
            assert info['resume']['master_task_id'] == tid

            # supervisor requeues; a fresh consume runs attempt 2
            SupervisorBuilder(session=session).build()
            logger = create_logger(session)
            qp = QueueProvider(session)
            consumed = wmain._consume_one(session, qp, logger, 0,
                                          in_process=True)
            assert consumed
            final = tp.by_id(tid)
            assert final.status == int(TaskStatus.Success), final.result
            assert '"resumed": true' in final.result
        finally:
            if worker.poll() is None:
                try:
                    os.killpg(os.getpgid(worker.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

    def test_api_death_under_remote_session_clean_fail_and_recover(
            self, session):
        """Kill the API server under a RemoteSession worker: in-flight
        use fails with a clean error (the worker loop's heal path
        catches it), and the same RemoteSession works again once the
        server is back — stateless HTTP, nothing to rebuild."""
        import urllib.error

        from mlcomp_tpu import TOKEN
        from mlcomp_tpu.db.models import Computer
        from mlcomp_tpu.db.providers import ComputerProvider
        from mlcomp_tpu.db.remote import RemoteSession
        from mlcomp_tpu.server.api import ApiServer

        server = ApiServer(host='127.0.0.1', port=0).start_background()
        port = server.port
        rs = RemoteSession(f'http://127.0.0.1:{port}',
                           key='chaos_remote', token=TOKEN)
        provider = ComputerProvider(rs)
        provider.create_or_update(
            Computer(name='chaosbox', cores=1, cpu=1, memory=1), 'name')
        assert provider.by_name('chaosbox') is not None

        server.shutdown()                      # control plane dies
        import pytest as _pytest
        with _pytest.raises((urllib.error.URLError, ConnectionError,
                             OSError)):
            provider.by_name('chaosbox')       # clean failure, no hang

        # server comes back on the same address; the session recovers
        # without any reconstruction (what worker()'s heal loop does)
        server2 = ApiServer(host='127.0.0.1', port=port)
        server2.start_background()
        try:
            row = provider.by_name('chaosbox')
            assert row is not None and row.cores == 1
        finally:
            server2.shutdown()


class TestTpuTelemetry:
    def test_in_process_worker_reports_tpu_usage(self, session,
                                                 monkeypatch, tmp_path):
        """The in-process worker (the one process holding a TPU
        client) writes the 'tpu' usage field after each task, and
        worker_usage PRESERVES it instead of clobbering (the
        worker-supervisor must never create its own client — a second
        live client starves the compute client's compiles ~30x)."""
        import json

        import mlcomp_tpu.worker.__main__ as wmain
        from mlcomp_tpu.db.providers import ComputerProvider

        folder = tmp_path / 'exp'
        folder.mkdir()
        (folder / 'executors.py').write_text(
            'from mlcomp_tpu.worker.executors import Executor\n'
            '@Executor.register\n'
            'class Noop2(Executor):\n'
            '    def __init__(self, **kw):\n'
            '        pass\n'
            '    def work(self):\n'
            '        return {}\n')
        config = {
            'info': {'name': 'tpu_usage_dag', 'project': 'p_usage'},
            'executors': {'noop': {'type': 'noop2'}},
        }
        wmain.register_computer(session, cores=1)
        fake = [{'id': 0, 'kind': 'fake-tpu', 'hbm_used': 123}]
        monkeypatch.setattr(wmain, '_tpu_usage', lambda: fake)
        dag, tasks = _dispatch(session, monkeypatch, config, str(folder))
        logger = create_logger(session)
        qp = QueueProvider(session)
        assert wmain._consume_one(session, qp, logger, 0,
                                  in_process=True)
        provider = ComputerProvider(session)
        row = provider.by_name(wmain.HOSTNAME)
        assert json.loads(row.usage)['tpu'] == fake
        # the supervisor's sampler keeps the worker-written field
        monkeypatch.setattr(wmain, '_tpu_usage', lambda: [])
        wmain.worker_usage(session, logger)
        usage = json.loads(provider.by_name(wmain.HOSTNAME).usage)
        assert usage['tpu'] == fake
        assert 'cpu' in usage and 'memory' in usage


class TestOneProcessPerChip:
    """Device selection for a chip that ONE process owns at a time
    (docs/deployment.md "TPU process model")."""

    def test_chip_pin_env_on_a_2x2_host(self):
        from mlcomp_tpu.worker.tasks import chip_pin_env
        # the whole host: the runtime's defaults, nothing set
        assert chip_pin_env([0, 1, 2, 3], 4) == {}
        assert chip_pin_env([0], 1) == {}
        assert chip_pin_env([], 4) == {}
        one = chip_pin_env([2], 4)
        assert one['TPU_VISIBLE_CHIPS'] == '2'
        assert one['TPU_CHIPS_PER_PROCESS_BOUNDS'] == '1,1,1'
        assert one['TPU_PROCESS_BOUNDS'] == '1,1,1'
        two = chip_pin_env([2, 3], 4)
        assert two['TPU_VISIBLE_CHIPS'] == '2,3'
        assert two['TPU_CHIPS_PER_PROCESS_BOUNDS'] == '1,2,1'
        # side-by-side processes never share a runtime port
        ports = {chip_pin_env([c], 4)['TPU_PROCESS_PORT']
                 for c in range(4)}
        assert len(ports) == 4
        import pytest
        with pytest.raises(ValueError, match='3 chips'):
            chip_pin_env([0, 1, 2], 4)
        # found on the chips: only aligned pairs start as a 1,2,1 block
        for pair in ([0, 2], [1, 2]):
            with pytest.raises(ValueError, match='aligned pair'):
                chip_pin_env(pair, 4)

    def test_core_probe_failure_is_an_error_not_zero(
            self, session, monkeypatch):
        """A probe child that dies names its stderr; an already
        registered host keeps its count, an unknown one is fatal."""
        import subprocess

        import pytest

        import mlcomp_tpu.worker.__main__ as wmain
        from mlcomp_tpu.db.providers import ComputerProvider
        monkeypatch.delenv('MLCOMP_TPU_CORES', raising=False)
        # forced CPU: a CPU host by declaration, no probe at all
        monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
        monkeypatch.setattr(
            subprocess, 'run',
            lambda *a, **k: pytest.fail('probed a forced-CPU host'))
        assert wmain._tpu_core_count() == 0
        monkeypatch.delenv('JAX_PLATFORMS')
        monkeypatch.setattr(
            subprocess, 'run', lambda *a, **k:
            subprocess.CompletedProcess(a, 1, '', 'libtpu: in use'))
        with pytest.raises(wmain.CoreProbeError, match='libtpu: in use'):
            wmain._tpu_core_count()
        logger = create_logger(session)
        with pytest.raises(wmain.CoreProbeError):
            wmain._host_cores(session, logger)       # no row: fatal
        wmain.register_computer(session, cores=4)
        assert wmain._host_cores(session, logger) == 4
        assert ComputerProvider(session).by_name(
            wmain.HOSTNAME).cores == 4
        monkeypatch.setattr(
            subprocess, 'run', lambda *a, **k:
            subprocess.CompletedProcess(a, 0, 'noise\n4\n', ''))
        assert wmain._tpu_core_count() == 4

    def test_coreless_task_is_pinned_to_cpu(self, session, monkeypatch):
        """The run-task child of a task the supervisor gave no cores
        gets JAX_PLATFORMS=cpu; one with cores keeps the daemon's."""
        import mlcomp_tpu.worker.__main__ as wmain
        from mlcomp_tpu.db.models import Task
        provider = TaskProvider(session)
        seen = []

        class FakePopen:
            returncode = 0

            def __init__(self, cmd, env):
                seen.append(env.get('JAX_PLATFORMS'))

            def wait(self):
                pass

        monkeypatch.setattr(wmain.subprocess, 'Popen', FakePopen)
        monkeypatch.setenv('JAX_PLATFORMS', 'tpu')
        for cores in ('[]', '[0]'):
            task = Task(name='t', executor='e', cores_assigned=cores)
            provider.add(task)
            wmain._run_subprocess(task.id, 0, None, session)
        assert seen == ['cpu', 'tpu']

    def test_task_on_cores_without_a_tpu_fails(self, session,
                                               monkeypatch):
        """Placed on TPU cores, came up on the CPU backend: fails
        ``no-accelerator``; the explicit emulated mode is left alone."""
        import pytest

        from mlcomp_tpu.db.models import Task
        from mlcomp_tpu.recovery import (
            AcceleratorMissing, classify_exception,
        )
        from mlcomp_tpu.worker.tasks import ExecuteBuilder
        task = Task(name='t', executor='e', cores_assigned='[0]')
        TaskProvider(session).add(task)
        builder = ExecuteBuilder(task.id, session=session)
        builder.task = task
        builder.require_accelerator()       # JAX_PLATFORMS=cpu: emulated
        monkeypatch.delenv('JAX_PLATFORMS')
        with pytest.raises(AcceleratorMissing) as err:
            builder.require_accelerator()
        assert classify_exception(err.value) == 'no-accelerator'
        task.cores_assigned = '[]'
        builder.require_accelerator()       # no cores: nothing to hold

    def test_compile_cache_placed_from_outside(self, tmp_path):
        """Set: left alone. Unset: one fixed directory in the
        checkout. Forced CPU: no default. jax is never imported."""
        import subprocess
        import sys
        code = ('import os, sys, mlcomp_tpu; '
                'assert "jax" not in sys.modules; '
                'print(os.environ.get("JAX_COMPILATION_CACHE_DIR"))')
        base = {k: v for k, v in os.environ.items()
                if k not in ('JAX_COMPILATION_CACHE_DIR',
                             'JAX_PLATFORMS')}
        base['MLCOMP_TPU_ROOT'] = str(tmp_path / 'root')

        def cache_dir(**env):
            return subprocess.run(
                [sys.executable, '-c', code], env=dict(base, **env),
                capture_output=True, text=True, check=True,
                timeout=60).stdout.strip()

        import mlcomp_tpu
        repo = os.path.dirname(os.path.dirname(mlcomp_tpu.__file__))
        assert cache_dir() == os.path.join(repo, '.jax_cache')
        assert cache_dir(JAX_COMPILATION_CACHE_DIR='/given') == '/given'
        assert cache_dir(JAX_PLATFORMS='cpu') == 'None'
