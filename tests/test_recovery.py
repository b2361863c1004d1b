"""Chaos suite: the automatic-recovery subsystem under injected faults
(mlcomp_tpu/recovery.py, testing/faults.py, supervisor.process_recovery,
queue leases, checkpoint crash-safety, restart-with-resume API).

Determinism rules: faults fire on hit COUNTERS, lease/backoff expiry is
simulated by rewinding the stored timestamps — no test sleeps its way
into flakiness.
"""

import datetime
import json
import os
import sqlite3
import subprocess
import sys

import pytest

from mlcomp_tpu.db.enums import TaskStatus, TaskType
from mlcomp_tpu.db.models import Computer, Task
from mlcomp_tpu.db.providers import (
    AlertProvider, ComputerProvider, DockerProvider, QueueProvider,
    TaskProvider,
)
from mlcomp_tpu.recovery import (
    RecoveryConfig, classify_exception, classify_returncode, is_transient,
    retry_delay_s,
)
from mlcomp_tpu.server.supervisor import SupervisorBuilder
from mlcomp_tpu.testing import faults
from mlcomp_tpu.utils.io import yaml_dump, yaml_load
from mlcomp_tpu.utils.misc import now


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear_faults()
    yield
    faults.clear_faults()


def add_computer(session, name='host1', cores=8, heartbeat=True):
    ComputerProvider(session).create_or_update(
        Computer(name=name, cores=cores, cpu=16, memory=64,
                 ip='127.0.0.1', can_process_tasks=True), 'name')
    if heartbeat:
        DockerProvider(session).heartbeat(name, 'default')


def add_task(session, name='t', status=TaskStatus.NotRan, **kwargs):
    task = Task(name=name, executor=name, cores=1, cores_max=1,
                status=int(status), last_activity=now(), **kwargs)
    TaskProvider(session).add(task)
    return task


def rewind(session, table, column, row_id, seconds):
    session.execute(
        f'UPDATE {table} SET {column}=? WHERE id=?',
        (now() - datetime.timedelta(seconds=seconds), row_id))


def kill_heartbeat(session, computer):
    session.execute(
        'UPDATE docker SET last_activity=? WHERE computer=?',
        (now() - datetime.timedelta(seconds=3600), computer))


# ---------------------------------------------------------------- faults
class TestFaultRegistry:
    def test_disabled_is_inert(self):
        faults.clear_faults()
        for _ in range(3):
            faults.fault_point('anything')     # must not raise
        assert faults.fault_state() == {}

    def test_after_and_times_window_is_exact(self):
        faults.configure_faults({'p': {'action': 'raise',
                                       'exc': 'runtime',
                                       'after': 2, 'times': 2}})
        fired = []
        for hit in range(1, 6):
            try:
                faults.fault_point('p')
            except RuntimeError:
                fired.append(hit)
        assert fired == [2, 3]

    def test_exception_kinds(self):
        faults.configure_faults(
            {'db': {'action': 'raise', 'exc': 'operational',
                    'times': None}})
        with pytest.raises(sqlite3.OperationalError):
            faults.fault_point('db')
        faults.configure_faults(
            {'net': {'action': 'raise', 'exc': 'oserror',
                     'times': None}})
        with pytest.raises(OSError):
            faults.fault_point('net')

    def test_handler_receives_context(self):
        got = {}
        faults.register_handler('h', lambda **ctx: got.update(ctx))
        faults.fault_point('h', msg_id=7)
        assert got == {'msg_id': 7}

    def test_env_arming_in_subprocess(self):
        """The spec travels MLCOMP_FAULTS → child import → firing: the
        plumbing-free path a killed worker subprocess relies on."""
        code = ('from mlcomp_tpu.testing.faults import fault_point\n'
                'for _ in range(3):\n'
                '    fault_point("x")\n'
                'print("survived")\n')
        env = {**os.environ,
               'MLCOMP_TPU_KEEP_ROOT': '1',   # don't wipe the sandbox
               'MLCOMP_FAULTS': json.dumps(
                   {'x': {'action': 'exit', 'after': 2, 'code': 41}})}
        out = subprocess.run([sys.executable, '-c', code], env=env,
                             capture_output=True, text=True)
        assert out.returncode == 41
        assert 'survived' not in out.stdout


# -------------------------------------------------------- classification
class TestClassification:
    def test_taxonomy(self):
        assert classify_exception(
            sqlite3.OperationalError('database is locked')) == 'db-error'
        assert classify_exception(
            RuntimeError('remote db error: locked')) == 'db-error'
        assert classify_exception(ConnectionResetError()) == 'io-error'
        assert classify_exception(TimeoutError()) == 'io-error'
        assert classify_exception(ValueError('bug')) == 'executor-error'
        # a task placed on TPU cores that came up on the CPU backend:
        # permanent, never a run carried on with on the host
        from mlcomp_tpu.recovery import AcceleratorMissing, is_transient
        assert classify_exception(
            AcceleratorMissing('no TPU')) == 'no-accelerator'
        assert not is_transient('no-accelerator')
        # deterministic OS errors never classify transient
        assert classify_exception(
            FileNotFoundError('gone')) == 'executor-error'
        assert classify_exception(
            PermissionError('nope')) == 'executor-error'

    def test_cause_chain_is_walked(self):
        try:
            try:
                raise sqlite3.OperationalError('locked')
            except sqlite3.OperationalError as inner:
                raise RuntimeError('flush failed') from inner
        except RuntimeError as wrapped:
            assert classify_exception(wrapped) == 'db-error'

    def test_returncodes(self):
        assert classify_returncode(-15) == 'preempted'
        assert classify_returncode(143) == 'preempted'
        assert classify_returncode(-9) == 'preempted'
        assert classify_returncode(137) == 'preempted'
        assert classify_returncode(1) is None

    def test_transient_set(self):
        assert is_transient('stall-killed')
        assert is_transient('lease-expired')
        assert not is_transient('executor-error')
        assert not is_transient(None)

    def test_backoff_deterministic_and_capped(self):
        cfg = RecoveryConfig(backoff_base_s=10, backoff_factor=2,
                             backoff_cap_s=100, jitter_frac=0.2)
        a = retry_delay_s(1, cfg, task_id=42)
        assert a == retry_delay_s(1, cfg, task_id=42)  # no wall-clock
        assert 20 <= a <= 24                     # base*2 + <=20% jitter
        assert retry_delay_s(10, cfg, task_id=42) <= 120   # capped
        # jitter de-syncs different tasks
        assert retry_delay_s(1, cfg, task_id=1) != \
            retry_delay_s(1, cfg, task_id=2)


# --------------------------------------------------------------- leases
class TestQueueLease:
    def test_reclaim_exactly_once(self, session):
        qp = QueueProvider(session)
        msg_id = qp.enqueue('q', {'action': 'execute', 'task_id': 1})
        assert qp.claim(['q'], 'w:0')[0] == msg_id
        assert qp.claimed_expired(30) == []      # lease still fresh
        rewind(session, 'queue_message', 'claimed_at', msg_id, 60)
        (expired,) = qp.claimed_expired(30)
        assert expired.id == msg_id
        assert qp.reclaim(msg_id)
        assert not qp.reclaim(msg_id)            # the exactly-once guard
        assert qp.status(msg_id) == 'pending'
        # a fresh claim of the re-delivered message restarts the lease
        assert qp.claim(['q'], 'w2:0')[0] == msg_id
        assert qp.claimed_expired(30) == []

    def test_stranded_after_second_window(self, session):
        qp = QueueProvider(session)
        msg_id = qp.enqueue('q', {'action': 'execute', 'task_id': 1})
        qp.claim(['q'], 'w:0')
        rewind(session, 'queue_message', 'claimed_at', msg_id, 60)
        assert qp.reclaim(msg_id)
        assert qp.stranded_redelivered(30) == []   # window restarted
        rewind(session, 'queue_message', 'claimed_at', msg_id, 60)
        (stranded,) = qp.stranded_redelivered(30)
        assert stranded.id == msg_id

    def test_second_death_after_reclaim_fails_the_task(self, session):
        """The reviving host claims its re-delivered message, then dies
        again: no third delivery — the message fails (conditionally,
        racing completes win) and the task enters the retry path."""
        add_computer(session, 'zombie_host')
        task = add_task(session)
        tp = TaskProvider(session)
        qp = QueueProvider(session)
        msg_id = qp.enqueue('zombie_host_default',
                            {'action': 'execute', 'task_id': task.id})
        task.queue_id = msg_id
        tp.update(task, ['queue_id'])
        qp.claim(['zombie_host_default'], 'zombie_host:0')
        rewind(session, 'queue_message', 'claimed_at', msg_id, 60)
        assert qp.reclaim(msg_id)                  # first death
        qp.claim(['zombie_host_default'], 'zombie_host:0')  # revived
        tp.change_status(task, TaskStatus.InProgress)
        rewind(session, 'queue_message', 'claimed_at', msg_id, 60)
        rewind(session, 'task', 'last_activity', task.id, 4000)
        kill_heartbeat(session, 'zombie_host')     # ...and died again
        SupervisorBuilder(
            session=session,
            recovery_config=RecoveryConfig(lease_seconds=30)).build()
        assert qp.status(msg_id) == 'failed'
        task = tp.by_id(task.id)
        assert task.status == int(TaskStatus.Failed)
        assert task.failure_reason == 'lease-expired'

    def test_live_task_behind_heartbeat_gap_not_reclaimed(self, session):
        """A claimed message spans the whole task run; a 15 s docker
        heartbeat gap (daemon upgrade, stalled agent loop) while the
        task still shows life must NOT reclaim — that would start a
        duplicate execution of a healthy run."""
        add_computer(session, 'gappy_host', heartbeat=False)
        task = add_task(session)
        tp = TaskProvider(session)
        qp = QueueProvider(session)
        msg_id = qp.enqueue('gappy_host_default',
                            {'action': 'execute', 'task_id': task.id})
        task.queue_id = msg_id
        tp.update(task, ['queue_id'])
        qp.claim(['gappy_host_default'], 'gappy_host:0')
        rewind(session, 'queue_message', 'claimed_at', msg_id, 3600)
        # the run is alive: InProgress + fresh last_activity (the
        # metric-flush heartbeat touches it)
        tp.change_status(task, TaskStatus.InProgress)
        SupervisorBuilder(
            session=session,
            recovery_config=RecoveryConfig(lease_seconds=30)).build()
        assert qp.status(msg_id) == 'claimed'
        assert tp.by_id(task.id).status == int(TaskStatus.InProgress)

    def test_supervisor_leaves_live_hosts_alone(self, session):
        add_computer(session, 'alive_host')
        task = add_task(session)
        qp = QueueProvider(session)
        msg_id = qp.enqueue('alive_host_default',
                            {'action': 'execute', 'task_id': task.id})
        qp.claim(['alive_host_default'], 'alive_host:0')
        task.queue_id = msg_id
        TaskProvider(session).update(task, ['queue_id'])
        rewind(session, 'queue_message', 'claimed_at', msg_id, 3600)
        SupervisorBuilder(
            session=session,
            recovery_config=RecoveryConfig(lease_seconds=30)).build()
        # heartbeat is fresh → the local reaper owns it, not the lease
        assert qp.status(msg_id) == 'claimed'


# ---------------------------------------------------------- retry policy
class TestRetryPolicy:
    def _sup(self, session, **over):
        over.setdefault('lease_seconds', 30)
        over.setdefault('backoff_base_s', 60)
        return SupervisorBuilder(session=session,
                                 recovery_config=RecoveryConfig(**over))

    def test_permanent_failure_not_retried(self, session):
        add_computer(session)
        tp = TaskProvider(session)
        task = add_task(session, 'buggy')
        tp.fail_with_reason(task, 'executor-error')
        self._sup(session).build()
        task = tp.by_id(task.id)
        assert task.status == int(TaskStatus.Failed)
        assert task.next_retry_at is None
        assert (task.attempt or 0) == 0

    def test_bare_failed_without_reason_not_retried(self, session):
        add_computer(session)
        tp = TaskProvider(session)
        task = add_task(session, 'legacy')
        tp.change_status(task, TaskStatus.Failed)
        self._sup(session).build()
        task = tp.by_id(task.id)
        assert task.status == int(TaskStatus.Failed)
        assert task.next_retry_at is None

    def test_transient_schedules_then_requeues_with_resume(self, session):
        add_computer(session, 'host1')
        add_computer(session, 'host2')
        tp = TaskProvider(session)
        task = add_task(session, 'flaky')
        task.computer_assigned = 'host1'
        tp.update(task, ['computer_assigned'])
        tp.fail_with_reason(task, 'db-error')
        sup = self._sup(session)
        sup.build()
        task = tp.by_id(task.id)
        assert task.status == int(TaskStatus.Failed)
        assert task.next_retry_at is not None      # scheduled, not yet due
        rewind(session, 'task', 'next_retry_at', task.id, 10)
        sup.build()
        task = tp.by_id(task.id)
        assert task.attempt == 1
        assert task.status == int(TaskStatus.Queued)
        assert task.computer_assigned == 'host2'   # excluded host1
        info = yaml_load(task.additional_info)
        assert info['resume']['load_last'] is True
        assert info['resume']['master_task_id'] == task.id
        assert info['retry_exclude'] == ['host1']
        # the retry event is observable: metric row + /metrics family
        rows = session.query(
            "SELECT * FROM metric WHERE name='task.retry' AND task=?",
            (task.id,))
        assert len(rows) == 1
        assert json.loads(rows[0]['tags'])['reason'] == 'db-error'
        from mlcomp_tpu.telemetry.export import (
            parse_openmetrics, render_server_metrics,
        )
        doc = parse_openmetrics(render_server_metrics(session))
        assert any(
            labels.get('reason') == 'db-error'
            and str(labels.get('task')) == str(task.id) and value == 1
            for _, labels, value in
            doc['mlcomp_task_retries']['samples'])

    def test_exclusion_is_soft_on_single_computer(self, session):
        add_computer(session, 'only_host')
        tp = TaskProvider(session)
        task = add_task(session, 'flaky')
        task.computer_assigned = 'only_host'
        tp.update(task, ['computer_assigned'])
        tp.fail_with_reason(task, 'io-error')
        sup = self._sup(session)
        sup.build()
        rewind(session, 'task', 'next_retry_at', task.id, 10)
        sup.build()
        task = tp.by_id(task.id)
        # better the same host than parking the retry forever
        assert task.status == int(TaskStatus.Queued)
        assert task.computer_assigned == 'only_host'

    def test_exhausted_budget_raises_alert(self, session):
        add_computer(session)
        tp = TaskProvider(session)
        task = add_task(session, 'spent', attempt=2, max_retries=2)
        tp.fail_with_reason(task, 'preempted')
        self._sup(session).build()
        task = tp.by_id(task.id)
        assert task.status == int(TaskStatus.Failed)
        alerts = AlertProvider(session).get(status='open',
                                            rule='retry-exhausted')
        assert any(a.task == task.id and a.severity == 'critical'
                   for a in alerts)
        # re-ticking dedups instead of stacking rows
        self._sup(session).build()
        assert len(AlertProvider(session).get(
            status='open', rule='retry-exhausted')) == 1

    def _distributed_family(self, session, child_reasons):
        tp = TaskProvider(session)
        parent = add_task(session, 'master')
        tp.change_status(parent, TaskStatus.InProgress)
        for i, reason in enumerate(child_reasons):
            child = add_task(session, f'master_{i}',
                             type=int(TaskType.Service),
                             additional_info=yaml_dump(
                                 {'distr_info': {'process_index': i}}))
            child.parent = parent.id
            tp.update(child, ['parent'])
            if reason:
                tp.fail_with_reason(child, reason)
            else:
                tp.change_status(child, TaskStatus.Failed)
        return parent

    def test_parent_inherits_transient_child_reason(self, session):
        """A distributed parent failed by aggregation must inherit its
        children's TRANSIENT verdict, or distributed tasks would never
        auto-retry (the retry pass skips children and reasonless
        parents)."""
        add_computer(session)
        tp = TaskProvider(session)
        parent = self._distributed_family(session, ['preempted'])
        sup = self._sup(session)
        sup.build()
        parent = tp.by_id(parent.id)
        assert parent.status == int(TaskStatus.Failed)
        assert parent.failure_reason == 'preempted'
        sup.build()     # the SAME machinery now schedules the retry
        assert tp.by_id(parent.id).next_retry_at is not None

    def test_parent_pinned_by_permanent_child_reason(self, session):
        """Any permanent child failure pins the parent Failed — and
        overwrites a stale transient reason from an earlier attempt,
        which would otherwise retry into the same bug forever."""
        add_computer(session)
        tp = TaskProvider(session)
        parent = self._distributed_family(session, ['executor-error'])
        parent.failure_reason = 'stall-killed'   # stale, from attempt 1
        tp.update(parent, ['failure_reason'])
        sup = self._sup(session)
        sup.build()
        parent = tp.by_id(parent.id)
        assert parent.status == int(TaskStatus.Failed)
        assert parent.failure_reason == 'executor-error'
        sup.build()
        assert tp.by_id(parent.id).next_retry_at is None   # no retry

    def test_resolved_exhaustion_alert_stays_resolved(self, session):
        """An operator resolving a retry-exhausted alert must not see
        it re-raised on the next tick — the alert fires once per
        exhaustion (keyed to the task's final failure time)."""
        add_computer(session)
        tp = TaskProvider(session)
        task = add_task(session, 'acked', attempt=1, max_retries=1)
        tp.fail_with_reason(task, 'db-error')
        sup = self._sup(session)
        sup.build()
        ap = AlertProvider(session)
        (alert,) = ap.get(status='open', rule='retry-exhausted')
        assert ap.resolve(alert.id)
        sup.build()
        assert ap.get(status='open', rule='retry-exhausted') == []

    def test_requeue_detaches_stale_service_children(self, session):
        add_computer(session)
        tp = TaskProvider(session)
        parent = add_task(session, 'master')
        child = add_task(session, 'master_0',
                         type=int(TaskType.Service),
                         additional_info=yaml_dump(
                             {'distr_info': {'process_index': 0}}))
        child.parent = parent.id
        child.computer_assigned = 'host1'
        tp.update(child, ['parent', 'computer_assigned'])
        tp.change_status(child, TaskStatus.Failed)
        tp.fail_with_reason(parent, 'worker-lost')
        sup = self._sup(session)
        sup.build()
        rewind(session, 'task', 'next_retry_at', parent.id, 10)
        sup.build()
        parent = tp.by_id(parent.id)
        assert parent.status in (int(TaskStatus.NotRan),
                                 int(TaskStatus.Queued))
        # resume points at the rank-0 child's checkpoint folder...
        info = yaml_load(parent.additional_info)
        assert info['resume']['master_task_id'] == child.id
        # ...and the stale Failed child no longer aggregates into the
        # fresh parent (next tick would otherwise re-fail it)
        assert tp.by_id(child.id).parent is None
        sup.build()
        assert tp.by_id(parent.id).status != int(TaskStatus.Failed)

    def test_requeue_without_master_drops_stale_resume(self, session):
        """When no rank-0 master is found THIS attempt, the requeue
        must drop a previous attempt's resume blob — restoring a
        two-attempts-old checkpoint silently would be worse than
        starting from scratch."""
        from mlcomp_tpu.recovery import reset_for_requeue
        tp = TaskProvider(session)
        task = add_task(session, 'stale', additional_info=yaml_dump(
            {'resume': {'master_task_id': 42, 'load_last': True}}))
        reset_for_requeue(tp, task, resume=None)
        info = yaml_load(tp.by_id(task.id).additional_info)
        assert 'resume' not in info

    def test_success_clears_failure_reason(self, session):
        tp = TaskProvider(session)
        task = add_task(session, 'healed')
        tp.fail_with_reason(task, 'db-error')
        tp.change_status(task, TaskStatus.Success)
        assert tp.by_id(task.id).failure_reason is None


# ------------------------------------------------------- busy-retry (db)
class TestBusyRetry:
    def test_short_lock_window_absorbed(self, session):
        faults.configure_faults(
            {'db.execute': {'action': 'raise', 'exc': 'operational',
                            'after': 1, 'times': 2}})
        res = session.execute('SELECT 7 AS v')
        assert res.fetchone()['v'] == 7

    def test_sustained_lock_still_raises(self, session):
        faults.configure_faults(
            {'db.execute': {'action': 'raise', 'exc': 'operational',
                            'after': 1, 'times': None}})
        with pytest.raises(sqlite3.OperationalError):
            session.execute('SELECT 1')

    def test_worker_metric_flush_survives_lock_window(self, session):
        """The satellite's original symptom: a locked DB during a
        worker-side metric flush surfaced as a task failure."""
        from mlcomp_tpu.telemetry import MetricRecorder
        rec = MetricRecorder(session=session, task=None,
                             component='train', flush_every=10000)
        rec.series('loss', 0.5, step=1)
        faults.configure_faults(
            {'db.execute': {'action': 'raise', 'exc': 'operational',
                            'after': 1, 'times': 2}})
        assert rec.flush() == 1
        assert rec.dropped_count == 0


# ------------------------------------------------- checkpoint satellites
class TestCheckpointCrashSafety:
    def _save(self, tmp_path, state, epoch, best=False):
        from mlcomp_tpu.train.checkpoint import save_checkpoint
        return save_checkpoint(
            str(tmp_path), state,
            {'stage': 's', 'stage_epoch': epoch, 'epoch': epoch,
             'score': 0.1 * epoch}, best=best)

    def test_torn_last_falls_back_to_best(self, tmp_path, caplog):
        import logging
        from mlcomp_tpu.train.checkpoint import restore_checkpoint
        state = {'w': [1.0, 2.0]}
        self._save(tmp_path, state, 0, best=True)
        self._save(tmp_path, {'w': [3.0, 4.0]}, 1)
        # torn last blob (power loss): truncated msgpack
        with open(tmp_path / 'last.msgpack', 'wb') as fh:
            fh.write(b'\x00garbage')
        with caplog.at_level(logging.WARNING,
                             logger='mlcomp_tpu.train.checkpoint'):
            restored, meta = restore_checkpoint(str(tmp_path),
                                                {'w': [0.0, 0.0]})
        assert list(restored['w']) == [1.0, 2.0]   # best survived
        assert meta['epoch'] == 0
        assert any('falling back' in r.message for r in caplog.records)

    def test_torn_last_without_best_still_raises(self, tmp_path):
        from mlcomp_tpu.train.checkpoint import restore_checkpoint
        self._save(tmp_path, {'w': [1.0]}, 0)
        with open(tmp_path / 'last.msgpack', 'wb') as fh:
            fh.write(b'\x00garbage')
        with pytest.raises(Exception):
            restore_checkpoint(str(tmp_path), {'w': [0.0]})

    def test_crash_between_writes_leaves_usable_pair(self, tmp_path):
        """The checkpoint.between_writes fault: new blob + old meta.
        Resume must restore (redoing at most one epoch), not crash."""
        from mlcomp_tpu.train.checkpoint import (
            load_meta, restore_checkpoint, resume_plan,
        )
        self._save(tmp_path, {'w': [1.0]}, 0)

        class Crash(Exception):
            pass

        def boom(**_):
            raise Crash()

        faults.register_handler('checkpoint.between_writes', boom)
        with pytest.raises(Crash):
            self._save(tmp_path, {'w': [2.0]}, 1)
        faults.clear_faults()
        restored, meta = restore_checkpoint(str(tmp_path), {'w': [0.0]})
        assert list(restored['w']) == [2.0]     # the new blob committed
        assert meta['epoch'] == 0               # the meta is one behind
        stages = [{'name': 's', 'epochs': 3}]
        remaining, start_epoch = resume_plan(stages, load_meta(
            str(tmp_path)))
        assert remaining and start_epoch == 1   # epoch redone, not lost

    def test_corrupt_meta_reads_as_fresh_start(self, tmp_path):
        from mlcomp_tpu.train.checkpoint import load_meta
        self._save(tmp_path, {'w': [1.0]}, 0)
        with open(tmp_path / 'last.msgpack.meta.json', 'w') as fh:
            fh.write('{"epoch": ')         # torn sidecar
        assert load_meta(str(tmp_path)) is None


# ------------------------------------------- restart-with-resume API
class TestRestartWithResumeApi:
    def _start(self, session, dag_id):
        from mlcomp_tpu.server.api import api_dag_start
        return api_dag_start({'id': dag_id}, session)

    def _dag(self, session):
        from mlcomp_tpu.db.models import Dag, Project
        from mlcomp_tpu.db.providers import DagProvider, ProjectProvider
        ProjectProvider(session).add(Project(name='p_resume'))
        project = session.query_one(
            'SELECT id FROM project WHERE name=?', ('p_resume',))['id']
        dag = Dag(name='d', project=project, created=now(),
                  config='info: {}')
        DagProvider(session).add(dag)
        return dag.id

    def test_failed_task_no_checkpoint_yet(self, session):
        """A dag that failed before its first checkpoint restarts with
        resume info attached; the worker finding no checkpoint files
        simply starts fresh (restore_checkpoint returns None)."""
        dag_id = self._dag(session)
        tp = TaskProvider(session)
        task = add_task(session, 'never_saved', dag=dag_id)
        task.computer_assigned = 'hostX'
        task.attempt = 2
        task.failure_reason = 'executor-error'
        tp.update(task, ['computer_assigned', 'attempt',
                         'failure_reason'])
        tp.change_status(task, TaskStatus.Failed)
        res = self._start(session, dag_id)
        assert res['restarted'] == [task.id]
        task = tp.by_id(task.id)
        assert task.status == int(TaskStatus.NotRan)
        assert task.queue_id is None and task.pid is None
        assert task.computer_assigned is None
        info = yaml_load(task.additional_info)
        assert info['resume'] == {'master_computer': 'hostX',
                                  'master_task_id': task.id,
                                  'load_last': True}
        # a human restart forgives the automatic-retry budget
        assert (task.attempt or 0) == 0
        assert task.failure_reason is None

    def test_distributed_master_itself_failed(self, session):
        """A Failed distributed master resolves resume to its rank-0
        service child (the checkpoint folder owner), and the stale
        children detach so aggregation can't re-fail the restart."""
        dag_id = self._dag(session)
        tp = TaskProvider(session)
        master = add_task(session, 'master', dag=dag_id)
        children = []
        for rank in (1, 0):
            c = add_task(session, f'master_{rank}', dag=dag_id,
                         type=int(TaskType.Service),
                         additional_info=yaml_dump(
                             {'distr_info': {'process_index': rank}}))
            c.parent = master.id
            c.computer_assigned = f'host{rank}'
            tp.update(c, ['parent', 'computer_assigned'])
            tp.change_status(c, TaskStatus.Failed)
            children.append(c)
        tp.change_status(master, TaskStatus.Failed)
        res = self._start(session, dag_id)
        assert res['restarted'] == [master.id]
        master = tp.by_id(master.id)
        info = yaml_load(master.additional_info)
        rank0 = next(c for c in children
                     if 'process_index\': 0' in repr(
                         yaml_load(c.additional_info)))
        assert info['resume']['master_task_id'] == rank0.id
        assert info['resume']['master_computer'] == 'host0'
        for c in children:
            assert tp.by_id(c.id).parent is None
        # the service children themselves are NOT restarted
        assert all(tp.by_id(c.id).status == int(TaskStatus.Failed)
                   for c in children)

    def test_children_without_rank0_is_an_api_error(self, session):
        from mlcomp_tpu.server.api import ApiError
        dag_id = self._dag(session)
        tp = TaskProvider(session)
        master = add_task(session, 'master', dag=dag_id)
        c = add_task(session, 'master_1', dag=dag_id,
                     type=int(TaskType.Service),
                     additional_info=yaml_dump(
                         {'distr_info': {'process_index': 1}}))
        c.parent = master.id
        tp.update(c, ['parent'])
        tp.change_status(c, TaskStatus.Failed)
        tp.change_status(master, TaskStatus.Failed)
        with pytest.raises(ApiError):
            self._start(session, dag_id)

    def test_stopped_and_skipped_restart_running_does_not(self, session):
        dag_id = self._dag(session)
        tp = TaskProvider(session)
        stopped = add_task(session, 'stopped', dag=dag_id)
        tp.change_status(stopped, TaskStatus.Stopped)
        skipped = add_task(session, 'skipped', dag=dag_id)
        tp.change_status(skipped, TaskStatus.Skipped)
        running = add_task(session, 'running', dag=dag_id)
        tp.change_status(running, TaskStatus.InProgress)
        res = self._start(session, dag_id)
        assert sorted(res['restarted']) == [stopped.id, skipped.id]
        assert tp.by_id(running.id).status == int(TaskStatus.InProgress)


# ---------------------------------------------------------- migration v7
class TestMigrationV7:
    def test_v6_db_upgrades_in_place(self, session, tmp_path):
        """A pre-v7 DB (no retry columns, no redelivered flag) upgrades
        via the guarded ALTERs; legacy rows read attempt=0 /
        redelivered=0, not NULL-crashes."""
        from mlcomp_tpu.db.core import Session
        from mlcomp_tpu.db.migration import migrate
        old = Session(f'sqlite:///{tmp_path}/old.db', key='v6_upgrade')
        try:
            old.execute(
                'CREATE TABLE task ('
                'id INTEGER PRIMARY KEY AUTOINCREMENT, name TEXT, '
                'status INTEGER, executor TEXT)')
            old.execute(
                'CREATE TABLE queue_message ('
                'id INTEGER PRIMARY KEY AUTOINCREMENT, queue TEXT, '
                'payload TEXT, status TEXT, created TEXT, '
                'claimed_at TEXT, claimed_by TEXT, result TEXT)')
            old.execute(
                "INSERT INTO task (name, status, executor) "
                "VALUES ('legacy', 3, 'e')")
            old.execute(
                "INSERT INTO queue_message (queue, payload, status) "
                "VALUES ('q', '{}', 'claimed')")
            old.execute(
                'CREATE TABLE migration_version (version INTEGER)')
            old.execute(
                'INSERT INTO migration_version (version) VALUES (6)')
            migrate(old)
            row = old.query_one('SELECT * FROM task')
            assert row['attempt'] == 0
            assert row['failure_reason'] is None
            msg = old.query_one('SELECT * FROM queue_message')
            assert msg['redelivered'] == 0
        finally:
            Session.cleanup('v6_upgrade')


# ------------------------------------------------------- end-to-end chaos
EXECUTOR_SRC = '''\
import json
import os

from mlcomp_tpu.testing.faults import fault_point
from mlcomp_tpu.worker.executors import Executor


@Executor.register
class CrashyTrain(Executor):
    """File-based stand-in for jax_train: one "epoch" = one checkpoint
    commit, with the same train.epoch fault seam."""

    def __init__(self, **kw):
        pass

    def work(self):
        done = 0
        if os.path.exists('ckpt.json'):
            with open('ckpt.json') as fh:
                done = json.load(fh)['epoch']
        for epoch in range(done, 3):
            with open('epochs_run.txt', 'a') as fh:
                fh.write(f'{epoch + 1}\\n')
            with open('ckpt.json', 'w') as fh:
                json.dump({'epoch': epoch + 1}, fh)
            fault_point('train.epoch', epoch=epoch + 1)
        return {'epochs': 3, 'resumed_from': done}
'''


class TestEndToEndChaos:
    def test_sigkill_reclaim_retry_resume_success(
            self, session, tmp_path, monkeypatch):
        """The acceptance path: a worker is SIGKILL'd mid-epoch (after
        epoch 2's checkpoint commit) → its claimed queue message is
        reclaimed after lease expiry and re-delivered exactly once →
        the still-dead host strands it → the task retries with backoff
        on a DIFFERENT computer, resumes from the last checkpoint (no
        completed epoch repeated), finishes Success — and the retry is
        visible in task.retry telemetry, /metrics and the task-info
        API."""
        import mlcomp_tpu.worker.__main__ as wmain
        from mlcomp_tpu.server.create_dags.standard import dag_standard
        from mlcomp_tpu.utils.logging import create_logger

        # the task subprocess re-imports mlcomp_tpu with the test env
        # vars set — it must not wipe the sandbox this test lives in
        monkeypatch.setenv('MLCOMP_TPU_KEEP_ROOT', '1')
        folder = tmp_path / 'exp'
        folder.mkdir()
        (folder / 'executors.py').write_text(EXECUTOR_SRC)
        config = {
            'info': {'name': 'chaos_dag', 'project': 'p_chaos'},
            'executors': {'train_job': {'type': 'crashy_train'}},
        }
        dag, tasks = dag_standard(session, config,
                                  upload_folder=str(folder))
        task_id = tasks['train_job'][0]
        tp = TaskProvider(session)
        qp = QueueProvider(session)
        add_computer(session, 'host1')
        add_computer(session, 'host2')

        cfg = RecoveryConfig(lease_seconds=30, backoff_base_s=60,
                             max_retries=3)
        sup = SupervisorBuilder(session=session, recovery_config=cfg)
        monkeypatch.setattr(wmain, 'HOSTNAME', 'host1')
        sup.build()
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.Queued)
        first_host = task.computer_assigned
        other_host = 'host2' if first_host == 'host1' else 'host1'
        msg_id = task.queue_id

        # --- the worker claims, spawns the task subprocess, and the
        # whole worker is SIGKILL'd mid-epoch: the child dies at the
        # train.epoch seam (hit 2 = right after epoch 2's checkpoint),
        # the daemon never completes/fails the message, the host agent
        # stops heartbeating
        claim = qp.claim([f'{first_host}_default'], f'{first_host}:0')
        assert claim is not None and claim[0] == msg_id
        env = {**os.environ,
               'MLCOMP_TASK_ID': str(task_id),
               'MLCOMP_FAULTS': json.dumps(
                   {'train.epoch': {'action': 'exit', 'after': 2}})}
        proc = subprocess.run(
            [sys.executable, '-m', 'mlcomp_tpu.worker', 'run-task',
             str(task_id), '--index', '0'], env=env,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 137, proc.stderr[-2000:]
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.InProgress)  # died mid-run
        from mlcomp_tpu import TASK_FOLDER
        run_dir = os.path.join(TASK_FOLDER, str(task_id))
        with open(os.path.join(run_dir, 'epochs_run.txt')) as fh:
            assert fh.read().split() == ['1', '2']

        kill_heartbeat(session, first_host)
        rewind(session, 'queue_message', 'claimed_at', msg_id, 120)
        # the dead run's own heartbeat (last_activity) goes stale past
        # the watchdog stall deadline — the reclaim horizon for
        # InProgress tasks, so a live run mid-compile is never
        # duplicated
        rewind(session, 'task', 'last_activity', task_id, 4000)
        sup.build()
        msg = session.query_one(
            'SELECT * FROM queue_message WHERE id=?', (msg_id,))
        assert msg['status'] == 'pending' and msg['redelivered'] == 1
        assert tp.by_id(task_id).status == int(TaskStatus.Queued)
        assert not qp.reclaim(msg_id)          # re-delivery is spent

        # nobody claims on the dead host: a second lease window later
        # the strand sweep fails message + task for retry elsewhere
        rewind(session, 'queue_message', 'claimed_at', msg_id, 120)
        sup.build()
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.Failed)
        assert task.failure_reason == 'lease-expired'

        sup.build()                            # schedules the backoff
        task = tp.by_id(task_id)
        assert task.next_retry_at is not None
        rewind(session, 'task', 'next_retry_at', task_id, 10)
        sup.build()                            # requeues + re-places
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.Queued)
        assert task.computer_assigned == other_host
        assert task.attempt == 1
        info = yaml_load(task.additional_info)
        assert info['resume']['load_last'] is True
        assert info['retry_exclude'] == [first_host]

        # --- a live worker on the other computer consumes the retry;
        # no faults in its environment (in-process: the SIGKILL leg
        # above already proved the subprocess path, and an in-process
        # consume keeps the chaos suite's wall-clock down)
        monkeypatch.delenv('MLCOMP_FAULTS', raising=False)
        monkeypatch.setattr(wmain, 'HOSTNAME', other_host)
        logger = create_logger(session)
        assert wmain._consume_one(session, qp, logger, 0,
                                  in_process=True)
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.Success), task.result
        assert task.failure_reason is None
        result = yaml_load(task.result)
        assert result['resumed_from'] == 2     # checkpoint-aware resume
        with open(os.path.join(run_dir, 'epochs_run.txt')) as fh:
            # every epoch ran exactly once across both attempts
            assert fh.read().split() == ['1', '2', '3']

        # --- exactly-once delivery accounting: the original message
        # failed after its single re-delivery; the retry got a FRESH
        # message; nothing is left to double-consume
        msgs = session.query(
            'SELECT status FROM queue_message WHERE payload LIKE ?',
            (f'%"task_id": {task_id}%',))
        assert sorted(m['status'] for m in msgs) == ['done', 'failed']
        assert not wmain._consume_one(session, qp, logger, 0,
                                      in_process=True)

        # --- the retry is observable on every surface
        rows = session.query(
            "SELECT * FROM metric WHERE name='task.retry' AND task=?",
            (task_id,))
        assert len(rows) == 1
        from mlcomp_tpu.telemetry.export import (
            parse_openmetrics, render_server_metrics,
        )
        doc = parse_openmetrics(render_server_metrics(session))
        assert any(
            labels.get('reason') == 'lease-expired'
            and str(labels.get('task')) == str(task_id) and value == 1
            for _, labels, value in
            doc['mlcomp_task_retries']['samples'])
        from mlcomp_tpu.server.api import api_task_info
        detail = api_task_info({'id': task_id}, session)
        assert detail['attempt'] == 1
        assert detail['failure_reason'] is None

    def test_permanent_executor_exception_not_retried(
            self, session, tmp_path, monkeypatch):
        """A deterministic executor bug fails for good: classified
        executor-error by the worker, never requeued by the
        supervisor."""
        import mlcomp_tpu.worker.__main__ as wmain
        from mlcomp_tpu.server.create_dags.standard import dag_standard
        from mlcomp_tpu.utils.logging import create_logger

        folder = tmp_path / 'exp'
        folder.mkdir()
        (folder / 'executors.py').write_text(
            'from mlcomp_tpu.worker.executors import Executor\n'
            '@Executor.register\n'
            'class AlwaysBug(Executor):\n'
            '    def __init__(self, **kw):\n'
            '        pass\n'
            '    def work(self):\n'
            '        raise ValueError("deterministic bug")\n')
        config = {
            'info': {'name': 'bug_dag', 'project': 'p_bug'},
            'executors': {'job': {'type': 'always_bug'}},
        }
        dag, tasks = dag_standard(session, config,
                                  upload_folder=str(folder))
        task_id = tasks['job'][0]
        add_computer(session, 'host1')
        monkeypatch.setattr(wmain, 'HOSTNAME', 'host1')
        sup = SupervisorBuilder(
            session=session,
            recovery_config=RecoveryConfig(lease_seconds=30))
        sup.build()
        logger = create_logger(session)
        assert wmain._consume_one(session, QueueProvider(session),
                                  logger, 0, in_process=True)
        tp = TaskProvider(session)
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.Failed)
        assert task.failure_reason == 'executor-error'
        sup.build()
        task = tp.by_id(task_id)
        assert task.status == int(TaskStatus.Failed)   # still Failed
        assert task.next_retry_at is None              # no retry
        assert session.query(
            "SELECT * FROM metric WHERE name='task.retry'") == []

    def test_slow_dispatch_fault_delays_enqueue(self, session):
        import time
        faults.configure_faults(
            {'queue.enqueue': {'action': 'sleep', 'ms': 40,
                               'times': None}})
        qp = QueueProvider(session)
        t0 = time.perf_counter()
        qp.enqueue('q_slow', {'action': 'execute', 'task_id': 1})
        assert time.perf_counter() - t0 >= 0.03
