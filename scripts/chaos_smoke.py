#!/usr/bin/env python
"""CI smoke: the automatic-recovery paths under injected faults, on a
temp sqlite root, without jax and without a TPU.

Each scenario drives the REAL components — QueueProvider leases,
SupervisorBuilder.process_recovery, Session busy-retry, the fault
registry (mlcomp_tpu/testing/faults.py) — with deterministic faults
(hit counters, no wall-clock/random flakiness; lease expiry is
simulated by rewinding the stored timestamps, never by sleeping):

1. lease reclaim: a SIGKILL'd worker's claimed message is re-delivered
   exactly once; a second expiry on a dead queue fails the task with
   ``lease-expired``
2. checkpoint-aware retry: the transiently-Failed task is backoff-
   scheduled, then requeued with ``resume`` info + the failed computer
   excluded, placed on the OTHER computer, and the retry is visible as
   ``task.retry`` telemetry and ``mlcomp_task_retries_total`` on the
   OpenMetrics export
3. permanent failures are NOT retried; an exhausted budget raises the
   ``retry-exhausted`` alert
4. DB-outage window: an injected ``database is locked`` streak shorter
   than the Session's bounded busy-retry is absorbed; a longer outage
   still surfaces
5. claim race: a rival stealing the candidate between SELECT and
   UPDATE (injected at the ``queue.claim`` seam) costs the claimer one
   loop iteration, never a double delivery
6. gang preemption (elastic gang-atomic recovery): a 3-rank gang loses
   rank 1's HOST via the ``host.preempt`` seam (its heartbeat writer
   dies); the gang-stall watchdog rule diagnoses the silence, the
   supervisor fails the silent rank ``worker-lost`` and gang-aborts
   ranks 0/2 in the same tick (``gang-aborted``, messages revoked),
   the gang requeues EXACTLY ONCE as generation 2 — re-placed on the
   two surviving hosts (reshaped world size 2, dead host excluded) —
   and the bump is visible in ``gang.generation`` telemetry and
   ``mlcomp_gang_generations_total`` on /metrics
7. fleet self-healing (serving tier, server/fleet.py + gateway.py): a
   3-replica fleet serves sustained load through the routing gateway;
   one replica subprocess is killed mid-load via the ``replica.crash``
   seam (``when``-filtered — one env var arms all three, kills exactly
   one). The gateway's circuit breaker + hedged retry keep every
   client request a 200 (no failures other than explicit 429 sheds),
   the reconciler's probes classify the corpse ``replica-unhealthy``,
   kill its task and respawn EXACTLY ONCE on a different computer
   (``retry_exclude``), and the respawn is visible in
   ``mlcomp_fleet_respawns_total`` on /metrics; then a ROLLING SWAP to
   a new export version completes under continued load — generation 2
   warms, the router flips, generation 1 drains — with zero failed
   requests and the flip visible in ``mlcomp_fleet_swaps_total`` and
   ``mlcomp_fleet_generation``
8. OOM flight recorder (deep-step observability, telemetry/memory.py):
   an injected ``RESOURCE_EXHAUSTED`` at the train seam classifies
   ``oom`` (permanent — never blind-retried at the same shapes), a
   postmortem bundle (loss/HBM series tail + run snapshot + memory
   attribution) is frozen at the failure, and the scrape
   self-observability families (per-collector
   ``mlcomp_scrape_errors``) stay clean
9. supervisor failover (HA, server/ha.py + db/fencing.py): a LEADER
   supervisor subprocess dispatching a task burst is killed by the
   ``supervisor.dispatch`` seam EXACTLY between the two halves of a
   dispatch (execute message enqueued, task not yet paired to it —
   the torn shape ``exit`` leaves, ``os._exit``, no finally blocks,
   real SIGKILL semantics); the hot standby promotes once the lease
   window lapses (epoch 2), its promotion sweep re-pairs the torn
   dispatch EXACTLY once, the remaining tasks dispatch normally —
   zero lost, zero duplicated execute messages across the whole
   failover — a zombie write replayed at the dead leader's epoch is
   rejected by the store-side fence, and the failover counters
   (``mlcomp_supervisor_epoch``/``_leader``/``_failovers``/
   ``_fenced_writes``) are visible on /metrics
10. sweep prune failover (ASHA scheduling, server/sweep.py): the
   leader is killed at the ``sweep.prune`` seam — the prune VERDICT is
   recorded in ``sweep_decision`` but the cell not yet killed; the
   standby promotes, its repair pass finishes the recorded prune and
   judges the remaining cells, and the decision log shows EXACTLY ONE
   prune per pruned cell across the failover; a zombie verdict at the
   dead leader's epoch is fenced; pruned cells are never auto-retried
   (no attempt consumed, no backoff scheduled); the prune counters
   (``mlcomp_sweep_prunes_total``/``mlcomp_sweep_cells``) are visible
   on /metrics
11. SLO burn-rate alerting + usage-ledger failover (telemetry/slo.py
   + db/providers/usage.py): dispatch latency is degraded past its
   objective and the SLO engine is driven over a simulated hour of
   evaluations — the fast-burn page (``slo-dispatch-p99``, critical)
   opens on the FIRST evaluation window and stays deduped across all
   subsequent ones; after the degradation clears and the burn windows
   drain, the page AUTO-RESOLVES with a ``resolved`` finding; then a
   terminal task is folded into the usage ledger by BOTH sides of a
   leader failover (old leader's tick replayed by the new one) and
   the bill comes out EXACTLY ONCE — one ledger row per (task,
   attempt) across the whole scenario history
12. mixed-workload preemption (multi-tenant scheduling, migration v15
   + server/scheduler.py): a high-class gang trainer and a
   preemptible ASHA sweep fill a 2-host pool to the last core, then a
   high-class serving fleet arrives needing room NOW — the preemption
   engine evicts EXACTLY the checkpointable sweep cells (decision row
   recorded first, exactly once per victim attempt, then the kill),
   never the equal-class gang; the replicas place on the freed cores
   the next tick; the victims requeue EXACTLY ONCE with
   resume-from-checkpoint info through the normal transient-retry
   path; and ``mlcomp_preemptions_total`` plus bounded per-class
   ``mlcomp_queue_max_wait_seconds`` starvation gauges are visible on
   /metrics
"""

import datetime
import json
import os
import sqlite3
import sys
import tempfile

os.environ.setdefault(
    'MLCOMP_TPU_ROOT', tempfile.mkdtemp(prefix='chaos_smoke_'))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # repo root, wherever CI runs from

from mlcomp_tpu.db.core import Session                       # noqa: E402
from mlcomp_tpu.db.enums import TaskStatus                   # noqa: E402
from mlcomp_tpu.db.migration import migrate                  # noqa: E402
from mlcomp_tpu.db.models import Computer, Task              # noqa: E402
from mlcomp_tpu.db.providers import (                        # noqa: E402
    AlertProvider, ComputerProvider, DockerProvider, QueueProvider,
    TaskProvider,
)
from mlcomp_tpu.recovery import RecoveryConfig               # noqa: E402
from mlcomp_tpu.server.supervisor import SupervisorBuilder   # noqa: E402
from mlcomp_tpu.testing.faults import (                      # noqa: E402
    clear_faults, configure_faults, register_handler,
)
from mlcomp_tpu.utils.io import yaml_load                    # noqa: E402
from mlcomp_tpu.utils.misc import now                        # noqa: E402

FAILURES = []


def check(name, ok, detail=''):
    print(('ok   ' if ok else 'FAIL ') + name + (f' — {detail}'
                                                 if detail else ''))
    if not ok:
        FAILURES.append(name)


def add_computer(session, name, heartbeat=True):
    ComputerProvider(session).create_or_update(
        Computer(name=name, cores=8, cpu=16, memory=64, ip='127.0.0.1',
                 can_process_tasks=True), 'name')
    if heartbeat:
        DockerProvider(session).heartbeat(name, 'default')


def rewind(session, table, column, msg_id, seconds):
    """Simulated clock: move a stored timestamp into the past."""
    session.execute(
        f'UPDATE {table} SET {column}=? WHERE id=?',
        (now() - datetime.timedelta(seconds=seconds), msg_id))


def scenario_lease_and_retry(session):
    add_computer(session, 'host_a')
    add_computer(session, 'host_b')
    tp = TaskProvider(session)
    qp = QueueProvider(session)
    task = Task(name='victim', executor='noop', cores=1, cores_max=1,
                status=int(TaskStatus.NotRan), last_activity=now())
    tp.add(task)
    cfg = RecoveryConfig(lease_seconds=30, backoff_base_s=60,
                         max_retries=3)
    sup = SupervisorBuilder(session=session, recovery_config=cfg)
    sup.build()
    task = tp.by_id(task.id)
    check('dispatch queued the task',
          task.status == int(TaskStatus.Queued)
          and task.queue_id is not None)
    first_host = task.computer_assigned

    # the worker claims, then is SIGKILL'd before completing; its host
    # agent dies with it (heartbeat goes stale)
    claim = qp.claim([f'{first_host}_default'], f'{first_host}:0')
    check('worker claimed the dispatch',
          claim is not None and claim[0] == task.queue_id)
    tp.change_status(task, TaskStatus.InProgress)   # worker marked it
    rewind(session, 'queue_message', 'claimed_at', task.queue_id, 120)
    # the dead run's own heartbeat goes stale past the watchdog stall
    # deadline (the reclaim demands dead-docker-heartbeat AND task
    # silence beyond that horizon, so a healthy run mid-compile behind
    # a heartbeat gap is never duplicated)
    rewind(session, 'task', 'last_activity', task.id, 4000)
    session.execute('UPDATE docker SET last_activity=? WHERE computer=?',
                    (now() - datetime.timedelta(seconds=3600),
                     first_host))

    sup.build()
    msg = session.query_one('SELECT * FROM queue_message WHERE id=?',
                            (task.queue_id,))
    task = tp.by_id(task.id)
    check('expired lease reclaimed to pending',
          msg['status'] == 'pending' and msg['redelivered'] == 1,
          f"status={msg['status']}")
    check('task reset to Queued for re-delivery',
          task.status == int(TaskStatus.Queued))

    # nobody claims it (the host stays dead): a second lease window
    # later the strand sweep fails message + task for retry elsewhere
    rewind(session, 'queue_message', 'claimed_at', task.queue_id, 120)
    sup.build()
    msg = session.query_one('SELECT * FROM queue_message WHERE id=?',
                            (task.queue_id,))
    task = tp.by_id(task.id)
    check('stranded re-delivery failed exactly once',
          msg['status'] == 'failed')
    check('task failed as lease-expired',
          task.status == int(TaskStatus.Failed)
          and task.failure_reason == 'lease-expired')

    # the SAME tick scheduled nothing yet; the next tick schedules the
    # backoff, and once the (rewound) deadline passes the task
    # requeues with resume info, excluding the dead computer
    sup.build()
    task = tp.by_id(task.id)
    check('retry scheduled with backoff',
          task.next_retry_at is not None
          and task.status == int(TaskStatus.Failed))
    session.execute('UPDATE task SET next_retry_at=? WHERE id=?',
                    (now() - datetime.timedelta(seconds=1), task.id))
    sup.build()
    task = tp.by_id(task.id)
    info = yaml_load(task.additional_info) or {}
    check('retried task re-dispatched on the live computer',
          task.status == int(TaskStatus.Queued)
          and task.computer_assigned == 'host_b'
          and task.attempt == 1,
          f'assigned={task.computer_assigned} attempt={task.attempt}')
    check('resume info attached for checkpoint restore',
          (info.get('resume') or {}).get('load_last') is True
          and info.get('retry_exclude') == [first_host])

    retry_rows = session.query(
        "SELECT * FROM metric WHERE name='task.retry' AND task=?",
        (task.id,))
    check('task.retry telemetry emitted', len(retry_rows) == 1)
    from mlcomp_tpu.telemetry.export import (
        parse_openmetrics, render_server_metrics,
    )
    doc = parse_openmetrics(render_server_metrics(session))
    samples = doc.get('mlcomp_task_retries', {}).get('samples', [])
    check('mlcomp_task_retries_total on /metrics', any(
        l.get('reason') == 'lease-expired'
        and str(l.get('task')) == str(task.id) and v == 1
        for _, l, v in samples), str(samples))
    return sup


def scenario_permanent_and_exhaustion(session, sup):
    tp = TaskProvider(session)
    perm = Task(name='buggy', executor='noop', cores=1, cores_max=1,
                status=int(TaskStatus.NotRan), last_activity=now())
    tp.add(perm)
    tp.fail_with_reason(perm, 'executor-error')
    spent = Task(name='spent', executor='noop', cores=1, cores_max=1,
                 status=int(TaskStatus.NotRan), last_activity=now(),
                 attempt=3, max_retries=3)
    tp.add(spent)
    tp.fail_with_reason(spent, 'db-error')
    sup.build()
    perm = tp.by_id(perm.id)
    check('permanent failure not retried',
          perm.status == int(TaskStatus.Failed)
          and perm.next_retry_at is None and (perm.attempt or 0) == 0)
    spent = tp.by_id(spent.id)
    alerts = AlertProvider(session).get(status='open',
                                        rule='retry-exhausted')
    check('retry exhaustion raises the watchdog alert',
          spent.status == int(TaskStatus.Failed)
          and any(a.task == spent.id for a in alerts))


def scenario_db_outage(session):
    configure_faults({'db.execute': {'action': 'raise',
                                     'exc': 'operational',
                                     'after': 1, 'times': 2}})
    try:
        row = session.query_one('SELECT 1 AS one')
        check('reads bypass the outage seam', row['one'] == 1)
        res = session.execute('SELECT 2 AS two')
        check('short DB outage absorbed by bounded busy-retry',
              res.fetchone()['two'] == 2)
    finally:
        clear_faults()
    configure_faults({'db.execute': {'action': 'raise',
                                     'exc': 'operational',
                                     'after': 1, 'times': None}})
    try:
        session.execute('SELECT 3')
        check('sustained DB outage still surfaces', False)
    except sqlite3.OperationalError:
        check('sustained DB outage still surfaces', True)
    finally:
        clear_faults()


def scenario_claim_race(session):
    import mlcomp_tpu.db.providers.queue as queue_mod
    qp = QueueProvider(session)
    first = qp.enqueue('race_q', {'action': 'execute', 'task_id': 900})
    second = qp.enqueue('race_q', {'action': 'execute', 'task_id': 901})
    stolen = []

    def rival(msg_id=None, session=None, **_):
        if not stolen:      # steal only the first candidate
            stolen.append(msg_id)
            session.execute(
                "UPDATE queue_message SET status='claimed', "
                "claimed_by='rival', claimed_at=? "
                "WHERE id=? AND status='pending'", (now(), msg_id))

    register_handler('queue.claim', rival)
    was = queue_mod._RETURNING_OK
    queue_mod._RETURNING_OK = False   # the race window lives in the
    try:                              # sqlite<3.35 fallback path
        claim = qp.claim(['race_q'], 'honest:0')
        check('raced claimer falls through to the next message',
              claim is not None and claim[0] == second
              and stolen == [first], f'claim={claim} stolen={stolen}')
        check('no double delivery', qp.claim(['race_q'], 'late:0')
              is None)
    finally:
        queue_mod._RETURNING_OK = was
        clear_faults()


def scenario_gang_preemption(session):
    """A preempted host takes down one rank of a 3-rank gang; the
    supervisor gang-aborts the survivors and requeues the WHOLE gang
    once, reshaped onto the two surviving hosts."""
    from mlcomp_tpu.db.providers import DockerProvider
    # retire the earlier scenarios' hosts: this scenario's re-placement
    # assertion is about WHICH survivors of the gang's own pool win
    session.execute('UPDATE computer SET can_process_tasks=0')
    for host in ('gang_a', 'gang_b', 'gang_c'):
        add_computer(session, host)
    tp = TaskProvider(session)
    qp = QueueProvider(session)
    task = Task(name='gang_train', executor='noop', cores=8,
                cores_max=24, single_node=False,
                additional_info='distr: true\n',
                status=int(TaskStatus.NotRan), last_activity=now())
    tp.add(task)
    cfg = RecoveryConfig(lease_seconds=30, backoff_base_s=0,
                         max_retries=3)
    sup = SupervisorBuilder(session=session, recovery_config=cfg)
    sup.watchdog.config.evaluate_every_s = 0.0   # judge every tick
    sup.build()
    children = tp.children(task.id)
    parent = tp.by_id(task.id)
    check('gang fanned out across 3 hosts as generation 1',
          len(children) == 3 and parent.gang_id == f'g{task.id}'
          and parent.gang_generation == 1
          and all(c.gang_id == parent.gang_id
                  and c.gang_generation == 1 for c in children),
          str(sup.aux.get('not_placed')))
    victim = next(c for c in children
                  if c.computer_assigned == 'gang_b')
    survivors = [c for c in children if c.id != victim.id]
    # ranks 0/2 claim + run; rank 1's host is preempted BEFORE its
    # worker ever claims — the stuck-Queued case that used to pin the
    # coordinator port forever
    for c in survivors:
        qp.claim([f'{c.computer_assigned}_default'],
                 f'{c.computer_assigned}:0')
        tp.change_status(c, TaskStatus.InProgress)

    # host.preempt: gang_b's heartbeat writer dies from here on; the
    # stored heartbeat is rewound past the gang-stall horizon (clocks
    # are never slept on in this suite)
    configure_faults({'host.preempt': {
        'action': 'raise', 'when': {'computer': 'gang_b'},
        'times': None}})
    try:
        try:
            DockerProvider(session).heartbeat('gang_b', 'default')
            check('host.preempt seam fires', False)
        except RuntimeError:
            check('host.preempt seam fires', True)
        horizon = sup.watchdog.config.gang_host_silence_s + 60
        session.execute(
            'UPDATE docker SET last_activity=? WHERE computer=?',
            (now() - datetime.timedelta(seconds=horizon), 'gang_b'))
        rewind(session, 'task', 'last_activity', victim.id, horizon)
        sup.build()
    finally:
        clear_faults()
    victim = tp.by_id(victim.id)
    check('silent rank failed worker-lost by the gang-stall rule',
          victim.status == int(TaskStatus.Failed)
          and victim.failure_reason == 'worker-lost',
          f'{TaskStatus(victim.status).name}/{victim.failure_reason}')
    aborted = [tp.by_id(c.id) for c in survivors]
    check('surviving ranks gang-aborted in the same tick',
          all(a.status == int(TaskStatus.Failed)
              and a.failure_reason == 'gang-aborted' for a in aborted),
          str([(a.id, a.status, a.failure_reason) for a in aborted]))
    parent = tp.by_id(task.id)
    check('gang verdict is the root cause, not the collateral',
          parent.status == int(TaskStatus.Failed)
          and parent.failure_reason == 'worker-lost',
          str(parent.failure_reason))

    # backoff 0: the next ticks schedule + requeue generation 2
    sup.build()
    session.execute('UPDATE task SET next_retry_at=? WHERE id=?',
                    (now() - datetime.timedelta(seconds=1), task.id))
    sup.build()
    parent = tp.by_id(task.id)
    info = yaml_load(parent.additional_info) or {}
    gen2 = tp.children(task.id)
    check('single generation bump, exactly-once requeue',
          parent.gang_generation == 2 and parent.attempt == 1,
          f'gen={parent.gang_generation} attempt={parent.attempt}')
    check('reshaped 2-host re-placement excluding the dead host',
          len(gen2) == 2
          and info.get('retry_exclude') == ['gang_b']
          and all(c.computer_assigned != 'gang_b'
                  and c.gang_generation == 2 for c in gen2)
          and all((yaml_load(c.additional_info) or {})
                  ['distr_info']['process_count'] == 2 for c in gen2),
          str([(c.id, c.computer_assigned) for c in gen2]))
    bumps = session.query(
        "SELECT * FROM metric WHERE name='gang.generation' AND task=?",
        (task.id,))
    check('gang.generation telemetry emitted once', len(bumps) == 1)
    from mlcomp_tpu.telemetry.export import (
        parse_openmetrics, render_server_metrics,
    )
    doc = parse_openmetrics(render_server_metrics(session))
    samples = doc.get('mlcomp_gang_generations', {}).get('samples', [])
    check('mlcomp_gang_generations_total on /metrics', any(
        labels.get('gang') == parent.gang_id
        and labels.get('reason') == 'worker-lost' and value == 1
        for _, labels, value in samples), str(samples))


#: stub replica process: /health answers ok, /predict hits the
#: replica.crash seam (armed via MLCOMP_FAULTS in the environment)
#: then answers — the jax-free stand-in for a ModelServer replica
_STUB_REPLICA = r'''
import json, sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
sys.path.insert(0, sys.argv[2])
from mlcomp_tpu.testing.faults import fault_point
REPLICA = int(sys.argv[1])

class H(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def _send(self, payload):
        blob = json.dumps(payload).encode()
        self.send_response(200)
        self.send_header('Content-Length', str(len(blob)))
        self.end_headers()
        self.wfile.write(blob)

    def do_GET(self):
        self._send({'status': 'ok', 'replica': REPLICA})

    def do_POST(self):
        n = int(self.headers.get('Content-Length', 0))
        self.rfile.read(n)
        fault_point('replica.crash', replica=REPLICA, phase='request')
        self._send({'y': [REPLICA], 'ms': 1.0})

srv = ThreadingHTTPServer(('127.0.0.1', 0), H)
print(srv.server_address[1], flush=True)
srv.serve_forever()
'''


def scenario_fleet_self_healing(session):
    """A 3-replica serving fleet under load loses one replica to
    replica.crash mid-run: the gateway fails over (zero non-429
    failures), the reconciler respawns exactly once on another
    computer, and /metrics shows the respawn."""
    import subprocess
    import time
    import urllib.request
    from mlcomp_tpu import TOKEN
    from mlcomp_tpu.db.enums import TaskType
    from mlcomp_tpu.db.providers import FleetProvider, ReplicaProvider
    from mlcomp_tpu.server.fleet import FleetConfig, create_fleet
    from mlcomp_tpu.server.gateway import FleetGateway

    session.execute('UPDATE computer SET can_process_tasks=0')
    for host in ('fleet_a', 'fleet_b', 'fleet_c', 'fleet_d'):
        add_computer(session, host)
    tp = TaskProvider(session)
    qp = QueueProvider(session)
    rp = ReplicaProvider(session)
    fleet = create_fleet(session, 'chaos', 'stub_model', desired=3,
                         slo_p99_ms=10000.0)
    sup = SupervisorBuilder(
        session=session,
        recovery_config=RecoveryConfig(lease_seconds=3600),
        fleet_config=FleetConfig(probe_interval_s=0.0,
                                 unhealthy_after=2))
    sup.build()
    replicas = rp.of_fleet(fleet.id)
    tasks = [tp.by_id(r.task) for r in replicas]
    check('fleet fanned out 3 replica tasks across hosts',
          len(replicas) == 3
          and len({t.computer_assigned for t in tasks}) == 3,
          str([(t.id, t.computer_assigned) for t in tasks]))

    # "workers" claim the dispatches and bring up stub replica
    # processes; ONE MLCOMP_FAULTS env arms all three, the `when`
    # filter kills exactly replica[0] on its 10th request
    import json as _json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    victim = replicas[0]
    env = dict(os.environ, JAX_PLATFORMS='cpu')     # jax-free child
    env['MLCOMP_FAULTS'] = _json.dumps({'replica.crash': {
        'action': 'exit', 'after': 10,
        'when': {'replica': victim.id}}})
    procs = []
    try:
        for replica, task in zip(replicas, tasks):
            qp.claim([f'{task.computer_assigned}_default'],
                     f'{task.computer_assigned}:0')
            tp.change_status(task, TaskStatus.InProgress)
            proc = subprocess.Popen(
                [sys.executable, '-c', _STUB_REPLICA,
                 str(replica.id), repo],
                env=env, stdout=subprocess.PIPE, text=True)
            port = int(proc.stdout.readline())
            procs.append(proc)
            rp.mark_endpoint(replica.id, task.computer_assigned, port,
                             f'http://127.0.0.1:{port}')
        sup.build()
        check('probes brought all replicas healthy',
              [r.state for r in rp.of_fleet(fleet.id)] == ['healthy'] * 3,
              str([(r.id, r.state) for r in rp.of_fleet(fleet.id)]))

        gateway = FleetGateway(port=0, session=session, refresh_s=0.1,
                               breaker_kw={'failure_threshold': 1,
                                           'cooldown_s': 30.0})
        gateway.start_background()

        def drive(n, codes, tick_every=5):
            for i in range(n):
                req = urllib.request.Request(
                    f'http://127.0.0.1:{gateway.port}/predict/chaos',
                    data=b'{"x": [[1]]}',
                    headers={'Authorization': TOKEN})
                try:
                    with urllib.request.urlopen(req, timeout=10) as r:
                        code = r.status
                        r.read()
                except urllib.error.HTTPError as e:
                    code = e.code
                    e.read()
                codes[code] = codes.get(code, 0) + 1
                if i % tick_every == tick_every - 1:
                    sup.build()     # the 1 Hz tick, compressed
                time.sleep(0.01)

        codes = {}
        try:
            drive(60, codes)
            check('no request failed other than explicit 429 sheds',
                  set(codes) <= {200, 429}, str(codes))
            check('load actually flowed', codes.get(200, 0) >= 40,
                  str(codes))
        finally:
            gateway.flush_telemetry(session)
        for _ in range(3):
            sup.build()             # settle classification + respawn
        rows = rp.of_fleet(fleet.id)
        dead = [r for r in rows if r.id == victim.id]
        check('crashed replica classified dead through the taxonomy',
              dead and dead[0].state == 'dead'
              and dead[0].failure_reason == 'replica-unhealthy',
              str([(r.id, r.state, r.failure_reason) for r in rows]))
        vt = tp.by_id(victim.task)
        check('victim task failed replica-unhealthy',
              vt.status == int(TaskStatus.Failed)
              and vt.failure_reason == 'replica-unhealthy',
              f'{TaskStatus(vt.status).name}/{vt.failure_reason}')
        spawned = [r for r in rows if r.respawned_from == victim.id]
        check('exactly-once respawn', len(spawned) == 1
              and len(rows) == 4, str([(r.id, r.respawned_from)
                                       for r in rows]))
        if spawned:
            nt = tp.by_id(spawned[0].task)
            info = yaml_load(nt.additional_info) or {}
            check('respawn excluded the dead computer',
                  nt.computer_assigned != vt.computer_assigned
                  and info.get('retry_exclude') ==
                  [vt.computer_assigned],
                  f'{nt.computer_assigned} vs {vt.computer_assigned}')
        from mlcomp_tpu.telemetry.export import (
            parse_openmetrics, render_server_metrics,
        )
        doc = parse_openmetrics(render_server_metrics(session))
        respawns = doc.get('mlcomp_fleet_respawns', {}) \
            .get('samples', [])
        check('mlcomp_fleet_respawns_total on /metrics', any(
            labels.get('fleet') == 'chaos'
            and labels.get('reason') == 'replica-unhealthy'
            and value == 1 for _, labels, value in respawns),
            str(respawns))
        states = doc.get('mlcomp_fleet_replicas', {}).get('samples', [])
        check('replica states exported on /metrics', any(
            labels.get('fleet') == 'chaos'
            and labels.get('state') == 'healthy'
            for _, labels, _ in states), str(states))

        # ---- rolling swap under load: generation 2 with a new export
        # version warms, the router flips, generation 1 drains — and
        # every client request through the whole window stays a 200
        from mlcomp_tpu.server.fleet import start_swap
        fp = FleetProvider(session)
        start_swap(session, fp.by_name('chaos'), 'stub_model_v2')
        sup.build()                 # stage generation 2 replica tasks
        gen2 = rp.of_fleet(fleet.id, generation=2)
        check('swap staged desired replicas as generation 2',
              len(gen2) == 3 and fp.by_name('chaos').generation == 1,
              str([(r.id, r.generation) for r in gen2]))
        for replica in gen2:        # "workers" bring generation 2 up
            task = tp.by_id(replica.task)
            qp.claim([f'{task.computer_assigned}_default'],
                     f'{task.computer_assigned}:0')
            tp.change_status(task, TaskStatus.InProgress)
            proc = subprocess.Popen(
                [sys.executable, '-c', _STUB_REPLICA,
                 str(replica.id), repo],
                env=env, stdout=subprocess.PIPE, text=True)
            port = int(proc.stdout.readline())
            procs.append(proc)
            rp.mark_endpoint(replica.id, task.computer_assigned, port,
                             f'http://127.0.0.1:{port}')
        swap_codes = {}
        drive(40, swap_codes, tick_every=4)   # load ACROSS the flip
        time.sleep(0.3)             # let the router refresh past it
        swap_tail = {}
        drive(10, swap_tail, tick_every=5)
        gateway.shutdown()
        fleet_row = fp.by_name('chaos')
        check('rolling swap flipped to generation 2 under load',
              fleet_row.generation == 2
              and fleet_row.model == 'stub_model_v2'
              and fleet_row.status == 'active',
              f'gen={fleet_row.generation} model={fleet_row.model}')
        check('zero failed requests across the swap',
              set(swap_codes) | set(swap_tail) <= {200, 429}
              and swap_tail.get(200, 0) >= 8,
              f'{swap_codes} then {swap_tail}')
        g1 = rp.of_fleet(fleet.id, generation=1)
        check('generation 1 retired through drain',
              all(r.state in ('draining', 'dead') for r in g1
                  if r.url), str([(r.id, r.state) for r in g1]))
        doc = parse_openmetrics(render_server_metrics(session))
        swaps = doc.get('mlcomp_fleet_swaps', {}).get('samples', [])
        gens = doc.get('mlcomp_fleet_generation', {}).get('samples', [])
        check('swap completion + generation visible on /metrics', any(
            labels.get('fleet') == 'chaos'
            and labels.get('outcome') == 'completed'
            for _, labels, _ in swaps) and any(
            labels.get('fleet') == 'chaos' and value == 2
            for _, labels, value in gens),
            f'{swaps} / {gens}')
    finally:
        for proc in procs:
            try:
                proc.kill()
            except OSError:
                pass


def scenario_oom_flight_recorder(session, sup):
    """OOM flight recorder (ISSUE 12 acceptance, jax-free half): a
    task with live telemetry dies on an injected RESOURCE_EXHAUSTED at
    the train seam → the taxonomy verdict is ``oom`` (permanent — the
    supervisor never blind-retries the same shapes), and a postmortem
    bundle (loss/HBM tail + run snapshot + memory attribution) is
    frozen in the ``postmortem`` table and visible on the OpenMetrics
    export's HBM family. The jax end-to-end twin (real train loop,
    CLI + API retrieval) lives in tests/test_postmortem.py."""
    from mlcomp_tpu.db.providers import MetricProvider
    from mlcomp_tpu.recovery import classify_exception
    from mlcomp_tpu.telemetry import load_postmortem
    from mlcomp_tpu.telemetry.export import (
        parse_openmetrics, render_server_metrics,
    )
    from mlcomp_tpu.testing.faults import fault_point
    tp = TaskProvider(session)
    task = Task(name='oom_victim', executor='jax_train', cores=1,
                cores_max=1, status=int(TaskStatus.InProgress),
                computer_assigned='host_a', last_activity=now())
    tp.add(task)
    ts = now()
    MetricProvider(session).add_many(
        [(task.id, 'loss', 'series', i, 2.0 - i * 0.01, ts, 'train',
          None) for i in range(30)]
        + [(task.id, 'device0.hbm_used', 'series', i,
            1.0e10 + i * 2e8, ts, 'train', None) for i in range(30)]
        + [(task.id, 'device0.hbm_limit', 'series', i, 1.6e10, ts,
            'train', None) for i in range(30)]
        + [(task.id, 'memory.attribution', 'gauge', None, 1.5e10, ts,
            'train', json.dumps({'argument_bytes': 6e9,
                                 'temp_bytes': 9e9}))]
        + [(task.id, 'run.snapshot', 'gauge', None, 0.0, ts, 'train',
            json.dumps({'model': 'transformer_lm',
                        'mesh': {'dp': 8}, 'batch_size': 8}))])
    configure_faults({'train.epoch': {'action': 'raise',
                                      'exc': 'resource', 'after': 1}})
    try:
        try:
            fault_point('train.epoch', epoch=1, task=task.id)
            check('injected RESOURCE_EXHAUSTED fires', False)
        except RuntimeError as e:
            reason = classify_exception(e)
            check('RESOURCE_EXHAUSTED classifies as oom',
                  reason == 'oom', reason)
            tp.fail_with_reason(task, reason)
    finally:
        clear_faults()
    sup.build()
    task = tp.by_id(task.id)
    check('oom is permanent: never auto-retried',
          task.status == int(TaskStatus.Failed)
          and task.failure_reason == 'oom'
          and task.next_retry_at is None and (task.attempt or 0) == 0)
    bundle = load_postmortem(session, task.id)
    check('postmortem bundle frozen at death',
          bundle is not None and bundle['reason'] == 'oom'
          and len(bundle['series'].get('loss', [])) == 30
          and 'device0.hbm_used' in bundle['series']
          and bundle['context'].get('memory.attribution') is not None
          and (bundle['context'].get('run.snapshot') or {}).get(
              'tags', {}).get('model') == 'transformer_lm',
          str(bundle and sorted(bundle['series'])))
    doc = parse_openmetrics(render_server_metrics(session))
    errors = doc.get('mlcomp_scrape_errors', {}).get('samples', [])
    check('scrape errors labeled per collector and all zero',
          len(errors) >= 15 and all(v == 0 for _, _, v in errors)
          and all(labels.get('collector') for _, labels, _ in errors),
          str(errors[:3]))


#: leader-supervisor subprocess for the failover scenario: acquires
#: the lease, then dispatches the seeded burst — and dies at the
#: supervisor.dispatch seam (armed via MLCOMP_FAULTS in its env)
#: between the enqueue and the pairing write, the torn half-dispatch
#: the new leader's promotion sweep must repair
_LEADER_DRIVER = r'''
import sys
sys.path.insert(0, sys.argv[1])
from mlcomp_tpu.db.core import Session
from mlcomp_tpu.server.ha import LeaderLease
from mlcomp_tpu.server.supervisor import SupervisorBuilder
session = Session.create_session(key='chaos_leader')
lease = LeaderLease(session, holder='chaos:leader:aaa',
                    lease_seconds=30.0)
assert lease.ensure(), 'leader subprocess failed to acquire'
print('LEADING', lease.epoch, flush=True)
sup = SupervisorBuilder(session=session, lease=lease)
sup.build()     # dies at the armed supervisor.dispatch hit (os._exit)
print('SURVIVED', flush=True)     # reaching here fails the scenario
'''


def scenario_supervisor_failover(session):
    """SIGKILL the leader mid-dispatch; the standby must take over
    within the lease window with exactly-once dispatch accounting."""
    import json as _json
    import subprocess
    from mlcomp_tpu.db.fencing import FencedSession, FenceLostError
    from mlcomp_tpu.server.ha import LeaderLease, StaticLease
    from mlcomp_tpu.server.supervisor import (
        SupervisorBuilder, SupervisorLoop,
    )

    session.execute('UPDATE computer SET can_process_tasks=0')
    # retire scenario 7's fleet: its reconciler runs BEFORE load_tasks
    # in every tick, and a live desired-count would mint replica tasks
    # that consume this scenario's deterministic dispatch-seam hits
    session.execute(
        "UPDATE serve_fleet SET status='stopped', desired=0")
    for host in ('ha_a', 'ha_b', 'ha_c'):
        add_computer(session, host)
    tp = TaskProvider(session)
    n_tasks, kill_at = 20, 8
    tasks = []
    for i in range(n_tasks):
        task = Task(name=f'ha_{i}', executor='noop', cores=1,
                    cores_max=1, status=int(TaskStatus.NotRan),
                    last_activity=now())
        tp.add(task)
        tasks.append(task)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS='cpu')     # jax-free child
    env['MLCOMP_FAULTS'] = _json.dumps({'supervisor.dispatch': {
        'action': 'exit', 'after': kill_at}})
    proc = subprocess.run(
        [sys.executable, '-c', _LEADER_DRIVER, repo],
        env=env, capture_output=True, text=True, timeout=120)
    check('leader subprocess died mid-dispatch (not SURVIVED)',
          'LEADING 1' in proc.stdout
          and 'SURVIVED' not in proc.stdout
          and proc.returncode == 137,
          f'rc={proc.returncode} out={proc.stdout!r} '
          f'err={proc.stderr[-300:]!r}')
    torn = session.query(
        "SELECT COUNT(*) AS n FROM queue_message "
        "WHERE status='pending' AND queue LIKE 'ha\\_%' ESCAPE '\\'"
        )[0]['n']
    queued = sum(1 for t in tp.by_status(TaskStatus.Queued)
                 if t.name.startswith('ha_'))
    check('dead leader left exactly one torn half-dispatch',
          torn == kill_at and queued == kill_at - 1,
          f'pending={torn} queued={queued}')

    # the hot standby: its gate refuses while the lease is live, then
    # promotes once the window lapses (rewound — never slept on)
    standby = LeaderLease(session, holder='chaos:standby:bbb',
                          lease_seconds=30.0)
    sup2 = SupervisorBuilder(session=session, lease=standby)
    loop = SupervisorLoop(sup2, interval=0.05, lease=standby)
    loop._stop_evt.set()        # gate runs inline; never parks
    check('standby holds back while the leader lease is live',
          loop._ha_gate() is False and standby.epoch is None)
    rewind(session, 'supervisor_lease', 'expires_at', 1, 3600)
    check('standby promotes within the lease window',
          loop._ha_gate() is True and standby.epoch == 2,
          f'epoch={standby.epoch}')
    adopted = (sup2.aux.get('dispatch_reconciled') or {}).get(
        'adopted') or []
    check('promotion sweep re-paired the torn dispatch exactly once',
          len(adopted) == 1, str(sup2.aux.get('dispatch_reconciled')))

    # a zombie write replayed at the dead leader's epoch: fenced
    victim = tp.by_id(tasks[0].id)
    zombie = FencedSession(session, StaticLease(1))
    try:
        TaskProvider(zombie).fail_with_reason(victim, 'worker-lost')
        check('zombie ex-leader write rejected by the fence', False)
    except FenceLostError:
        fresh = tp.by_id(victim.id)
        check('zombie ex-leader write rejected by the fence',
              fresh.status == int(TaskStatus.Queued)
              and fresh.failure_reason is None,
              f'{TaskStatus(fresh.status).name}/{fresh.failure_reason}')

    # the new leader finishes the burst: exactly-once accounting
    sup2.build()
    sup2.telemetry.flush()      # persist the fenced-write delta
    by_status = {}
    for task in [tp.by_id(t.id) for t in tasks]:
        by_status[task.status] = by_status.get(task.status, 0) + 1
    check('every task dispatched after failover',
          by_status == {int(TaskStatus.Queued): n_tasks},
          str(by_status))
    dup = session.query(
        "SELECT payload, COUNT(*) AS n FROM queue_message "
        "WHERE queue LIKE 'ha\\_%' ESCAPE '\\' "
        "GROUP BY payload HAVING COUNT(*) > 1")
    per_task = session.query(
        "SELECT COUNT(*) AS n FROM queue_message WHERE "
        "status IN ('pending', 'claimed') "
        "AND queue LIKE 'ha\\_%' ESCAPE '\\'")
    check('zero lost and zero duplicated dispatches',
          not dup and per_task[0]['n'] == n_tasks,
          f'dups={[(r["payload"], r["n"]) for r in dup]} '
          f'live={per_task[0]["n"]}')

    from mlcomp_tpu.telemetry.export import (
        parse_openmetrics, render_server_metrics,
    )
    doc = parse_openmetrics(render_server_metrics(session))
    leader = doc.get('mlcomp_supervisor_leader', {}).get('samples', [])
    epoch = doc.get('mlcomp_supervisor_epoch', {}).get('samples', [])
    failovers = doc.get('mlcomp_supervisor_failovers', {}) \
        .get('samples', [])
    fenced = doc.get('mlcomp_supervisor_fenced_writes', {}) \
        .get('samples', [])
    check('failover visible on /metrics (leader/epoch/counters)',
          any(labels.get('holder') == 'chaos:standby:bbb'
              for _, labels, _ in leader)
          and any(v == 2 for _, _, v in epoch)
          and any(v >= 1 for _, _, v in failovers)
          and any(v >= 1 for _, _, v in fenced),
          f'leader={leader} epoch={epoch} failovers={failovers} '
          f'fenced={fenced}')


def scenario_sweep_prune_failover(session):
    """Kill the leader MID-PRUNE (verdict recorded, kill not yet
    applied — the ``sweep.prune`` seam sits exactly between the two);
    the standby must promote, FINISH the recorded prune, judge the
    remaining cells, and the decision log must show exactly one prune
    per pruned cell across the whole failover. A zombie verdict
    replayed at the dead leader's epoch is rejected by the fence, and
    a pruned cell is never auto-retried."""
    import json as _json
    import subprocess
    from mlcomp_tpu.contrib.search.asha import report_sweep_score
    from mlcomp_tpu.db.fencing import FencedSession, FenceLostError
    from mlcomp_tpu.db.models import Dag, Sweep
    from mlcomp_tpu.db.providers import (
        DagProvider, ProjectProvider, SweepDecisionProvider,
        SweepProvider,
    )
    from mlcomp_tpu.server.ha import LeaderLease, StaticLease
    from mlcomp_tpu.server.supervisor import SupervisorBuilder

    # scenario 9 left its standby holding the lease for 30 s — expire
    # it (simulated clock, never a sleep) so this scenario's leader
    # can acquire
    rewind(session, 'supervisor_lease', 'expires_at', 1, 3600)
    project = ProjectProvider(session).add_project('chaos_sweep')
    dag = Dag(name='chaos_sweep', project=project.id, config='{}',
              created=now())
    DagProvider(session).add(dag)
    sweep = Sweep(dag=dag.id, executor='sweep_cells',
                  name='chaos_sweep/cells', metric='score', mode='max',
                  eta=2.0, rung_base=1, unit='epochs',
                  min_cells_per_rung=2, cells=4, status='active',
                  created=now())
    SweepProvider(session).add(sweep)
    tp = TaskProvider(session)
    cells = []
    for i, score in enumerate((0.9, 0.8, 0.2, 0.1)):
        cell = Task(name=f'sweep_cell_{i}', executor='sweep_cells',
                    dag=dag.id, status=int(TaskStatus.InProgress),
                    computer_assigned='ha_a', last_activity=now())
        tp.add(cell)
        report_sweep_score(session, cell.id, 1, score)
        cells.append(cell)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS='cpu')     # jax-free child
    env['MLCOMP_FAULTS'] = _json.dumps({'sweep.prune': {
        'action': 'exit', 'after': 1}})
    proc = subprocess.run(
        [sys.executable, '-c', _LEADER_DRIVER, repo],
        env=env, capture_output=True, text=True, timeout=120)
    check('leader subprocess died mid-prune (not SURVIVED)',
          'LEADING' in proc.stdout and 'SURVIVED' not in proc.stdout
          and proc.returncode == 137,
          f'rc={proc.returncode} out={proc.stdout!r} '
          f'err={proc.stderr[-300:]!r}')
    dp = SweepDecisionProvider(session)
    decisions = dp.for_sweep(sweep.id)
    prunes = [d for d in decisions if d.verdict == 'prune']
    victim = tp.by_id(prunes[0].task) if prunes else None
    check('dead leader left a recorded-but-unapplied prune',
          len(prunes) == 1 and victim is not None
          and victim.status == int(TaskStatus.InProgress),
          f'prunes={[(d.task, d.rung) for d in prunes]} '
          f'victim={victim and TaskStatus(victim.status).name}')
    dead_epoch = int(prunes[0].epoch) if prunes else 0

    # the hot standby: expire the dead leader's lease, promote, tick —
    # the repair pass must FINISH the recorded prune and the judge
    # pass must handle the remaining cell, all exactly once
    rewind(session, 'supervisor_lease', 'expires_at', 1, 3600)
    standby = LeaderLease(session, holder='chaos:sweep-standby:ccc',
                          lease_seconds=30.0)
    sup2 = SupervisorBuilder(session=session, lease=standby)
    check('standby promotes past the dead leader',
          standby.ensure() and standby.epoch == dead_epoch + 1,
          f'epoch={standby.epoch} vs leader {dead_epoch}')
    sup2.build()
    rows = [tp.by_id(c.id) for c in cells]
    check('both losers pruned, winners untouched, across the failover',
          [r.failure_reason for r in rows] ==
          [None, None, 'sweep-pruned', 'sweep-pruned']
          and rows[0].status == int(TaskStatus.InProgress)
          and rows[2].status == int(TaskStatus.Failed),
          str([(r.status, r.failure_reason) for r in rows]))
    dup = session.query(
        'SELECT task, COUNT(*) AS n FROM sweep_decision WHERE sweep=? '
        "AND verdict='prune' GROUP BY task HAVING COUNT(*) > 1",
        (sweep.id,))
    decisions = dp.for_sweep(sweep.id)
    check('decision log: exactly one prune per pruned cell',
          not dup and sorted(
              d.task for d in decisions if d.verdict == 'prune') ==
          [cells[2].id, cells[3].id],
          f'dup={[(r["task"], r["n"]) for r in dup]} '
          f'decisions={[(d.task, d.verdict) for d in decisions]}')

    # a zombie verdict replayed at the dead leader's epoch: fenced.
    # A FRESH rung (no existing row) isolates the FENCE as the thing
    # rejecting the insert — a rung with an existing decision would
    # zero out on the once-guard before the fence is even consulted
    zombie = SweepDecisionProvider(
        FencedSession(session, StaticLease(dead_epoch)))
    try:
        zombie.record(sweep.id, cells[0].id, 7, 'prune', 0.0, 1.0,
                      4, dead_epoch)
        check('zombie prune verdict rejected by the fence', False)
    except FenceLostError:
        check('zombie prune verdict rejected by the fence',
              (cells[0].id, 7) not in dp.decided(sweep.id))

    # pruned cells are exempt from the retry pass: another tick (and
    # an explicit recovery pass) must leave them Failed, budget
    # untouched, no backoff ever scheduled
    sup2.build()
    rows = [tp.by_id(c.id) for c in (cells[2], cells[3])]
    check('sweep-pruned is never auto-retried',
          all(r.status == int(TaskStatus.Failed)
              and (r.attempt or 0) == 0 and r.next_retry_at is None
              for r in rows),
          str([(r.status, r.attempt, r.next_retry_at) for r in rows]))

    from mlcomp_tpu.telemetry.export import (
        parse_openmetrics, render_server_metrics,
    )
    doc = parse_openmetrics(render_server_metrics(session))
    prunes_fam = doc.get('mlcomp_sweep_prunes', {}).get('samples', [])
    cells_fam = doc.get('mlcomp_sweep_cells', {}).get('samples', [])
    check('prunes and pruned cells visible on /metrics',
          any(labels.get('sweep') == 'chaos_sweep/cells'
              and labels.get('rung') == '0' and v == 2
              for _, labels, v in prunes_fam)
          and any(labels.get('sweep') == 'chaos_sweep/cells'
                  and labels.get('state') == 'pruned' and v == 2
                  for _, labels, v in cells_fam),
          f'prunes={prunes_fam} cells={cells_fam}')


def scenario_slo_burn_and_usage_fold(session):
    """Degrade dispatch latency past its objective and drive the SLO
    engine over a simulated hour: the fast-burn page must open within
    one evaluation window, dedup across the rest, and auto-resolve
    once the degradation clears and the windows drain. Then both
    sides of a leader failover fold the same terminal task into the
    usage ledger — the bill must come out exactly once."""
    from mlcomp_tpu.db.providers import MetricProvider, UsageProvider
    from mlcomp_tpu.telemetry.slo import SloEngine

    mp = MetricProvider(session)
    engine = SloEngine(session)
    ap = AlertProvider(session)
    t0 = now()

    # the fault: dispatch p99 pinned at 9 s (objective: 5 s) across an
    # hour of 60 s-cadence evaluations — every one measures bad=1.0.
    # The clock is simulated via now_dt; nothing sleeps.
    first = None
    for age in range(3600, -1, -60):
        t = t0 - datetime.timedelta(seconds=age)
        mp.add_many([(None, 'supervisor.dispatch_latency_s.p99',
                      'histogram', None, 9.0, t, 'supervisor', None)])
        findings = engine.evaluate(now_dt=t)
        if first is None:
            first = [f for f in findings
                     if f['rule'] == 'slo-dispatch-p99']
    check('fast-burn page opened within one evaluation window',
          first and first[0]['severity'] == 'critical'
          and first[0]['burn'] >= 14.4, str(first))
    open_slo = ap.get(status='open', rule='slo-dispatch-p99')
    check('page deduped across 61 evaluations',
          len(open_slo) == 1
          and open_slo[0].severity == 'critical',
          f'open={len(open_slo)}')

    # the fault clears; 7 h later every burn window holds only healthy
    # samples — the page must resolve on its own, no human in the loop
    t1 = t0 + datetime.timedelta(hours=7)
    resolved = []
    for age in (120, 60, 0):
        t = t1 - datetime.timedelta(seconds=age)
        mp.add_many([(None, 'supervisor.dispatch_latency_s.p99',
                      'histogram', None, 0.4, t, 'supervisor', None)])
        resolved += [f for f in engine.evaluate(now_dt=t)
                     if f['rule'] == 'slo-dispatch-p99']
    check('page auto-resolved after the degradation cleared',
          any(f['severity'] == 'resolved' for f in resolved)
          and not ap.get(status='open', rule='slo-dispatch-p99'),
          str(resolved))

    # usage across a failover: the old leader folds the terminal
    # attempt, dies, and the new leader's first tick replays the fold
    # — the conditional insert (UNIQUE(task, attempt) backstop) must
    # bill exactly once
    finished = now()
    task = Task(name='chaos_billed', executor='noop',
                status=int(TaskStatus.Success), owner='chaos',
                project='chaos_proj', cores_assigned='[0, 1]',
                started=finished - datetime.timedelta(seconds=30),
                finished=finished, last_activity=now())
    TaskProvider(session).add(task)
    old_leader = SupervisorBuilder(session=session)
    new_leader = SupervisorBuilder(session=session)
    old_leader.process_usage()
    new_leader.process_usage()    # the replayed fold after promotion
    n = session.query('SELECT COUNT(*) AS n FROM usage WHERE task=?',
                      (task.id,))[0]['n']
    billed = session.query(
        'SELECT owner, project, core_seconds FROM usage WHERE task=?',
        (task.id,))[0]
    check('usage folded exactly once across the failover',
          n == 1 and billed['owner'] == 'chaos'
          and 58.0 <= billed['core_seconds'] <= 62.0,
          f'rows={n} billed={dict(billed)}')
    dup = session.query(
        'SELECT task, attempt, COUNT(*) AS n FROM usage '
        'GROUP BY task, attempt HAVING COUNT(*) > 1')
    check('ledger holds one row per (task, attempt) across every '
          'scenario', not dup,
          str([(r['task'], r['n']) for r in dup]))


def scenario_mixed_workload_preemption(session):
    """Mixed workload on one 2-host pool: a high-class gang trainer
    (12 of 16 cores) plus a preemptible 4-cell ASHA sweep fill it
    completely; a high-class serving fleet then needs 4 cores NOW.
    The engine must evict exactly the 4 sweep cells — cheapest first,
    decision row before the kill, one row per victim attempt — leave
    the equal-class gang alone, place the replicas on the freed cores
    next tick, and requeue the victims exactly once with resume info
    through the normal transient-retry path."""
    from mlcomp_tpu.db.models import Dag, Sweep
    from mlcomp_tpu.db.providers import (
        DagProvider, ProjectProvider, ReplicaProvider, SweepProvider,
    )
    from mlcomp_tpu.server.fleet import FleetConfig, create_fleet

    # retire earlier scenarios' hosts, fleets and sweeps: this
    # scenario's eviction arithmetic is about ITS OWN 16-core pool
    session.execute('UPDATE computer SET can_process_tasks=0')
    session.execute(
        "UPDATE serve_fleet SET status='stopped', desired=0")
    session.execute("UPDATE sweep SET status='stopped'")
    add_computer(session, 'mix_a')
    add_computer(session, 'mix_b')
    tp = TaskProvider(session)
    qp = QueueProvider(session)
    cfg = RecoveryConfig(lease_seconds=3600, backoff_base_s=0,
                         max_retries=3)
    sup = SupervisorBuilder(
        session=session, recovery_config=cfg,
        fleet_config=FleetConfig(probe_interval_s=3600.0))

    # the gang trainer: explicitly high-class — it holds most of the
    # pool and must NOT be what an equal-class replica evicts
    gang = Task(name='mix_gang', executor='noop', cores=12,
                cores_max=12, single_node=False, priority='high',
                additional_info='distr: true\n',
                status=int(TaskStatus.NotRan), last_activity=now())
    tp.add(gang)
    sup.build()
    ranks = tp.children(gang.id)
    check('gang trainer fanned out across both hosts (12 cores)',
          len(ranks) == 2
          and {r.computer_assigned for r in ranks} ==
          {'mix_a', 'mix_b'},
          str(sup.aux.get('not_placed')))
    for r in ranks:
        qp.claim([f'{r.computer_assigned}_default'],
                 f'{r.computer_assigned}:0')
        tp.change_status(r, TaskStatus.InProgress)

    # the ASHA sweep: 4 preemptible cells soak up the last 4 cores
    project = ProjectProvider(session).add_project('chaos_mixed')
    # config empty (not a dict): submit-gate preflight is out of
    # scope here — these cells arrive pre-built, like scenario 10's
    dag = Dag(name='chaos_mixed', project=project.id, config='',
              created=now())
    DagProvider(session).add(dag)
    sweep = Sweep(dag=dag.id, executor='mix_cells',
                  name='chaos_mixed/cells', metric='score', mode='max',
                  eta=2.0, rung_base=1, unit='epochs',
                  min_cells_per_rung=2, cells=4, status='active',
                  created=now())
    SweepProvider(session).add(sweep)
    cells = []
    for i in range(4):
        cell = Task(name=f'mix_cell_{i}', executor='mix_cells',
                    dag=dag.id, cores=1, cores_max=1,
                    additional_info=f'sweep: {sweep.id}\n',
                    status=int(TaskStatus.NotRan), last_activity=now())
        tp.add(cell)
        cells.append(cell)
    sup.build()
    cells = [tp.by_id(c.id) for c in cells]
    check('sweep cells filled the pool to the last core',
          all(c.status == int(TaskStatus.Queued) for c in cells),
          str([(c.id, TaskStatus(c.status).name,
                c.computer_assigned) for c in cells]))
    for c in cells:
        qp.claim([f'{c.computer_assigned}_default'],
                 f'{c.computer_assigned}:0')
        tp.change_status(c, TaskStatus.InProgress)

    # the serving fleet arrives on the FULL pool: 2 high-class
    # replicas x 2 cores; its spawn tick is the contention tick
    fleet = create_fleet(session, 'mix_fleet', 'stub_model',
                         desired=2, cores=2)
    sup.build()
    decisions = session.query('SELECT * FROM preemption ORDER BY id')
    cell_ids = sorted(c.id for c in cells)
    check('exactly one applied decision row per evicted cell',
          sorted(d['task'] for d in decisions) == cell_ids
          and all(d['applied'] == 1 and d['attempt'] == 0
                  and d['victim_class'] == 'preemptible'
                  and d['reason'] == 'capacity' for d in decisions),
          str([(d['task'], d['attempt'], d['applied'],
                d['victim_class'], d['reason']) for d in decisions]))
    cells = [tp.by_id(c.id) for c in cells]
    check('victims failed with the transient preempted reason',
          all(c.status == int(TaskStatus.Failed)
              and c.failure_reason == 'preempted' for c in cells),
          str([(c.id, c.failure_reason) for c in cells]))
    gang_rows = [tp.by_id(gang.id)] + \
        [tp.by_id(r.id) for r in ranks]
    check('equal-class gang trainer untouched by the eviction',
          all(g.status != int(TaskStatus.Failed)
              and g.failure_reason is None for g in gang_rows),
          str([(g.id, g.status, g.failure_reason)
               for g in gang_rows]))

    # next tick: the freed cores place both replicas
    sup.build()
    replicas = ReplicaProvider(session).of_fleet(fleet.id)
    rtasks = [tp.by_id(r.task) for r in replicas]
    check('replicas placed on the freed cores within one tick',
          len(rtasks) == 2
          and all(t.status == int(TaskStatus.Queued)
                  and t.computer_assigned == 'mix_b' for t in rtasks),
          str([(t.id, t.status, t.computer_assigned)
               for t in rtasks]))

    # the victims ride the normal retry path: backoff scheduled, then
    # (deadline rewound — never slept on) requeued with resume info,
    # EXACTLY once — attempt 1, one decision row per cell, forever
    for c in cells:
        session.execute(
            'UPDATE task SET next_retry_at=? WHERE id=?',
            (now() - datetime.timedelta(seconds=1), c.id))
    sup.build()
    cells = [tp.by_id(c.id) for c in cells]
    check('preempted cells requeued exactly once with resume info',
          all((c.attempt or 0) == 1
              and (yaml_load(c.additional_info) or {}).get(
                  'resume', {}).get('load_last') is True
              for c in cells),
          str([(c.id, c.attempt, c.additional_info) for c in cells]))
    sup.build()      # an extra tick must not double-preempt/requeue
    n_rows = session.query(
        'SELECT COUNT(*) AS n FROM preemption')[0]['n']
    cells = [tp.by_id(c.id) for c in cells]
    check('no double preemption or double requeue on later ticks',
          n_rows == 4 and all((c.attempt or 0) == 1 for c in cells),
          f'rows={n_rows} '
          f'attempts={[(c.id, c.attempt) for c in cells]}')

    sup.telemetry.flush()
    from mlcomp_tpu.server.scheduler import AGING_STEP_S
    from mlcomp_tpu.telemetry.export import (
        parse_openmetrics, render_server_metrics,
    )
    doc = parse_openmetrics(render_server_metrics(session))
    pre = doc.get('mlcomp_preemptions', {}).get('samples', [])
    check('mlcomp_preemptions_total on /metrics', any(
        labels.get('class') == 'preemptible'
        and labels.get('reason') == 'capacity' and v == 4
        for _, labels, v in pre), str(pre))
    waits = doc.get('mlcomp_queue_max_wait_seconds', {}) \
        .get('samples', [])
    bound = 3 * AGING_STEP_S        # the aging anti-starvation bound
    check('per-class max wait bounded below the aging ceiling',
          waits and any(labels.get('class') == 'sweep'
                        for _, labels, _ in waits)
          and all(v < bound for _, _, v in waits),
          str(waits))


def main():
    session = Session.create_session(key='chaos_smoke')
    migrate(session)
    sup = scenario_lease_and_retry(session)
    scenario_permanent_and_exhaustion(session, sup)
    scenario_db_outage(session)
    scenario_claim_race(session)
    scenario_gang_preemption(session)
    scenario_fleet_self_healing(session)
    scenario_oom_flight_recorder(session, sup)
    scenario_supervisor_failover(session)
    scenario_sweep_prune_failover(session)
    scenario_slo_burn_and_usage_fold(session)
    scenario_mixed_workload_preemption(session)
    if FAILURES:
        print(f'FAIL: {len(FAILURES)} scenario check(s): {FAILURES}')
        return 1
    print('OK: all recovery paths verified under injected faults')
    return 0


if __name__ == '__main__':
    sys.exit(main())
