"""Automatic failure recovery: taxonomy, retry policy, requeue plumbing.

The reference MLComp leaned on Celery redelivery plus a human clicking
"restart" in the UI; this module is the policy half of closing that
loop without the human (the mechanism lives in the supervisor's
``process_recovery`` tick and the queue provider's lease reclaim).
Production trainers treat preemption and transient faults as the
common case — Borg-style preemptible TPU jobs, Ray's task-retry model
— and the posture here is the same: **classify** every task failure,
**retry** the transient ones from the last checkpoint with exponential
backoff, and **give up loudly** (a ``retry-exhausted`` alert) when the
budget is spent.

Failure taxonomy (``Task.failure_reason``):

==============  =========  ==================================================
reason          class      set by
==============  =========  ==================================================
db-error        transient  sqlite ``OperationalError`` / remote-db errors
io-error        transient  ``ConnectionError``/``TimeoutError``/``OSError``
preempted       transient  SIGTERM/SIGKILL of the task subprocess
stall-killed    transient  the watchdog's task-stall kill (supervisor)
worker-lost     transient  dead-pid reaper / worker subprocess vanished /
                           gang-stall host-silence verdict
lease-expired   transient  queue lease reclaim gave up on a dead host
gang-peer-lost  transient  coordinator-join timeout: a peer rank of the
                           gang never showed up (parallel/distributed.py)
gang-aborted    transient  the supervisor's gang-abort sweep killed this
                           surviving rank after a sibling failed
replica-unhealthy transient  the fleet reconciler's health probes gave
                           up on a serving replica (server/fleet.py) —
                           it is killed and respawned elsewhere
sweep-pruned    permanent  the ASHA sweep scheduler's rung verdict
                           (server/sweep.py): the cell lost its rung
                           and was killed to recycle the slot. Never
                           retried — resurrecting a judged loser would
                           burn the very compute the sweep exists to
                           save; the ``sweep_decision`` row is the
                           audit trail
oom             permanent  RESOURCE_EXHAUSTED / device out-of-memory
                           (also host MemoryError): the same shapes
                           OOM again on retry — blind-retrying burns a
                           TPU slot re-deriving the same crash. The
                           flight recorder persists a postmortem
                           bundle at the failure (telemetry/memory.py)
no-accelerator  permanent  a task the supervisor placed on TPU cores came
                           up on the CPU backend (worker/tasks.py
                           ``pin_cores``): the TPU runtime did not
                           start — a host fault to look at, never a run
                           to carry on with on the host
executor-error  permanent  any other executor exception (a bug retries
                           into the same bug — fail fast instead)
==============  =========  ==================================================

``gang-peer-lost`` and ``gang-aborted`` are COLLATERAL reasons: they
say a rank died because its gang did, not why the gang died. The gang
verdict (``aggregate_child_reasons``) therefore prefers a sibling's
root-cause reason over them, so a gang whose rank 1 was preempted
retries as ``preempted`` even though ranks 0/2 carry ``gang-aborted``.

Deterministic OS errors (``FileNotFoundError``, ``PermissionError``,
``IsADirectoryError``, ``NotADirectoryError``) are carved out of the
OSError family: a missing data file does not heal by retrying.

Retries resume, not restart: the requeue attaches the same ``resume``
info as the restart-with-resume API (``server/api.py dag/start``), so
a retried trainer restores ``last.msgpack`` and loses no completed
epochs, and the computer that just failed the task is excluded from
the next placement (softly — a one-computer cluster still places).
"""

import hashlib
import sqlite3

from mlcomp_tpu.db.enums import TaskStatus, TaskType
from mlcomp_tpu.utils.io import yaml_dump, yaml_load

#: reasons the supervisor will automatically retry
TRANSIENT_REASONS = frozenset({
    'db-error', 'io-error', 'preempted', 'stall-killed', 'worker-lost',
    'lease-expired', 'gang-peer-lost', 'gang-aborted',
    'replica-unhealthy',
})

#: transient reasons that describe gang COLLATERAL, not a root cause —
#: the gang verdict prefers any sibling's root-cause reason over these
GANG_COLLATERAL_REASONS = frozenset({'gang-peer-lost', 'gang-aborted'})

#: deterministic OSError subclasses that must NOT classify as transient
_DETERMINISTIC_OS_ERRORS = (FileNotFoundError, PermissionError,
                            IsADirectoryError, NotADirectoryError)

#: error-text markers of device memory exhaustion. XLA surfaces OOM as
#: an XlaRuntimeError whose message leads with the grpc status name
#: (``RESOURCE_EXHAUSTED: Out of memory allocating ...``); the
#: allocator wording varies by backend, the status name does not
_OOM_MARKERS = ('resource_exhausted', 'resource exhausted',
                'out of memory', 'out-of-memory',
                'memory allocation failure')


class GangPeerLost(RuntimeError):
    """A rank of a multi-host gang gave up waiting for its peers at the
    jax coordinator (bounded join timeout, parallel/distributed.py).
    Classified ``gang-peer-lost``: transient collateral — the gang
    verdict retries on the ROOT cause a sibling carries."""


class AcceleratorMissing(RuntimeError):
    """A task placed on TPU cores found no TPU in its process (jax fell
    back to the CPU backend). Classified ``no-accelerator``:
    permanent."""


def is_transient(reason) -> bool:
    return reason in TRANSIENT_REASONS


def aggregate_child_reasons(reasons) -> str:
    """The failure reason a distributed parent (gang) inherits from its
    Failed service children, or None (= never auto-retried).

    - any permanent (or missing) child reason pins the verdict there —
      retrying a gang whose rank hit a deterministic bug re-hits it;
    - all-transient children make the gang retryable, and the verdict
      prefers a ROOT-cause reason (``preempted``, ``worker-lost``, …)
      over gang collateral (``gang-aborted``/``gang-peer-lost``),
      which only says a rank died because its gang did."""
    reasons = list(reasons)
    if not reasons:
        return None
    for reason in reasons:
        if not reason or not is_transient(reason):
            return reason or None   # surface the permanent verdict
    for reason in reasons:
        if reason not in GANG_COLLATERAL_REASONS:
            return reason
    return reasons[0]               # all collateral: any will do


#: error-text markers of the distributed runtime dying under a task —
#: a surviving gang rank whose collective fails because a PEER vanished
#: raises an opaque XlaRuntimeError (RuntimeError subclass) that would
#: otherwise classify executor-error and PIN the whole gang permanent
_GANG_RUNTIME_MARKERS = (
    'gloo', 'coordination service', 'coordination_service', 'collective',
    'all-reduce', 'allreduce', 'all-gather', 'allgather',
    'deadline', 'connection reset', 'connection closed',
    'socket closed', 'broken pipe', 'peer', 'distributed runtime',
    'heartbeat', 'unavailable',
)


def classify_exception(exc, gang: bool = False) -> str:
    """Failure reason for an exception raised by the task pipeline.
    Walks the cause/context chain so a transient root wrapped in a
    framework exception still classifies transient.

    ``gang=True`` (the task is a rank of a multi-host gang) adds one
    carve-out to the executor-error fallback: a RuntimeError whose
    chain reads like the distributed runtime dying (gloo/coordination
    /collective failures, connection resets) classifies
    ``gang-peer-lost`` — a rank's collective failing because its peer
    vanished is collateral the gang retries on the root cause, not a
    deterministic bug in this rank's code.

    ``oom`` outranks the gang carve-out: an OOM inside a collective's
    buffer allocation mentions the collective, but retrying the gang
    at the same shapes OOMs again — the verdict must pin permanent,
    which is why the per-link OOM check runs before the text markers
    accumulate."""
    seen = set()
    cur = exc
    texts = []
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        if isinstance(cur, GangPeerLost):
            return 'gang-peer-lost'
        if isinstance(cur, AcceleratorMissing):
            return 'no-accelerator'
        if isinstance(cur, MemoryError):
            return 'oom'        # host-side exhaustion: same verdict
        if isinstance(cur, RuntimeError):
            text = f'{type(cur).__name__}: {cur}'.lower()
            if any(marker in text for marker in _OOM_MARKERS):
                # XlaRuntimeError('RESOURCE_EXHAUSTED: ...') — the
                # device OOM the flight recorder exists for
                return 'oom'
        if isinstance(cur, sqlite3.Error):
            return 'db-error'
        if isinstance(cur, RuntimeError) and \
                'remote db error' in str(cur):
            return 'db-error'       # RemoteSession surfaces server-side
        if isinstance(cur, _DETERMINISTIC_OS_ERRORS):
            return 'executor-error'
        if isinstance(cur, (ConnectionError, TimeoutError, OSError)):
            return 'io-error'
        if isinstance(cur, RuntimeError):
            # only RuntimeErrors feed the gang carve-out below: the
            # distributed runtime surfaces as XlaRuntimeError (a
            # RuntimeError subclass) — a ValueError mentioning
            # 'deadline' is still a deterministic bug
            texts.append(f'{type(cur).__name__}: {cur}'.lower())
        cur = cur.__cause__ or cur.__context__
    if gang and any(marker in text for text in texts
                    for marker in _GANG_RUNTIME_MARKERS):
        return 'gang-peer-lost'
    return 'executor-error'


def classify_returncode(returncode) -> str:
    """Failure reason for a task subprocess that died with this exit
    status, or None when the code says nothing (the process likely
    classified its own exception before exiting). Covers both the
    ``Popen`` negative-signal convention and the 128+N shell codes."""
    if returncode in (-15, 143):        # SIGTERM: preemption notice
        return 'preempted'
    if returncode in (-9, 137):         # SIGKILL: preempted / OOM-killed
        return 'preempted'
    return None


class RecoveryConfig:
    """Retry-policy knobs; construct with keyword overrides
    (``RecoveryConfig(lease_seconds=5, backoff_base_s=0.1)``)."""

    #: seconds a claimed queue message stays leased to its worker. Must
    #: comfortably exceed the queue-claim → InProgress-mark interval
    #: (subprocess spawn + code download), NOT the task duration — the
    #: lease guards the dispatch, the watchdog guards the run.
    lease_seconds = 60.0
    #: default retry budget for tasks without their own max_retries
    max_retries = 3.0
    #: exponential backoff: base * factor**attempt, capped
    backoff_base_s = 30.0
    backoff_factor = 2.0
    backoff_cap_s = 900.0
    #: jitter fraction added on top of the backoff — deterministic per
    #: (task, attempt), so retries de-sync without wall-clock flakiness
    jitter_frac = 0.2
    #: seconds a rank of a multi-host gang waits at the jax coordinator
    #: before failing fast with ``gang-peer-lost`` instead of hanging
    #: forever on a peer that will never arrive (stamped into
    #: distr_info at fan-out, consumed by parallel/distributed.py)
    join_timeout_s = 300.0

    def __init__(self, **overrides):
        for key, value in overrides.items():
            if not hasattr(type(self), key):
                raise TypeError(f'unknown recovery option {key!r}')
            setattr(self, key, float(value))


def retry_delay_s(attempt: int, config: RecoveryConfig = None,
                  task_id: int = 0) -> float:
    """Backoff before retry number ``attempt + 1``. Exponential with a
    cap, plus deterministic jitter: the hash of (task, attempt) spreads
    a burst of simultaneous failures without ``random`` — the chaos
    suite's no-flakiness requirement applies to the framework too."""
    config = config or RecoveryConfig()
    base = float(config.backoff_base_s) * \
        (float(config.backoff_factor) ** int(attempt))
    base = min(base, float(config.backoff_cap_s))
    digest = hashlib.sha256(
        f'{int(task_id)}:{int(attempt)}'.encode()).hexdigest()[:8]
    jitter = (int(digest, 16) / 0xffffffff) * \
        float(config.jitter_frac) * base
    return base + jitter


# ------------------------------------------------------------- requeue
def find_resume_info(provider, task) -> dict:
    """The ``resume`` blob a requeued task carries — the checkpoint
    master's location (restart-with-resume semantics,
    reference app.py:488-552). For a distributed parent, the rank-0
    service child owns the checkpoint folder; raises ``LookupError``
    when children exist but no rank-0 child is found."""
    children = sorted(provider.children(task.id),
                      key=lambda c: c.id, reverse=True)
    if children:
        for c in children:
            info = yaml_load(c.additional_info) \
                if c.additional_info else {}
            distr = (info or {}).get('distr_info')
            if not distr:
                continue
            if distr.get('process_index', distr.get('rank')) == 0:
                return {'master_computer': c.computer_assigned,
                        'master_task_id': c.id,
                        'load_last': True}
        raise LookupError('master task not found')
    return {'master_computer': task.computer_assigned,
            'master_task_id': task.id,
            'load_last': True}


def detach_service_children(session, task_id: int) -> int:
    """Detach the FINISHED service children of a task about to requeue
    (``parent=NULL``; rows and their telemetry stay). Without this a
    restarted distributed master is re-failed on the very next
    supervisor tick: parent aggregation sees the previous attempt's
    Failed children and flips the fresh NotRan parent straight back to
    Failed. The new dispatch fans out new service tasks."""
    finished = ','.join(str(int(s)) for s in TaskStatus.finished())
    cur = session.execute(
        f'UPDATE task SET parent=NULL WHERE parent=? AND type=? '
        f'AND status IN ({finished})',
        (int(task_id), int(TaskType.Service)))
    return cur.rowcount


def reset_for_requeue(provider, task, resume: dict = None,
                      exclude_computer=None,
                      reset_attempts: bool = False):
    """Reset a finished task back to NotRan for re-dispatch, with the
    ``resume`` info attached so training continues from the last
    checkpoint. Shared by the restart-with-resume API (human restart,
    ``reset_attempts=True``) and the supervisor's automatic retry
    (``exclude_computer`` = the host — or, for a gang, the hostS —
    that just failed it; a gang excluding its dead host re-places on
    the survivors with a reshaped mesh)."""
    info = yaml_load(task.additional_info) \
        if task.additional_info else {}
    info = dict(info or {})
    if resume is not None:
        info['resume'] = resume
    else:
        # no master found THIS attempt: a stale resume blob from an
        # earlier attempt would silently restore an outdated
        # checkpoint — restart from scratch means exactly that
        info.pop('resume', None)
    if exclude_computer:
        if isinstance(exclude_computer, str):
            exclude_computer = [exclude_computer]
        info['retry_exclude'] = sorted(set(exclude_computer))
    else:
        info.pop('retry_exclude', None)
    detach_service_children(provider.session, task.id)
    task.additional_info = yaml_dump(info)
    # requeue is reached only from the supervisor's retry pass (single
    # tick thread, task already terminal) and the restart API, which
    # rejects unfinished tasks before calling in — no live writer races
    # a terminal row's reset
    # preflight: disable=db-naked-transition — see above
    task.status = int(TaskStatus.NotRan)
    task.pid = None
    task.started = None
    task.finished = None
    task.computer_assigned = None
    task.queue_id = None
    task.worker_index = None
    task.docker_assigned = None
    task.next_retry_at = None
    if reset_attempts:
        task.attempt = 0
        task.failure_reason = None
    provider.update(task)


__all__ = ['TRANSIENT_REASONS', 'GANG_COLLATERAL_REASONS',
           'GangPeerLost', 'is_transient', 'aggregate_child_reasons',
           'classify_exception', 'classify_returncode',
           'RecoveryConfig', 'retry_delay_s', 'find_resume_info',
           'detach_service_children', 'reset_for_requeue']
