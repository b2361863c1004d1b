"""HBM memory timeline, static peak attribution, and the OOM flight
recorder.

The two failure modes that actually kill large TPU runs are HBM
exhaustion and communication-bound steps (telemetry/collectives.py
owns the second). Before this module the memory story was one coarse
``hbm_used`` gauge per epoch; after it:

- **timeline** — ``MemorySampler`` records ``device<i>.hbm_used`` /
  ``hbm_limit`` / ``hbm_peak`` as per-step series from
  ``device.memory_stats()`` (telemetry/device.py). The hot-path cost
  is one runtime stats call per local device every ``every`` steps
  (no device sync — the stats live in the host-side allocator);
  platforms that report no memory stats (CPU) are detected ONCE at
  construction and every later sample is a no-op, so the dashboard
  never renders empty 0/0 HBM rows for CPU runs. bench.py measures
  the sampler in isolation and publishes
  ``memory_sampler_overhead_pct`` (budget <1% of step time, with a
  bench_guard floor).
- **static attribution** — ``memory_attribution(compiled)`` reads the
  compiled executable's ``memory_analysis()``: peak HBM split into
  arguments / outputs / temporaries / generated code. One row per
  compiled stage (``memory.attribution``, the full split in the tags)
  — the "what would I have to shrink" answer next to the "how close
  am I" timeline.
- **flight recorder** — ``build_postmortem`` assembles, from rows
  already in the DB, the bundle an operator needs AFTER the crash:
  the last ``tail`` steps of the loss / step-time / phase / memory /
  compile series, the run snapshot (mesh, batch shape, model), the
  memory attribution, the collective tally, and the task's open
  alerts. ``TaskProvider.fail_with_reason`` persists it on EVERY
  reasoned failure (``postmortem`` table, migration v10) so the
  bundle is frozen at death — retrievable via
  ``mlcomp_tpu postmortem <task>`` and ``POST /api/task/postmortem``
  however long ago the run died and whatever aged out of the metric
  table since.

The watchdog's upgraded ``hbm-pressure`` rule consumes the timeline:
a least-squares slope over the recent occupancy window predicts
steps-to-OOM and alerts BEFORE the crash (telemetry/watchdog.py).
RESOURCE_EXHAUSTED itself classifies as the ``oom`` taxonomy reason —
permanent, never blind-retried at the same shape
(mlcomp_tpu/recovery.py).
"""

import json

#: series the postmortem bundle tails (prefix match), newest-first in
#: the stored bundle — the signals that explain an OOM or a slow death
POSTMORTEM_SERIES_PREFIXES = (
    'loss', 'step_time_ms', 'step.phase.',
    'step.pipeline_efficiency', 'device', 'compile.backend_ms',
    'comm.', 'mfu', 'host_sync.suspect_ms', 'devtime.',
)

#: single-row context signals carried whole (latest row, tags decoded)
#: — devtime.summary is the newest sampled device-time window
#: (telemetry/deviceprof.py), so an OOM/stall postmortem shows what
#: the device was actually doing
POSTMORTEM_CONTEXT_NAMES = ('run.snapshot', 'memory.attribution',
                            'comm.bytes_per_step', 'devtime.summary')


class MemorySampler:
    """Per-step HBM timeline recorder. Construct once per training
    loop; ``sample(step)`` emits one used/limit/peak triple per local
    device into the recorder's buffer (no device sync, no DB write —
    the recorder flushes on its own cadence).

    The device roster and "does this platform report memory stats at
    all" are resolved at construction: on CPU (no ``memory_stats``)
    ``sample`` degrades to a single attribute check per step, and no
    empty rows ever reach the dashboard. ``every`` thins the timeline
    for very fast steps (the default records every step — the OOM the
    flight recorder explains is usually only a few steps wide)."""

    def __init__(self, recorder, every: int = 1):
        self.recorder = recorder
        self.every = max(1, int(every))
        self.platform = None
        self._devices = []       # [(id, device)] that report stats
        try:
            import sys
            if 'jax' not in sys.modules:
                return           # never init a second jax client
            import jax
            for d in jax.local_devices():
                self.platform = self.platform or d.platform
                try:
                    stats = d.memory_stats() or {}
                except Exception:
                    stats = {}
                if stats.get('bytes_limit'):
                    self._devices.append((d.id, d))
        except Exception:
            self._devices = []

    @property
    def active(self) -> bool:
        return bool(self._devices)

    def sample(self, step: int = None):
        """Record one timeline point. ~one allocator-stats call per
        reporting device; inert on platforms without memory stats."""
        if not self._devices:
            return
        if step is not None and step % self.every:
            return
        rec = self.recorder
        for dev_id, d in self._devices:
            try:
                stats = d.memory_stats() or {}
            except Exception:
                continue
            used = stats.get('bytes_in_use')
            limit = stats.get('bytes_limit')
            if not limit:
                continue
            rec.series(f'device{dev_id}.hbm_used', float(used or 0),
                       step=step)
            rec.series(f'device{dev_id}.hbm_limit', float(limit),
                       step=step)
            peak = stats.get('peak_bytes_in_use')
            if peak:
                rec.series(f'device{dev_id}.hbm_peak', float(peak),
                           step=step)


# ------------------------------------------------------- static peak
def memory_attribution(compiled) -> dict:
    """Static peak attribution of one compiled executable from XLA's
    own ``memory_analysis()``: where the bytes of the high-water mark
    live. ``{}`` when the backend offers no analysis."""
    try:
        analysis = compiled.memory_analysis()
        if analysis is None:
            return {}
        out = {}
        for key, attr in (
                ('argument_bytes', 'argument_size_in_bytes'),
                ('output_bytes', 'output_size_in_bytes'),
                ('temp_bytes', 'temp_size_in_bytes'),
                ('generated_code_bytes', 'generated_code_size_in_bytes'),
                ('alias_bytes', 'alias_size_in_bytes')):
            value = getattr(analysis, attr, None)
            if value is not None:
                out[key] = int(value)
        if out:
            # aliased (donated) buffers overlap arguments — do not
            # double count them in the static peak
            out['total_bytes'] = (
                out.get('argument_bytes', 0)
                + out.get('output_bytes', 0)
                + out.get('temp_bytes', 0)
                + out.get('generated_code_bytes', 0)
                - out.get('alias_bytes', 0))
        return out
    except Exception:
        return {}


def persist_memory_attribution(session, task_id: int,
                               attribution: dict, stage: str = None,
                               component: str = 'train') -> bool:
    """One ``memory.attribution`` row per compiled stage: value is the
    static peak total, the full split rides the tags (the shape the
    postmortem bundle and the dashboard memory card read)."""
    if not attribution:
        return False
    from mlcomp_tpu.db.providers.telemetry import MetricProvider
    from mlcomp_tpu.utils.misc import now
    tags = dict(attribution)
    if stage is not None:
        tags['stage'] = stage
    MetricProvider(session).add_many([(
        task_id, 'memory.attribution', 'gauge', None,
        float(attribution.get('total_bytes', 0)), now(), component,
        json.dumps(tags))])
    return True


def persist_run_snapshot(session, task_id: int, snapshot: dict,
                         component: str = 'train') -> bool:
    """One ``run.snapshot`` row carrying the mesh / sharding / batch
    shape / model identity of the live run — the context half of the
    postmortem bundle (series say WHAT happened, this says on what)."""
    if not snapshot:
        return False
    from mlcomp_tpu.db.providers.telemetry import MetricProvider
    from mlcomp_tpu.utils.misc import now
    MetricProvider(session).add_many([(
        task_id, 'run.snapshot', 'gauge', None, 0.0, now(), component,
        json.dumps(snapshot))])
    return True


# ---------------------------------------------------- flight recorder
def build_postmortem(session, task_id: int, tail: int = 50) -> dict:
    """Assemble the postmortem bundle for one task from rows already
    in the DB (the crash-time flush ran before the failure path marks
    the task, so the series end at the death). Works for failures the
    task's own process never saw (worker-lost, lease-expired): the
    supervisor-side caller has the same DB."""
    from mlcomp_tpu.db.providers.telemetry import (
        AlertProvider, MetricProvider,
    )
    metrics = MetricProvider(session)
    series = {}
    for name in metrics.names(task_id):
        if not any(name == p or name.startswith(p)
                   for p in POSTMORTEM_SERIES_PREFIXES):
            continue
        rows = session.query(
            'SELECT step, value, time FROM metric '
            'WHERE task=? AND name=? ORDER BY id DESC LIMIT ?',
            (int(task_id), name, int(tail)))
        series[name] = [
            {'step': r['step'], 'value': r['value'], 'time': r['time']}
            for r in reversed(rows)]
    context = {}
    for name in POSTMORTEM_CONTEXT_NAMES:
        row = session.query_one(
            'SELECT value, tags FROM metric WHERE task=? AND name=? '
            'ORDER BY id DESC LIMIT 1', (int(task_id), name))
        if row is None:
            continue
        tags = None
        try:
            tags = json.loads(row['tags']) if row['tags'] else None
        except ValueError:
            pass
        context[name] = {'value': row['value'], 'tags': tags}
    alerts = [{'rule': a.rule, 'severity': a.severity,
               'message': a.message, 'time': str(a.time)}
              for a in AlertProvider(session).get(
                  status=None, task=task_id, limit=20)]
    row = session.query_one(
        'SELECT name, status, failure_reason, attempt, '
        'computer_assigned, additional_info FROM task WHERE id=?',
        (int(task_id),))
    task_card = {}
    if row is not None:
        task_card = {'name': row['name'], 'status': row['status'],
                     'failure_reason': row['failure_reason'],
                     'attempt': row['attempt'] or 0,
                     'computer': row['computer_assigned']}
        # the mesh/distr context the supervisor stamped on dispatch —
        # the sharding half of the snapshot for fanned-out ranks
        try:
            from mlcomp_tpu.utils.io import yaml_load
            info = yaml_load(row['additional_info']) \
                if row['additional_info'] else {}
            distr = (info or {}).get('distr_info') or {}
            if distr.get('mesh'):
                task_card['mesh'] = distr['mesh']
            if 'process_index' in distr:
                task_card['rank'] = distr.get('process_index')
        except Exception:
            pass
    return {'task': int(task_id), 'tail': int(tail),
            'task_card': task_card, 'series': series,
            'context': context, 'alerts': alerts}


#: bundles retained per task — retries append, the newest wins, and
#: older ones past this depth are pruned on insert so a flapping task
#: cannot grow the table one multi-KB bundle per failure forever
POSTMORTEM_KEEP_PER_TASK = 5


def persist_postmortem(session, task_id: int, reason: str = None,
                       tail: int = 50):
    """Build + freeze the bundle into the ``postmortem`` table (one
    row per failure event — retries append new rows; consumers read
    the newest, rows past ``POSTMORTEM_KEEP_PER_TASK`` are pruned).
    Never raises: the flight recorder must not break the failure path
    it rides."""
    try:
        from mlcomp_tpu.db.models import Postmortem
        from mlcomp_tpu.db.providers.telemetry import PostmortemProvider
        from mlcomp_tpu.utils.misc import now
        bundle = build_postmortem(session, task_id, tail=tail)
        row = Postmortem(task=int(task_id), created=now(),
                         reason=reason, data=json.dumps(bundle))
        provider = PostmortemProvider(session)
        provider.add(row)
        provider.prune(task_id, keep=POSTMORTEM_KEEP_PER_TASK)
        return row
    except Exception:
        return None


def load_postmortem(session, task_id: int):
    """Newest frozen bundle of a task (decoded dict with ``created``/
    ``reason`` stamps), or None."""
    from mlcomp_tpu.db.providers.telemetry import PostmortemProvider
    row = PostmortemProvider(session).latest(task_id)
    if row is None:
        return None
    try:
        bundle = json.loads(row.data) if row.data else {}
    except ValueError:
        bundle = {}
    bundle['created'] = str(row.created)
    bundle['reason'] = row.reason
    bundle['postmortem_id'] = row.id
    return bundle


__all__ = ['MemorySampler', 'memory_attribution',
           'persist_memory_attribution', 'persist_run_snapshot',
           'build_postmortem', 'persist_postmortem', 'load_postmortem',
           'POSTMORTEM_SERIES_PREFIXES', 'POSTMORTEM_CONTEXT_NAMES']
