"""Per-step metric series: counters, gauges, histograms whose hot-path
cost is a host-side list append.

The rule that makes this usable inside a training loop: **recording
never syncs the device**. ``series('loss', metrics['loss'], step)``
appends the jax array itself; the device→host pull happens at flush
time, once per ``flush_every`` steps, where one batch of ``float()``
conversions and one ``executemany`` amortize across the window. (A
per-scalar pull blocks the host on the device once per value — see
train/loop.py's ``aggregate_metrics``.)

Counters and histograms aggregate in memory and emit summary rows at
flush (``name.count``/``name.p50``/``name.p99``/…), so a serving
process observing every request writes a handful of rows per flush
interval, not one per request.
"""

import itertools
import json
import sys
import threading
import weakref
from collections import deque

import numpy as np

#: session-bound recorders alive in this process — the crash-time flush
#: (worker/tasks.py installs atexit + SIGTERM handlers) drains these so
#: the telemetry of a FAILED task, the rows the watchdog most needs,
#: is not lost with the process. WeakSet: registration must not keep a
#: finished executor's recorder (and its session ref) alive.
_LIVE_RECORDERS = weakref.WeakSet()


def flush_live_recorders() -> int:
    """Best-effort synchronous flush of every live session-bound
    recorder; returns rows written. Never raises — this runs on the
    interpreter's way down."""
    total = 0
    for recorder in list(_LIVE_RECORDERS):
        try:
            total += recorder.flush()
        except Exception:
            pass
    return total


class Histogram:
    """Streaming aggregate + bounded reservoir for percentiles.

    With ``buckets`` (sorted upper bounds), fixed-boundary counts are
    kept alongside — the cumulative ``le`` buckets an OpenMetrics
    scraper wants (telemetry/export.py renders them; a reservoir can
    only approximate quantiles, bucket counts are exact)."""

    __slots__ = ('count', 'total', 'min', 'max', '_reservoir',
                 'bucket_bounds', '_bucket_counts')

    def __init__(self, reservoir: int = 1024, buckets=None):
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._reservoir = deque(maxlen=reservoir)
        self.bucket_bounds = sorted(float(b) for b in buckets) \
            if buckets else None
        # one count per bound plus the implicit +Inf overflow bucket
        self._bucket_counts = [0] * (len(self.bucket_bounds) + 1) \
            if self.bucket_bounds else None

    def observe(self, value: float):
        value = float(value)
        self.count += 1
        self.total += value
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)
        self._reservoir.append(value)
        if self.bucket_bounds is not None:
            import bisect
            self._bucket_counts[
                bisect.bisect_left(self.bucket_bounds, value)] += 1

    def bucket_counts(self):
        """CUMULATIVE ``[(le, count)]`` ending with ``('+Inf', total)``
        — the OpenMetrics histogram convention — or None when this
        histogram was built without buckets."""
        if self._bucket_counts is None:
            return None
        out, running = [], 0
        for bound, n in zip(self.bucket_bounds, self._bucket_counts):
            running += n
            out.append((bound, running))
        out.append(('+Inf', running + self._bucket_counts[-1]))
        return out

    def summary(self) -> dict:
        if not self.count:
            return {}
        window = list(self._reservoir)
        return {
            'count': float(self.count),
            'mean': self.total / self.count,
            'min': self.min, 'max': self.max,
            'p50': float(np.percentile(window, 50)),
            'p95': float(np.percentile(window, 95)),
            'p99': float(np.percentile(window, 99)),
        }


class MetricRecorder:
    """One recorder per (task, component). Bind a session to persist;
    without one it is a pure in-memory buffer (tests, bench).

    Thread safety: every mutation holds ``_mutate_lock`` (an
    uncontended acquire is ~100 ns — noise against the budget), so a
    concurrent flush (serving heartbeat, ``async_flush`` worker) can
    swap the buffers without losing racing samples or crashing the
    snapshot iteration. ``async_flush=True`` moves the auto-flush
    triggered by a full window onto a background daemon thread — the
    instrumented step never blocks on the device pull or the DB write
    (the training hot path wants this; explicit ``flush()`` calls stay
    synchronous)."""

    def __init__(self, session=None, task: int = None,
                 component: str = None, flush_every: int = 100,
                 capacity: int = 65536, async_flush: bool = False):
        self.session = session
        self.task = task
        self.component = component
        self.flush_every = max(1, int(flush_every))
        self.capacity = int(capacity)
        self.async_flush = bool(async_flush)
        self._pending = []        # (name, kind, step, value) — hot path
        self._counters = {}
        self._histograms = {}
        self._mutate_lock = threading.Lock()
        self._hist_flushed_counts = {}   # name -> count at last flush
        self._flush_thread = None
        self._steps = itertools.count()
        self.dropped_count = 0
        self.flushed_count = 0
        if session is not None:
            _LIVE_RECORDERS.add(self)

    # ------------------------------------------------------------ hot path
    def _maybe_flush(self):
        # approximate trigger by design: a racy len() can only under-
        # or over-estimate by in-flight appends, deferring or adding
        # one flush. Taking _mutate_lock here would deadlock —
        # flush() acquires it and Lock is not reentrant.
        # preflight: disable=cc-lockset — see above
        if len(self._pending) < self.flush_every or self.session is None:
            return
        if not self.async_flush:
            self.flush()
            return
        t = self._flush_thread
        if t is not None and t.is_alive():
            return              # one in-flight flush is enough
        t = threading.Thread(target=self.flush, daemon=True,
                             name='telemetry-flush')
        self._flush_thread = t
        t.start()

    def series(self, name: str, value, step: int = None):
        """Per-step sample. ``value`` may be a live device array — it is
        NOT converted here (no device sync on the hot path)."""
        with self._mutate_lock:
            self._pending.append((name, 'series', step, value))
        self._maybe_flush()

    def gauge(self, name: str, value, step: int = None):
        with self._mutate_lock:
            self._pending.append((name, 'gauge', step, value))
        self._maybe_flush()

    def count(self, name: str, inc: float = 1):
        with self._mutate_lock:
            self._counters[name] = self._counters.get(name, 0.0) + inc

    def observe(self, name: str, value: float, buckets=None):
        """Histogram sample. ``buckets`` (upper bounds) apply on the
        FIRST observe of a name — later calls reuse the open
        histogram's boundaries (mixed bounds would corrupt the
        cumulative counts). Bucketed histograms are CUMULATIVE: they
        survive flushes (each flush emits a monotone snapshot — the
        shape Prometheus ``rate()`` needs), while bucket-less ones
        emit their window's summary and reset."""
        with self._mutate_lock:
            hist = self._histograms.get(name)
            if hist is None:
                hist = self._histograms[name] = Histogram(
                    buckets=buckets)
            hist.observe(value)

    def histogram_snapshot(self, name: str):
        """``(bucket_counts, count, total)`` of one open histogram
        under the lock — ONE consistent view (serving /health and
        /metrics read this; a mid-observe read would break the
        +Inf-bucket == count invariant). None when absent."""
        with self._mutate_lock:
            hist = self._histograms.get(name)
            if hist is None:
                return None
            return hist.bucket_counts(), hist.count, hist.total

    def next_step(self) -> int:
        return next(self._steps)

    def histogram_summaries(self) -> dict:
        """Live snapshot ``{name: summary_dict}`` of the open
        histograms — read without flushing (bench legs publish these
        in their JSON; a later flush still emits the rows)."""
        with self._mutate_lock:
            return {name: h.summary()
                    for name, h in self._histograms.items()}

    # ----------------------------------------------------------- flush path
    def _materialize(self):
        """Swap out pending samples + aggregate snapshots, converting
        values to floats (device pulls happen HERE, off the hot path).

        Buffered live device arrays come to host in ONE batched
        ``jax.device_get`` — per-scalar ``float()`` pulls block on the
        device once each (see train/loop.py's aggregate_metrics), so a
        100-sample window must be one transfer, not 100."""
        with self._mutate_lock:
            pending, self._pending = self._pending, []
            counters, self._counters = self._counters, {}
            hists = self._histograms
            # bucketed histograms stay registered and keep
            # aggregating — their flushed rows must be monotone across
            # flushes (cumulative Prometheus semantics); summary-only
            # histograms emit their window and reset
            self._histograms = {
                name: h for name, h in hists.items()
                if h.bucket_bounds is not None}
            # snapshot INSIDE the lock: the retained histograms are
            # still being observed by other threads. A retained
            # histogram that saw NO new samples since its last flush
            # emits nothing — an idle serving heartbeat must not grow
            # the metric table with identical snapshots forever.
            hist_snapshots = {}
            for name, h in hists.items():
                if h.bucket_bounds is not None and \
                        self._hist_flushed_counts.get(name) == h.count:
                    continue
                self._hist_flushed_counts[name] = h.count
                hist_snapshots[name] = (h.summary(),
                                        h.bucket_counts())
        if len(pending) > self.capacity:
            self.dropped_count += len(pending) - self.capacity
            pending = pending[-self.capacity:]
        values = [v for (_, _, _, v) in pending]
        if 'jax' in sys.modules and values:
            try:
                import jax
                values = jax.device_get(values)
            except Exception:
                pass
        # naive-UTC like every other DB timestamp (utils.misc.now) —
        # local time here would skew metric.time against log/queue rows
        from mlcomp_tpu.utils.misc import now
        ts = now()
        rows = []
        for (name, kind, step, _), value in zip(pending, values):
            try:
                rows.append((self.task, name, kind, step,
                             float(np.asarray(value)), ts,
                             self.component, None))
            except (TypeError, ValueError):
                # e.g. an unreduced per-device array: the sample is
                # unusable, but its loss must still be visible
                self.dropped_count += 1
                continue
        for name, total in counters.items():
            rows.append((self.task, name, 'counter', None, float(total),
                         ts, self.component, None))
        for name, (summary, buckets) in hist_snapshots.items():
            for stat, v in summary.items():
                rows.append((self.task, f'{name}.{stat}', 'histogram',
                             None, float(v), ts, self.component,
                             json.dumps({'of': name})))
            if buckets:
                # one row per cumulative le bucket, bound in the tags —
                # the shape /metrics re-renders as an OpenMetrics
                # histogram (telemetry/export.py)
                for le, count in buckets:
                    rows.append((self.task, f'{name}.bucket',
                                 'histogram', None, float(count), ts,
                                 self.component,
                                 json.dumps({'of': name, 'le': le})))
        return rows

    def flush(self, session=None) -> int:
        """Convert + persist everything pending in one batch. Telemetry
        failures never propagate into the instrumented code."""
        session = session or self.session
        rows = self._materialize()
        if not rows:
            return 0
        if session is None:
            self.dropped_count += len(rows)
            return 0
        from mlcomp_tpu.db.providers.telemetry import MetricProvider
        try:
            n = MetricProvider(session).add_many(rows)
        except Exception:
            self.dropped_count += len(rows)
            return 0
        self.flushed_count += n
        if self.task is not None:
            # heartbeat: a flush IS proof of life — touch the task row
            # so the watchdog's stall rule sees instrumented tasks as
            # alive without any extra plumbing (one UPDATE per flush
            # window, off the hot path)
            try:
                from mlcomp_tpu.db.providers.task import TaskProvider
                TaskProvider(session).update_last_activity(self.task)
            except Exception:
                pass
        return n

    def close(self) -> int:
        """Join any in-flight background flush, then flush the rest
        synchronously — the task-teardown call that guarantees every
        recorded sample is either in the DB or counted dropped."""
        t = self._flush_thread
        if t is not None and t.is_alive():
            t.join(timeout=30)
        return self.flush()


__all__ = ['MetricRecorder', 'Histogram', 'flush_live_recorders']
