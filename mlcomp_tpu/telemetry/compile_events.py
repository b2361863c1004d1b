"""Runtime recompile + host-sync detection.

A silent XLA recompile is the classic "the step got 100x slower and
nothing says why": a shape-varying input, a weak-type flip or a python
scalar in the carry retraces and recompiles the step, the host blocks
for seconds, and the only witness is a step-time spike. The static
preflight linter (analysis/jax_lint.py) catches the *patterns* at
submit time; this module catches the *events* at runtime:

- ``CompileEventRecorder`` subscribes to JAX's monitoring
  event-duration listeners (``jax.monitoring``) and records every
  backend compile as a ``compile.backend_ms`` metric sample carrying
  the triggering train step — with a conservative no-op fallback when
  the hooks are unavailable (stripped builds): the
  loop runs exactly as before, just without compile telemetry.
  The watchdog's **recompile-storm** rule (telemetry/watchdog.py)
  turns the series into action: N compiles after warmup inside a time
  window → a deduped, auto-resolving Alert.

- ``HostSyncTripwire`` is the runtime counterpart of the linter's
  host-sync rules (``.item()``/``float()``/``np.asarray`` inside jit
  regions): it watches the host-observed inter-dispatch interval the
  instrumented step already measures, and flags steps that blow past a
  multiple of the rolling median (and an absolute floor) — the
  signature of a blocking device transfer inside the step path —
  as ``host_sync.suspect_ms`` samples. Steps whose interval contains a
  recorded compile are exempt (a compile is slow for a *known* reason).

Hot-path cost: the listener runs only when XLA actually compiles
(never on a steady-state step); the tripwire is one comparison per
step against a cached median, refreshed every ``refresh_every``
samples.
"""

import statistics
import time
from collections import deque

#: monitoring keys that mean "XLA compiled a program" (matching is by
#: exact name so unrelated durations — tracing, lowering — never
#: count as compiles)
COMPILE_EVENTS = ('/jax/core/compile/backend_compile_duration',)


class CompileEventRecorder:
    """Record XLA compile events as metric samples with the triggering
    step.

    The instrumented step (train/loop.py) stamps ``self.step`` each
    step, so a compile fired from inside the step lands with the step
    number that triggered it — the recompile timeline the dashboard
    renders. ``install()`` returns False (and everything stays a
    no-op) when the jax monitoring hooks are unavailable.
    """

    def __init__(self, recorder=None, metric='compile.backend_ms',
                 max_events=512):
        self.recorder = recorder
        self.metric = metric
        self.step = None          # stamped by the instrumented step
        self.events = deque(maxlen=max_events)
        self.installed = False
        self._dead = False
        self._dirty = False       # a compile landed since last consume
        self._listener = None

    def install(self) -> bool:
        """Subscribe to jax's event-duration listeners. Safe to call
        when jax is absent or too old — returns False and stays
        inert. Re-arming after ``uninstall()`` works (the dead flag
        resets; assign ``self.recorder`` again if persistence is
        wanted — uninstall cleared it)."""
        if self.installed:
            return True
        self._dead = False
        try:
            import jax.monitoring as monitoring
            register = monitoring.register_event_duration_secs_listener
        except Exception:
            return False

        def _on_event(event, duration, **kwargs):
            # never let telemetry break the compile it observes
            try:
                if self._dead or event not in COMPILE_EVENTS:
                    return
                step = self.step
                self.events.append({'event': event,
                                    'duration_s': float(duration),
                                    'step': step, 'ts': time.time()})
                self._dirty = True
                if self.recorder is not None:
                    self.recorder.series(self.metric,
                                         float(duration) * 1e3,
                                         step=step)
            except Exception:
                pass

        try:
            register(_on_event)
        except Exception:
            return False
        self._listener = _on_event
        self.installed = True
        return True

    def uninstall(self):
        """Detach the listener. jax.monitoring has no public
        unregister, so the private helper is tried and the closure is
        dead-flagged either way — a persistent worker must not keep
        recording compiles into a finished task's recorder. The
        recorder reference is dropped regardless: if jax's listener
        list keeps the dead closure alive, it must pin only this bare
        object, never a finished task's recorder + DB session.
        ``events`` stays readable after uninstall (bounded deque)."""
        self._dead = True
        self.recorder = None
        if self._listener is None:
            return
        try:
            from jax._src import monitoring as _m
            _m._unregister_event_duration_listener_by_callback(
                self._listener)
        except Exception:
            pass
        self._listener = None
        self.installed = False

    def consume_dirty(self) -> bool:
        """True iff a compile landed since the previous call — the
        tripwire's exemption signal."""
        dirty, self._dirty = self._dirty, False
        return dirty


class HostSyncTripwire:
    """Flag steps whose host-observed interval says "something inside
    the step blocked the host" — a device→host transfer in the step
    path, after the pipeline should be async.

    ``observe(dt_ms)`` is called with the inter-dispatch interval the
    instrumented step already computes. After ``warmup_steps`` clean
    samples, an interval above ``max(min_ms, factor x rolling
    median)`` records a ``host_sync.suspect_ms`` sample (and is kept
    OUT of the baseline, so one sync can't teach the tripwire that
    syncs are normal).
    """

    def __init__(self, recorder=None, factor=20.0, min_ms=50.0,
                 warmup_steps=10, window=64, refresh_every=16,
                 metric='host_sync.suspect_ms'):
        self.recorder = recorder
        self.factor = float(factor)
        self.min_ms = float(min_ms)
        self.warmup_steps = int(warmup_steps)
        self.metric = metric
        self.suspects = 0
        self._times = deque(maxlen=int(window))
        self._median = None
        self._since_refresh = 0
        self._refresh_every = max(1, int(refresh_every))

    def observe(self, dt_ms: float, step=None) -> bool:
        dt_ms = float(dt_ms)
        if len(self._times) >= self.warmup_steps:
            if self._median is None or \
                    self._since_refresh >= self._refresh_every:
                self._median = statistics.median(self._times)
                self._since_refresh = 0
            self._since_refresh += 1
            threshold = max(self.min_ms, self.factor * self._median)
            if dt_ms > threshold:
                self.suspects += 1
                if self.recorder is not None:
                    self.recorder.series(self.metric, dt_ms, step=step)
                return True
        self._times.append(dt_ms)
        return False


__all__ = ['CompileEventRecorder', 'HostSyncTripwire', 'COMPILE_EVENTS']
