"""The benchmark's own wrappers around the program's calls.

Nothing under ``mlcomp_tpu/`` is edited and no option of the program is
added: while a job runs, four names the executor looks up are wrapped
from here, each wrapper calling the original.

- ``create_train_state``: the state the program built keeps its shape,
  optimizer state and shardings; its parameter VALUES are replaced by
  the benchmark's seeded weights (``weights.py``), so that the plain
  reference can make the same ones without the program.
- ``make_device_train_step`` / ``make_train_step``: the jitted step is
  wrapped by ``Step``, which notes the host time of every dispatch and,
  in the job's first steps, keeps what ``correct`` compares: the step's
  feed, its loss, the leaf norms of the optimizer's first moment after
  step 1 and of the parameters' change after step 3.
- ``fault_point('train.epoch')`` (the seam the executor crosses at the
  very end of each epoch, after validation's device->host pull, the
  series writes and the checkpoint submit): the host time of each
  epoch end, and the place where a traced run opens and closes
  ``jax.profiler`` around one whole epoch.

``Probe`` is the record of one job. ``CompileLog`` counts backend
compiles by the host clock, for the whole process.
"""

import contextlib
import time

from . import weights


class CompileLog:
    """Every backend compile of the process as (start, end) host
    seconds. jax has no public unregister, so one is installed once."""

    EVENT = '/jax/core/compile/backend_compile_duration'

    def __init__(self):
        self.events = []

    def install(self):
        import jax.monitoring

        def on_event(event, duration, **_):
            if event == self.EVENT:
                now = time.time()
                self.events.append((now - float(duration), now))

        jax.monitoring.register_event_duration_secs_listener(on_event)
        return self

    def inside(self, t0, t1):
        return [e for e in self.events if e[1] > t0 and e[0] < t1]


class Probe:
    """What the wrappers note down during one job."""

    def __init__(self, seed, capture_steps=0, trace_dir=None,
                 trace_epoch=None):
        self.seed = int(seed)
        self.capture_steps = int(capture_steps)
        self.trace_dir = trace_dir
        self.trace_epoch = trace_epoch      # epoch whose period is traced
        self.trace_marks = None             # (t_open, t_close) host s
        self.dispatch = []                  # host time of each step call
        self.epoch_ends = []
        self.in_use = []                    # device bytes in use at each
        self.param_spec = None
        self.feeds = []                     # per captured step: idx or rows
        self.losses = []                    # device scalars
        self.moment_norm = None             # {path: device scalar}
        self.delta_norm = None

    # ---------------------------------------------------- epoch boundary
    def on_epoch_end(self):
        now = time.time()
        self.epoch_ends.append(now)
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        self.in_use.append(int(stats.get('bytes_in_use', 0)))
        if self.trace_dir is None:
            return
        done = len(self.epoch_ends) - 1     # index of the epoch that ended
        if done + 1 == self.trace_epoch:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # device and TraceMe only
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            with jax.profiler.TraceAnnotation('bench_window_open'):
                t_open = time.time()
            self.trace_marks = (t_open, None)
        elif done == self.trace_epoch and self.trace_marks:
            with jax.profiler.TraceAnnotation('bench_window_close'):
                t_close = time.time()
            jax.profiler.stop_trace()
            self.trace_marks = (self.trace_marks[0], t_close)


class Step:
    """The program's jitted train step with the probe around it. Keeps
    ``lower`` so the executor's introspection compile still works."""

    def __init__(self, jitted, probe: Probe):
        self._jitted = jitted
        self._probe = probe
        self.lower = jitted.lower

    def __call__(self, state, *args):
        probe = self._probe
        n = len(probe.dispatch)
        probe.dispatch.append(time.time())
        if n < probe.capture_steps:
            # the step's own feed, never the resident set: the index
            # vector of (x_all, y_all, idx), or the rows of (x, y)
            probe.feeds.append(args[2] if len(args) == 3 else args[0])
        out = self._jitted(state, *args)
        if n < probe.capture_steps:
            new_state, metrics = out
            probe.losses.append(metrics['loss'])
            if n == 0:
                probe.moment_norm = _norms(
                    dict(weights.flat_paths(_first_moment(
                        new_state.opt_state))))
            if n == probe.capture_steps - 1:
                probe.delta_norm = _delta_norms(
                    dict(weights.flat_paths(new_state.params)),
                    probe.seed, probe.param_spec)
        return out


def _first_moment(opt_state):
    """The optimizer's first moment: optax's ``trace`` (momentum) or
    ``mu`` (Adam), wherever the chain keeps it."""
    found = []

    def walk(node):
        for name in ('trace', 'mu'):
            if hasattr(node, '_fields') and name in node._fields:
                found.append(getattr(node, name))
                return
        if isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError(
            f'{len(found)} first moments in the optimizer state')
    return found[0]


def _norms(tree: dict):
    import jax
    from .reference.common import leaf_norms
    return jax.jit(leaf_norms)(tree)


def _delta_norms(params: dict, seed: int, spec: dict):
    """Leaf norms of ``params - seeded weights``; the seeded weights are
    made again inside the same program, leaf by leaf, so no second copy
    of the model is ever resident."""
    import jax
    import jax.numpy as jnp

    def run(params, key):
        out = {}
        for path, leaf in params.items():
            start = weights.leaf_value(key, path, *spec[path])
            out[path] = jnp.sqrt(jnp.sum(jnp.square(
                leaf.astype(jnp.float32) - start.astype(jnp.float32))))
        return out

    with jax.threefry_partitionable(True):
        return jax.jit(run)(params, weights.seed_key(seed))


def seed_state(state, probe: Probe):
    """The program's fresh state with the benchmark's weights in it."""
    import jax
    flat = weights.flat_paths(state.params)
    spec = {p: (tuple(leaf.shape), leaf.dtype) for p, leaf in flat}
    shardings = {p: leaf.sharding for p, leaf in flat}
    probe.param_spec = spec
    values = weights.make_params(probe.seed, spec, shardings)
    new = state.replace(params=weights.replace_leaves(
        state.params, values))
    jax.block_until_ready(new.params)
    return new


@contextlib.contextmanager
def installed(probe: Probe):
    """Wrap the four names for the length of one job."""
    import mlcomp_tpu.testing.faults as faults
    import mlcomp_tpu.train.executor as executor
    import mlcomp_tpu.train.loop as loop

    orig = {
        'state': executor.create_train_state,
        'host_step': executor.make_train_step,
        'device_step': loop.make_device_train_step,
        'fault_point': faults.fault_point,
    }

    def create_train_state(*args, **kwargs):
        return seed_state(orig['state'](*args, **kwargs), probe)

    def make_train_step(*args, **kwargs):
        return Step(orig['host_step'](*args, **kwargs), probe)

    def make_device_train_step(*args, **kwargs):
        return Step(orig['device_step'](*args, **kwargs), probe)

    def fault_point(name, **ctx):
        if name == 'train.epoch':
            probe.on_epoch_end()
        return orig['fault_point'](name, **ctx)

    executor.create_train_state = create_train_state
    executor.make_train_step = make_train_step
    loop.make_device_train_step = make_device_train_step
    faults.fault_point = fault_point
    try:
        yield probe
    finally:
        executor.create_train_state = orig['state']
        executor.make_train_step = orig['host_step']
        loop.make_device_train_step = orig['device_step']
        faults.fault_point = orig['fault_point']
