"""Sharded training loop: TrainState, jit'd train/eval steps.

This is the TPU-native replacement for the reference's Catalyst runner
(reference worker/executors/catalyst/catalyst.py:313-376 delegates epochs
to catalyst; torch.distributed/NCCL does the gradient allreduce). Here
one jit'd step function serves every parallelism mode: the state is
placed with NamedShardings derived from the params' logical axes, the
batch rides dp/sp, and XLA inserts the gradient psum over ICI — there is
no rank/world_size plumbing anywhere.

bf16 policy: params/opt-state stay f32, compute dtype comes from the
model (`dtype='bfloat16'`), loss/metrics reduce in f32 on the MXU.
"""

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh

from mlcomp_tpu.parallel.sharding import (
    logical_rules, logical_to_sharding,
)
from mlcomp_tpu.train.device_data import gather_rows


class TrainState(struct.PyTreeNode):
    step: Any
    params: Any
    opt_state: Any
    batch_stats: Any = None
    rng: Any = None


# ------------------------------------------------------------------ losses
# Every loss takes optional per-example weights [B] (1=count, 0=ignore):
# eval pads tail batches with duplicate samples to stay mesh-divisible and
# zero-weights the padding so aggregates stay exact.
def _weighted(per_example, correct, weights):
    if weights is None:
        return per_example.mean(), correct.mean()
    w = weights.astype(jnp.float32)
    n = jnp.maximum(w.sum(), 1.0)
    return (per_example * w).sum() / n, \
        (correct.astype(jnp.float32) * w).sum() / n


def softmax_ce(logits, labels, weights=None):
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels)
    correct = jnp.argmax(logits, -1) == labels
    loss, acc = _weighted(per, correct, weights)
    return loss, {'loss': loss, 'accuracy': acc}


def lm_ce(logits, tokens, weights=None):
    """Next-token cross-entropy: logits [B,T,V] vs tokens [B,T]."""
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets).mean(-1)
    correct = jnp.mean(
        (jnp.argmax(logits, -1) == targets).astype(jnp.float32), -1)
    loss, acc = _weighted(per, correct, weights)
    return loss, {'loss': loss, 'accuracy': acc}


def seg_ce(logits, labels, weights=None):
    """Pixel cross-entropy: logits [B,H,W,C] vs labels [B,H,W]."""
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels).mean((-2, -1))
    correct = jnp.mean(
        (jnp.argmax(logits, -1) == labels).astype(jnp.float32), (-2, -1))
    loss, acc = _weighted(per, correct, weights)
    return loss, {'loss': loss, 'accuracy': acc}


def lm_ce_with(z_loss: float = 0.0, label_smoothing: float = 0.0,
               impl: str = 'auto') -> Callable:
    """lm_ce with z-loss / label smoothing (ops/fused_ce.py). The
    default impl='auto' is the dense formulation — measured to match
    the Pallas kernel even with both terms fused (fused_ce.py
    docstring); 'pallas' remains available for the kernel path."""

    def loss_fn(logits, tokens, weights=None):
        from mlcomp_tpu.ops.fused_ce import softmax_ce_per_example
        lg = logits[:, :-1]
        targets = tokens[:, 1:]
        b, t, v = lg.shape
        per_tok = softmax_ce_per_example(
            lg.reshape(b * t, v), targets.reshape(-1), impl=impl,
            z_loss=z_loss, label_smoothing=label_smoothing,
        ).reshape(b, t)
        per = per_tok.mean(-1)
        correct = jnp.mean(
            (jnp.argmax(lg.astype(jnp.float32), -1) == targets
             ).astype(jnp.float32), -1)
        loss, acc = _weighted(per, correct, weights)
        return loss, {'loss': loss, 'accuracy': acc}

    return loss_fn


#: beta of the looped model's loss: the weight of the exit distribution's
#: entropy (the paper's first-stage objective states the form, not the
#: number; ``benchmark/configs/ouro-2.6b.json`` ``assumed``)
ENTROPY_WEIGHT = 0.1


def looped_lm_ce(exits, tokens, weights=None,
                 entropy_weight: float = ENTROPY_WEIGHT):
    """The loss of a looped language model with gated exits
    (``models/ouro.py``): per token ``sum_t p(t) CE(z_t, next token) -
    entropy_weight * H(p)``, the mean over each sequence's tokens and
    then over the sequences. The model hands over ``exits``: ``states``
    [N, B, T, D] (the normed state of each exit), ``exit_logp`` [N, B, T]
    (log p(t)) and ``head`` [D, V]. The head and the cross-entropy run
    exit by exit (a ``lax.map``), each under ``jax.checkpoint``, so that
    one exit's ``[B, T, V]`` logits are live at a time, forward and
    backward; the logits are in the states' dtype, the softmax in
    float32. ``accuracy`` is the last exit's."""
    states, logp, head = exits['states'], exits['exit_logp'], exits['head']
    t = tokens.shape[1]
    # position i predicts token i + 1; the last position predicts nothing
    # (``live``)
    targets = jnp.roll(tokens, -1, axis=1)
    live = (jnp.arange(t) < t - 1).astype(jnp.float32)

    @jax.checkpoint
    def exit_ce(state):
        with jax.named_scope('exit'):
            logits = jnp.einsum('btd,dv->btv', state,
                                head.astype(state.dtype))
            logits = logits.astype(jnp.float32)
            hit = jnp.arange(logits.shape[-1]) == targets[..., None]
            picked = jnp.sum(jnp.where(hit, logits, 0.0), -1)
            ce = jax.nn.logsumexp(logits, -1) - picked
            return ce, jnp.argmax(logits, -1) == targets

    ce, correct = jax.lax.map(exit_ce, states)
    p = jnp.exp(logp)
    per_tok = jnp.sum(p * ce, 0) + entropy_weight * jnp.sum(p * logp, 0)
    per = jnp.sum(per_tok * live, -1) / (t - 1)
    acc = jnp.sum(correct[-1] * live, -1) / (t - 1)
    loss, acc = _weighted(per, acc, weights)
    return loss, {'loss': loss, 'accuracy': acc}


LOSSES = {'softmax_ce': softmax_ce, 'lm_ce': lm_ce, 'seg_ce': seg_ce,
          'looped_lm_ce': looped_lm_ce}
#: the losses whose target is the input itself (the next token)
SELF_SUPERVISED = ('lm_ce', 'looped_lm_ce')


def loss_for_task(task) -> Callable:
    """``task``: a registered loss name, or a dict spec — e.g.
    ``{name: lm_ce, z_loss: 1e-4, label_smoothing: 0.1}`` builds the
    fused-CE lm loss."""
    if isinstance(task, dict):
        spec = dict(task)
        name = spec.pop('name', None)
        if name == 'lm_ce' and spec:
            allowed = {'z_loss', 'label_smoothing', 'impl'}
            unknown = set(spec) - allowed
            if unknown:
                raise ValueError(
                    f'unknown lm_ce options {sorted(unknown)}; '
                    f'allowed: {sorted(allowed)}')
            return lm_ce_with(**spec)
        if spec:
            raise ValueError(
                f'loss options are supported for lm_ce only, '
                f'got {task!r}')
        task = name
    if task not in LOSSES:
        # contrib losses (dice/bce_dice/focal) register on import
        import mlcomp_tpu.contrib.criterion  # noqa: F401
    if task not in LOSSES:
        raise KeyError(f'unknown loss {task!r}; have {sorted(LOSSES)}')
    return LOSSES[task]


# ----------------------------------------------------------------- builder
#: weight of the MoE load-balance auxiliary loss (Switch's default)
MOE_AUX_COEF = 0.01

#: counters a model may sow under ``intermediates``, and how the values
#: of its layers become one number a step. They leave the step beside
#: the loss's metrics; ``JaxTrain`` emits each as a series per epoch
STEP_COUNTERS = {
    'moe.local_assign_share': jnp.mean,
    'moe.load_max_over_mean': jnp.mean,
    'moe.dropped': jnp.sum,
    'gated_delta.chunks': jnp.sum,
    'short_conv.rows': jnp.sum,
    'mla_attn.rows': jnp.sum,
    'loop.expected_exit': jnp.mean,
    'loop.layer_rows': jnp.sum,
}


#: the collection under which a model may sow, from the module that
#: owns a parameter and under the parameter's name, what is ADDED to
#: that leaf after the optimizer's update: an update that is no
#: gradient's (``SparseMoe``'s selection bias moves by its load rule)
LEAF_UPDATES = 'leaf_updates'

#: the two scopes of the update rule a compiled step's ops are named
#: under beside the model's own modules (``telemetry/op_blocks.py``
#: reads them): metadata only, the lowered program is the same
LOSS_SCOPE, OPTIMIZER_SCOPE = 'loss', 'optimizer'


def _apply(model, state: TrainState, x, train: bool, rng=None):
    """Returns (logits, new_batch_stats, aux_loss, counters,
    leaf_updates) — aux_loss is the summed sown ``moe_aux_loss`` (None
    when the model sows none), counters the sown ``STEP_COUNTERS`` and
    leaf_updates the sown ``LEAF_UPDATES`` ({} likewise)."""
    variables = {'params': state.params}
    mutable = []
    if state.batch_stats is not None:
        variables['batch_stats'] = state.batch_stats
        if train:
            mutable = ['batch_stats']
    if train:
        mutable = list(mutable) + ['intermediates', LEAF_UPDATES]
    rngs = {'dropout': rng} if (train and rng is not None) else None
    out = model.apply(variables, x, train=train, mutable=mutable,
                      rngs=rngs)
    if mutable:
        logits, updates = out
        # pick out ONLY moe_aux_loss and the counters — other sown
        # diagnostics must not leak into the loss or the metrics
        sown = {}

        def collect(tree):
            if isinstance(tree, dict):
                for key, value in tree.items():
                    if key == 'moe_aux_loss' or key in STEP_COUNTERS:
                        sown.setdefault(key, []).extend(
                            jnp.ravel(jnp.asarray(a, jnp.float32))
                            for a in jax.tree.leaves(value))
                    else:
                        collect(value)

        collect(updates.get('intermediates', {}))
        aux_leaves = sown.pop('moe_aux_loss', None)
        aux = sum(a.sum() for a in aux_leaves) if aux_leaves else None
        counters = {key: STEP_COUNTERS[key](jnp.concatenate(leaves))
                    for key, leaves in sown.items()}
        return (logits, updates.get('batch_stats'), aux, counters,
                updates.get(LEAF_UPDATES, {}))
    return (out[0] if isinstance(out, tuple) else out), None, None, {}, {}


def _add_leaf_updates(params, sown):
    """``params`` with what was sown under ``LEAF_UPDATES`` added to
    the leaves of the same paths."""
    if isinstance(sown, tuple):         # what `sow` keeps: (value,)
        return jax.tree.map(lambda leaf: leaf + sum(sown), params)
    return {**params, **{key: _add_leaf_updates(params[key], value)
                         for key, value in sown.items()}}


def _with_sown(loss, metrics, aux, counters):
    """The loss with the auxiliary loss added, the metrics with it and
    the counters beside them."""
    if aux is not None:
        loss = loss + MOE_AUX_COEF * aux
        metrics = dict(metrics, moe_aux=aux)
    if counters:
        metrics = dict(metrics, **jax.lax.stop_gradient(counters))
    return loss, metrics


def _jit_in_mesh(step, mesh: Optional[Mesh], donate_argnums=()):
    """``jax.jit`` of ``step``; given a mesh, traced inside it and its
    logical axis rules."""
    if mesh is None:
        return jax.jit(step, donate_argnums=donate_argnums)

    rules = logical_rules(mesh)

    def step_in_context(*args):
        with mesh, nn.logical_axis_rules(rules):
            return step(*args)

    return jax.jit(step_in_context, donate_argnums=donate_argnums)


def _update(model, optimizer, loss_fn, state: TrainState, x, target,
            step_rng):
    """The update rule of both train steps: the loss of ``x`` against
    ``target`` and its gradient, the optimizer's update, what the model
    sowed under ``LEAF_UPDATES``, the new state and the step's
    metrics."""

    def loss_wrapped(params):
        logits, new_stats, aux, counters, leaf_updates = _apply(
            model, state.replace(params=params), x, train=True,
            rng=step_rng)
        with jax.named_scope(LOSS_SCOPE):
            loss, metrics = _with_sown(*loss_fn(logits, target), aux,
                                       counters)
        return loss, (metrics, new_stats, leaf_updates)

    grads, (metrics, new_stats, leaf_updates) = jax.grad(
        loss_wrapped, has_aux=True)(state.params)
    with jax.named_scope(OPTIMIZER_SCOPE):
        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        if leaf_updates:
            new_params = _add_leaf_updates(new_params, leaf_updates)
    new_state = state.replace(
        step=state.step + 1, params=new_params, opt_state=new_opt,
        batch_stats=(new_stats if new_stats is not None
                     else state.batch_stats))
    return new_state, metrics


def _step_rng(state: TrainState):
    return (jax.random.fold_in(state.rng, state.step)
            if state.rng is not None else None)


def make_train_step(model, optimizer, loss_fn: Callable,
                    mesh: Optional[Mesh] = None,
                    self_supervised: bool = False):
    """Build the jit'd (state, x, y) -> (state, metrics) step.

    ``self_supervised``: y is ignored, the loss sees (logits, x) — the
    LM case where inputs are also targets.
    """

    def step(state: TrainState, x, y):
        return _update(model, optimizer, loss_fn, state, x,
                       x if self_supervised else y, _step_rng(state))

    return _jit_in_mesh(step, mesh, donate_argnums=(0,))


def make_device_train_step(model, optimizer, loss_fn: Callable,
                           mesh: Optional[Mesh] = None,
                           augment=None, dequantize: bool = False,
                           row_shape=None):
    """Device-resident-data variant of make_train_step: the step takes
    the FULL dataset (in HBM, held flat by ``place_dataset``) plus a
    [B] vector of row indices in the set's own order; the gather, the
    reshape of the batch to ``row_shape``, augmentation and
    dequantization (uint8 to float32 in [0, 1]) run inside the jit,
    ahead of the update rule the two steps share. Host→device traffic
    per step is the index vector (8 KB at batch 2,048) instead of the
    batch (6 MB). On the v5e trace (PERF.md, PR 26) the gather of 2,048
    CIFAR rows is one fusion of 0.07 ms in a 59.6 ms ResNet-18 step,
    and the step holds no other operation over the set.
    """

    def step(state: TrainState, x_all, y_all, idx):
        step_rng = _step_rng(state)
        x = gather_rows(x_all, idx, row_shape)
        y = jnp.take(y_all, idx, axis=0) if y_all is not None else None
        if augment is not None:
            # even without a dropout rng, fold the step counter so the
            # crop/flip pattern varies every step and epoch. Augment
            # runs BEFORE dequantization: on uint8-packed data the
            # one-hot crop then selects exact bf16 integers at full
            # MXU rate instead of f32 floats at HIGHEST precision
            base = step_rng if step_rng is not None else \
                jax.random.fold_in(jax.random.PRNGKey(0), state.step)
            x = augment(x, jax.random.fold_in(base, 1))
        if dequantize:
            x = x.astype(jnp.float32) / 255.0
        return _update(model, optimizer, loss_fn, state, x, y, step_rng)

    return _jit_in_mesh(step, mesh, donate_argnums=(0,))


def make_device_eval_step(model, loss_fn: Callable,
                          mesh: Optional[Mesh] = None,
                          dequantize: bool = False, row_shape=None):
    """Eval against the device-resident dataset: ships a [B] index
    vector + [B] weight vector per batch instead of the batch itself
    (the weights zero out tail padding so aggregates stay exact)."""

    def step(state: TrainState, x_all, y_all, idx, w):
        x = gather_rows(x_all, idx, row_shape)
        y = jnp.take(y_all, idx, axis=0)
        if dequantize:
            x = x.astype(jnp.float32) / 255.0
        logits = _apply(model, state, x, train=False)[0]
        with jax.named_scope(LOSS_SCOPE):
            _, metrics = loss_fn(logits, y, weights=w)
        return metrics

    return _jit_in_mesh(step, mesh)


def make_eval_step(model, loss_fn: Callable,
                   mesh: Optional[Mesh] = None,
                   self_supervised: bool = False):
    def step(state: TrainState, x, y, w=None):
        logits = _apply(model, state, x, train=False)[0]
        target = x if self_supervised else y
        with jax.named_scope(LOSS_SCOPE):
            _, metrics = loss_fn(logits, target, weights=w)
        return metrics

    return _jit_in_mesh(step, mesh)


def instrumented_step(step_fn, recorder,
                      metric_keys=('loss',), attribution=None,
                      tripwire=None, compile_events=None,
                      memory=None, deviceprof=None):
    """Wrap a jit'd train step of either maker, called as
    ``(state, *feed)``, with per-step telemetry recording
    (telemetry/metrics.py). Hot-path cost per step: a perf_counter
    read and 2-3 list appends — the device arrays in ``metrics`` are
    buffered as-is, NOT converted (no device sync; the recorder pulls
    them at flush time, every ``flush_every`` steps).

    ``step_time_ms`` is the host-observed interval between successive
    step dispatches: with async dispatch the per-call time measures
    the python/dispatch cost only, but once the device pipeline fills,
    back-pressure makes the inter-call interval track true device step
    time. The first call records no timing (no previous dispatch to diff
    against).

    Optional observability hooks (telemetry/attribution.py,
    telemetry/compile_events.py), each a clock read or a comparison:

    - ``attribution`` marks the compute/telemetry phases and closes
      each step (``step.phase.*`` series);
    - ``compile_events`` gets ``.step`` stamped so a compile fired
      inside this step lands with its triggering step number;
    - ``tripwire`` sees the same inter-dispatch interval and flags
      host-sync suspects — except on steps whose interval contains a
      recorded compile (slow for a known reason);
    - ``memory`` (telemetry/memory.py MemorySampler) records the
      per-step HBM timeline after the dispatch — one allocator-stats
      read per reporting device, no device sync, inert on platforms
      without memory stats (bench publishes
      ``memory_sampler_overhead_pct``; budget <1%);
    - ``deviceprof`` (telemetry/deviceprof.py DeviceProfiler) opens a
      short ``jax.profiler`` window every ``profile_every`` steps and
      closes it after its dispatch count — between windows this is
      one integer comparison (bench publishes
      ``devtime_overhead_pct``; budget <1%).
    """
    import time as _time
    last = [None]

    def wrapped(state, *args):
        # step number FIRST so a compile fired inside this dispatch is
        # labeled with the step that triggered it
        step = recorder.next_step()
        if compile_events is not None:
            compile_events.step = step
        if attribution is not None:
            attribution.begin('compute')
        out = step_fn(state, *args)
        t = _time.perf_counter()
        if attribution is not None:
            attribution.begin('telemetry', now=t)
        metrics = out[1] if isinstance(out, tuple) else {}
        for key in metric_keys:
            if key in metrics:
                recorder.series(key, metrics[key], step=step)
        prev, last[0] = last[0], t
        compiled = compile_events.consume_dirty() \
            if compile_events is not None else False
        if prev is not None:
            dt = t - prev
            recorder.series('step_time_ms', dt * 1e3, step=step)
            if tripwire is not None and not compiled:
                tripwire.observe(dt * 1e3, step=step)
        if memory is not None:
            memory.sample(step=step)
        if deviceprof is not None:
            # sampled device-time windows (telemetry/deviceprof.py):
            # one integer comparison per step outside a window; open
            # windows count this dispatch toward their extent
            deviceprof.on_step(step)
        if attribution is not None:
            attribution.step_end(step=step)
        return out

    return wrapped


def aggregate_metrics(metrics_list, weights=None):
    """Mean (optionally weighted) of a list of per-step metric dicts,
    pulled from device in ONE transfer.

    Per-scalar ``float()`` pulls block the host on the device once
    each; stacking on device and fetching a single [K, S] array makes
    metric collection one transfer.
    """
    import numpy as np
    if not metrics_list:
        return {}
    keys = sorted(metrics_list[0])
    stacked = jnp.stack(
        [jnp.stack([jnp.asarray(m[k], jnp.float32)
                    for m in metrics_list]) for k in keys])
    values = np.asarray(stacked)          # single device→host transfer
    if weights is not None:
        w = np.asarray(weights, np.float64)
        return {k: float(np.average(values[i], weights=w))
                for i, k in enumerate(keys)}
    return {k: float(values[i].mean()) for i, k in enumerate(keys)}


def create_train_state(model, optimizer, sample_x, rng,
                       mesh: Optional[Mesh] = None,
                       with_dropout_rng: bool = False) -> TrainState:
    """Init params + opt state; when a mesh is given, shard-place every
    leaf according to its logical axes (params stay boxed so specs remain
    recoverable for later resharding/checkpointing)."""
    init_rng, drop_rng = jax.random.split(jax.random.PRNGKey(0) if rng
                                          is None else rng)

    def init_fn(r):
        variables = model.init(r, sample_x, train=False)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=variables['params'],
            opt_state=optimizer.init(variables['params']),
            batch_stats=variables.get('batch_stats'),
            rng=(drop_rng if with_dropout_rng else None))

    if mesh is None:
        return init_fn(init_rng)

    abstract = jax.eval_shape(init_fn, init_rng)
    shardings = logical_to_sharding(abstract, mesh)
    # partitionable threefry for the sharded init: under the legacy
    # (non-partitionable) RNG, a jitted random draw's VALUES depend on
    # its out_sharding — the same model inited on a {'pp':4,'dp':2}
    # mesh vs a {'dp':8} mesh got different weights wherever the
    # logical rules sharded the leaf differently, breaking every
    # cross-mesh parity guarantee (pp-vs-dp, ep-vs-dp). Partitionable
    # draws are sharding-invariant by construction.
    with mesh, nn.logical_axis_rules(logical_rules(mesh)), \
            jax.threefry_partitionable(True):
        state = jax.jit(init_fn, out_shardings=shardings)(init_rng)
    return state


def state_sharding(state: TrainState, mesh: Mesh):
    return logical_to_sharding(jax.eval_shape(lambda: state), mesh)


def place_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place a host-side (e.g. checkpoint-restored numpy) state onto the
    mesh with its logical shardings. Multi-process safe: leaves are first
    device_put fully-replicated (identical host values on every process),
    then resharded to their target specs in one jit."""
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    replicated_state = jax.tree.map(
        lambda leaf: jax.device_put(leaf, rep)
        if not (isinstance(leaf, jax.Array) and leaf.committed)
        else leaf,
        state)
    shardings = logical_to_sharding(state, mesh)
    with mesh, nn.logical_axis_rules(logical_rules(mesh)):
        return jax.jit(lambda s: s,
                       out_shardings=shardings)(replicated_state)


__all__ = ['TrainState', 'make_train_step', 'make_device_train_step',
           'make_eval_step',
           'make_device_eval_step', 'aggregate_metrics',
           'instrumented_step',
           'create_train_state', 'state_sharding', 'place_state',
           'loss_for_task', 'LOSSES', 'SELF_SUPERVISED', 'softmax_ce',
           'lm_ce', 'seg_ce', 'lm_ce_with', 'looped_lm_ce']
