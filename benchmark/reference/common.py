"""What every plain reference shares: operand rounding, the learning
rate schedules and optimizer updates the configurations state, leaf
norms, and the loop that follows the program's first steps.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``;
nothing here imports the program (``mlcomp_tpu``), flax or optax.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: ``operands`` names -> the dtype matmul/conv operands are rounded to
#: (accumulation stays float32). ``float32`` is the reference proper;
#: ``bfloat16`` is what the configurations state; ``float8`` is the
#: nearest precision below it — the control that has to come out as
#: not correct.
OPERANDS = {'float32': None, 'bfloat16': jnp.bfloat16,
            'float8': jnp.float8_e4m3fn}


def rounder(operands: str):
    dtype = OPERANDS[operands]
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(jnp.float32)


# ---------------------------------------------------------------- schedule
def learning_rate(opt: dict, step):
    """The rate the configuration's ``optimizer`` block states at
    ``step`` (a traced or plain integer)."""
    lr = float(opt['lr'])
    sched = dict(opt.get('schedule') or {'name': 'constant'})
    name = sched['name']
    if name == 'constant':
        return jnp.float32(lr)
    if name == 'warmup_cosine':
        decay = int(sched['decay_steps'])
        warm = int(sched['warmup_steps'])
        init = float(sched.get('init_lr', lr / 25))
        final = float(sched.get('final_lr', 0.0))
        t = jnp.asarray(step, jnp.float32)
        up = init + (lr - init) * t / warm
        frac = jnp.clip((t - warm) / max(decay - warm, 1), 0.0, 1.0)
        down = final + (lr - final) * 0.5 * (1 + jnp.cos(math.pi * frac))
        return jnp.where(t < warm, up, down).astype(jnp.float32)
    raise ValueError(f'no reference for schedule {name!r}')


# --------------------------------------------------------------- optimizer
def opt_init(opt: dict, params: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    if opt['name'] == 'sgd':
        return {'m': zeros}
    if opt['name'] == 'adamw':
        return {'m': zeros, 'v': dict(zeros)}
    raise ValueError(f'no reference for optimizer {opt["name"]!r}')


def opt_update(opt: dict, params: dict, grads: dict, state: dict, step):
    """One update as the configuration states it; returns (params,
    state). ``step`` counts from 0."""
    lr = learning_rate(opt, step)
    if opt['name'] == 'sgd':
        mom = float(opt.get('momentum', 0.9))
        m = {k: grads[k] + mom * state['m'][k] for k in params}
        new = {k: params[k] - lr * m[k] for k in params}
        return new, {'m': m}
    b1, b2 = float(opt.get('b1', 0.9)), float(opt.get('b2', 0.999))
    wd = float(opt.get('weight_decay', 1e-2))
    eps = 1e-8
    t = jnp.asarray(step, jnp.float32) + 1.0
    m = {k: b1 * state['m'][k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * state['v'][k] + (1 - b2) * grads[k] ** 2
         for k in params}
    new = {}
    for k in params:
        m_hat = m[k] / (1 - b1 ** t)
        v_hat = v[k] / (1 - b2 ** t)
        new[k] = params[k] - lr * (
            m_hat / (jnp.sqrt(v_hat) + eps) + wd * params[k])
    return new, {'m': m, 'v': v}


def first_gradient(opt: dict, first_moment: dict) -> dict:
    """The first gradient as the optimizer got it, from its first
    moment after ONE step (momentum trace, or Adam's ``mu``)."""
    if opt['name'] == 'sgd':
        return first_moment
    b1 = float(opt.get('b1', 0.9))
    return {k: v / (1 - b1) for k, v in first_moment.items()}


# ------------------------------------------------------------------- norms
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


# -------------------------------------------------------------------- loop
def follow(loss_and_grads, opt: dict, params: dict, feeds, steps=3,
           offload=False):
    """Follow the program's first ``steps`` steps.

    ``loss_and_grads(params, feed, step) -> (loss, grads)`` is the
    family's forward+backward over one step's feed. Returns the losses,
    the leaf norms of the first gradient and of the parameters' change
    after the last step, as plain floats.

    ``offload``: for a model whose parameters, gradients and two
    moments do not fit the chip together — the update then goes leaf by
    leaf, with the moments and the starting weights kept on the host."""
    import numpy as np
    keep = np.asarray if offload else (lambda x: x)
    start = {k: keep(v) for k, v in params.items()}
    state = None
    losses, grad_norms = [], None

    @jax.jit
    def update(params, grads, state, step):
        return opt_update(opt, params, grads, state, step)

    def update_by_leaf(params, grads, state, step):
        new_params, new_state = {}, {}
        for k in sorted(params):
            one = {m: {k: jnp.asarray(v[k])} for m, v in state.items()} \
                if state else opt_init(opt, {k: params[k]})
            p, s = update({k: params.pop(k)}, {k: grads.pop(k)}, one,
                          step)
            new_params[k] = p[k]
            for m, v in s.items():
                new_state.setdefault(m, {})[k] = np.asarray(v[k])
        return new_params, new_state

    for step in range(steps):
        with jax.default_matmul_precision('highest'):
            loss, grads = loss_and_grads(params, feeds[step], step)
        if step == 0:
            grad_norms = jax.jit(leaf_norms)(grads)
        if offload:
            params, state = update_by_leaf(dict(params), dict(grads),
                                           state, step)
        else:
            state = state or opt_init(opt, params)
            params, state = update(params, grads, state, step)
        losses.append(float(loss))
        del grads
    delta = {k: float(jax.jit(lambda a, b: jnp.sqrt(jnp.sum(
        jnp.square(a - b))))(params[k], start[k])) for k in params}
    moment = {k: float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(v)))))
              for k, v in state['m'].items()}
    return {'loss': losses,
            'grad_norm': {k: float(v) for k, v in grad_norms.items()},
            'moment_norm': moment, 'delta_norm': delta}
