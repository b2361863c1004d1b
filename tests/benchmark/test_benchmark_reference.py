"""The plain references against the program's models at tiny sizes, in
float32 on the CPU: same seeded weights, same input, same numbers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import common, resnet, transformer_lm


def _program_variables(model, sample, spec, seed):
    variables = model.init(jax.random.PRNGKey(0), sample, train=False)
    theirs = weights.tree_spec(variables['params'])
    assert {k: (tuple(s), np.dtype(d)) for k, (s, d) in spec.items()} \
        == {k: (s, np.dtype(d)) for k, (s, d) in theirs.items()}
    values = weights.make_params(seed, spec)
    variables = dict(variables)
    variables['params'] = weights.replace_leaves(
        variables['params'], values)
    return variables, values


def test_resnet_forward_and_gradient_match_the_program():
    from mlcomp_tpu.models import create_model
    spec_model = {'name': 'resnet18', 'num_classes': 10,
                  'num_filters': 8, 'dtype': 'float32'}
    model = create_model(**spec_model)
    x = jax.random.uniform(jax.random.PRNGKey(1), (8, 16, 16, 3))
    y = jnp.arange(8) % 10
    variables, values = _program_variables(
        model, x, resnet.param_spec(spec_model), seed=7)

    def program_loss(params):
        logits, _ = model.apply(
            dict(variables, params=params), x, train=True,
            mutable=['batch_stats'])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    def reference_loss(params):
        logits = resnet.forward(params, x, spec_model, lambda a: a)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    lp, gp = jax.value_and_grad(program_loss)(variables['params'])
    lr, gr = jax.value_and_grad(reference_loss)(values)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    gp = dict(weights.flat_paths(gp))
    for path, ref in gr.items():
        np.testing.assert_allclose(gp[path], ref, rtol=2e-3, atol=2e-5,
                                   err_msg=path)


def test_resnet_input_path_matches_the_program():
    from mlcomp_tpu.train.device_data import make_device_augment
    specs = [{'name': 'pad_crop', 'pad': 4}, 'hflip']
    x = jax.random.randint(jax.random.PRNGKey(3), (16, 32, 32, 3), 0,
                           256).astype(jnp.uint8)
    key = jax.random.PRNGKey(5)
    theirs = make_device_augment(
        [('pad_crop', {'pad': 4}), ('hflip', {})], (32, 32, 3))(x, key)
    ours = resnet.augment(x.astype(jnp.float32), key, specs)
    np.testing.assert_array_equal(np.asarray(theirs, np.float32), ours)
    # the step's key, as the program derives it from the job's seed
    state_rng = jax.random.split(jax.random.PRNGKey(11))[1]
    want = jax.random.fold_in(jax.random.fold_in(state_rng, 2), 1)
    np.testing.assert_array_equal(
        jax.random.key_data(resnet.step_key(resnet.job_key(11), 2)),
        jax.random.key_data(want))


def test_transformer_loss_and_gradient_match_the_program():
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.train.loop import lm_ce
    spec_model = {'name': 'transformer_lm', 'vocab_size': 256,
                  'd_model': 64, 'n_layers': 2, 'n_heads': 4, 'd_ff': 128,
                  'max_seq_len': 128, 'dtype': 'float32',
                  'attn_impl': 'dense', 'remat': True}
    model = create_model(**spec_model)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, 256)
    variables, values = _program_variables(
        model, tokens, transformer_lm.param_spec(spec_model), seed=9)

    def program_loss(params):
        logits = model.apply({'params': params}, tokens, train=True)
        return lm_ce(logits, tokens)[0]

    lp, gp = jax.value_and_grad(program_loss)(variables['params'])
    lr, gr = jax.value_and_grad(transformer_lm.loss_fn)(
        values, tokens, lambda a: a)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    gp = dict(weights.flat_paths(gp))
    for path, ref in gr.items():
        np.testing.assert_allclose(gp[path], ref, rtol=2e-3, atol=1e-6,
                                   err_msg=path)


@pytest.mark.parametrize('opt', [
    {'name': 'sgd', 'lr': 0.1, 'momentum': 0.9,
     'schedule': {'name': 'warmup_cosine', 'warmup_steps': 3,
                  'decay_steps': 10}},
    {'name': 'adamw', 'lr': 4e-4, 'b1': 0.9, 'b2': 0.95,
     'weight_decay': 0.1}])
def test_updates_match_the_program_optimizer(opt):
    import optax
    from mlcomp_tpu.train.optim import make_optimizer
    theirs, _ = make_optimizer(opt, 10)
    params = {'w': jnp.linspace(-1, 1, 12).reshape(3, 4)}
    grads = [{'w': jnp.sin(params['w'] * (i + 1))} for i in range(6)]
    state_p, state_r = theirs.init(params), common.opt_init(opt, params)
    p_prog, p_ref = params, params
    for step, g in enumerate(grads):
        updates, state_p = theirs.update(g, state_p, p_prog)
        p_prog = optax.apply_updates(p_prog, updates)
        p_ref, state_r = common.opt_update(opt, p_ref, g, state_r, step)
        np.testing.assert_allclose(p_prog['w'], p_ref['w'], rtol=2e-6,
                                   atol=1e-7)


def test_lower_precision_operands_change_the_numbers():
    x = jnp.linspace(0.1, 1.0, 64)
    assert float(jnp.abs(common.rounder('float32')(x) - x).max()) == 0
    bf16 = float(jnp.abs(common.rounder('bfloat16')(x) - x).max())
    fp8 = float(jnp.abs(common.rounder('float8')(x) - x).max())
    assert 0 < bf16 < fp8 / 8
