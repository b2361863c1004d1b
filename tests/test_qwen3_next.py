"""The ``qwen3_next`` model and its ops on the CPU, float32, seeded
weights, small sizes: the model against the plain reference
(``benchmark/reference/qwen3_next.py``) per layer kind and for whole
periods; the chunked gated delta rule against the token recurrence; the
grouped-query flash kernels (interpret mode) against dense attention;
partial rotary against a hand-written rotation; the expert layer with
every token on ONE held expert; the share test — the parts all the
shares give add up to the uncut layer; and what a `remat`ted layer
holds by name (``REMAT_SAVED``): the plain gradients, every name read
by the backward pass, no kernel of the forward pass run again."""

import collections
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402
from mlcomp_tpu.models import create_model, qwen3_next  # noqa: E402
from mlcomp_tpu.models.decoder_parts import (  # noqa: E402
    MoeConfig, SparseMoe, rotary, routing_top_k,
)
from mlcomp_tpu.models.qwen3_next import Qwen3NextConfig  # noqa: E402
from mlcomp_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention_backward, flash_attention_forward, fused_attention,
    reference_attention,
)
from mlcomp_tpu.ops import gated_delta  # noqa: E402
from mlcomp_tpu.ops.gated_delta import (  # noqa: E402
    gated_delta_rule, inv_unit_lower, reference_gated_delta,
)

SMALL = dict(
    vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
    head_dim=16, linear_key_heads=2, linear_value_heads=4,
    linear_key_dim=8, linear_value_dim=8, n_experts=16, top_k=2,
    d_expert=16, d_shared=16, experts_held=8, expert_offset=4,
    dtype='float32', delta_chunk=16)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision('highest'):
        yield


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def seeded(model_kwargs, seed=7, gain=8.0, seq=32):
    """(module, its parameter tree and the reference's dict) with the
    benchmark's seeded weights, the kernels scaled up so that gates,
    decays and the router are far from their flat middle."""
    model = create_model('qwen3_next', **model_kwargs)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, seq), 0,
                                model_kwargs['vocab_size'])
    tree = meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(1), tokens)['params'])
    spec = ref.param_spec(dict(model_kwargs))
    assert {p: tuple(s) for p, (s, _) in spec.items()} == \
        {p: tuple(s) for p, (s, _) in weights.tree_spec(tree).items()}
    values = {k: v if k.endswith('scale') else v * gain
              for k, v in weights.make_params(seed, spec).items()}
    return model, weights.replace_leaves(tree, values), values, tokens


# ------------------------------------------------ model against reference
@pytest.mark.parametrize('case,over', [
    ('linear_layer', dict(n_layers=1)),
    ('full_layer', dict(n_layers=1, full_attention_interval=1)),
    ('one_period', dict()),
    ('one_period_remat', dict(remat=True)),
    ('two_periods_scanned', dict(n_layers=8, remat=True)),
])
def test_model_against_reference(case, over):
    kwargs = dict(SMALL, **over)
    model, params, values, tokens = seeded(kwargs)

    def program(p):
        logits = model.apply({'params': p}, tokens).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1])
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
        return -jnp.mean(jnp.mean(picked[..., 0], -1))

    loss, grads = jax.jit(jax.value_and_grad(program))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, dict(kwargs), lambda x: x)))(
        values)
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    grads = dict(weights.flat_paths(grads))
    assert set(grads) == set(want_grads)
    norms, gaps = jax.jit(lambda a, b: (
        {k: jnp.linalg.norm(v) for k, v in b.items()},
        {k: jnp.linalg.norm(a[k] - v) / jnp.linalg.norm(v)
         for k, v in b.items()}))(grads, want_grads)
    for leaf in want_grads:
        assert float(norms[leaf]) > 0, leaf
        assert float(gaps[leaf]) < 2e-3, (leaf, float(gaps[leaf]))


@pytest.mark.parametrize('over', [
    dict(n_layers=6, scan_layers=False), dict(n_layers=6),
    dict(n_layers=9, full_attention_interval=3)],
    ids=['looped_with_a_tail', 'one_period_and_a_tail',
         'three_scanned_periods'])
def test_the_reference_names_the_programs_leaves(over):
    """Depth is a number: whole periods scanned where there is more than
    one, the rest behind them — and the reference's ``param_spec`` has
    the program's parameter tree, leaf for leaf."""
    kwargs = dict(SMALL, **over)
    tree = meta.unbox(jax.eval_shape(
        create_model('qwen3_next', **kwargs).init, jax.random.PRNGKey(1),
        jnp.zeros((2, 32), jnp.int32))['params'])
    assert {p: tuple(s) for p, (s, _) in ref.param_spec(kwargs).items()} \
        == {p: tuple(s) for p, (s, _) in weights.tree_spec(tree).items()}


def test_counters_leave_the_step():
    from mlcomp_tpu.train.loop import (
        STEP_COUNTERS, create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    model = create_model('qwen3_next', **SMALL)
    optimizer = make_optimizer({'name': 'adamw', 'lr': 1e-3})
    optimizer = optimizer[0] if isinstance(optimizer, tuple) else optimizer
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    state = jax.jit(lambda key: create_train_state(
        model, optimizer, tokens, key))(jax.random.PRNGKey(1))
    step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                           self_supervised=True)
    _, metrics = step(state, tokens, None)
    # every counter but those only `lfm2_moe`'s conv mixer,
    # `deepseek_v3`'s latent attention and `ouro`'s loop sow
    assert set(STEP_COUNTERS) - {'short_conv.rows', 'mla_attn.rows',
                                 'loop.expected_exit',
                                 'loop.layer_rows'} <= set(metrics)
    assert 'short_conv.rows' not in metrics
    assert float(metrics['moe.dropped']) == 0
    # three linear layers of 2 sequences x 4 heads x 2 chunks of 16
    assert float(metrics['gated_delta.chunks']) == 3 * 2 * 4 * 2
    assert 0 < float(metrics['moe.local_assign_share']) < 1
    assert float(metrics['moe.load_max_over_mean']) >= 1


# ------------------------------------------------------ gated delta rule
def delta_inputs(b=2, t=96, h=3, dk=16, dv=32):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731,E501
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize('impl,chunk', [
    ('xla', 16), ('xla', 32), ('xla', 64), ('interpret', 16),
    ('interpret', 32), ('interpret', 64)])
def test_chunked_delta_rule_against_the_recurrence(impl, chunk):
    """T = 96: six, three and one and a half chunks (the last padded).
    ``interpret`` goes through the four kernels of the Pallas path."""
    args = delta_inputs()
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out * weight), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)

    (_, want), wants = both(reference_gated_delta)
    (_, got), grads = both(lambda *a: gated_delta_rule(
        *a, chunk=chunk, impl=impl, group=2, head_block=2))
    assert rel(got, want) < 1e-5
    for name, a, b in zip('q k v g beta'.split(), grads, wants):
        assert rel(a, b) < 2e-5, name


@pytest.mark.parametrize('n,base', [(16, 16), (64, 16), (64, 64), (32, 8)])
def test_inverse_of_unit_lower_triangular(n, base):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1)
    mat = a * 0.3 + jnp.eye(n)
    got = inv_unit_lower(mat, base)
    assert float(jnp.abs(got @ mat - jnp.eye(n)).max()) < 1e-4


# ------------------------ the chunk-local kernels against ``_prepare``
OPERANDS = 'qg kd w u p a'.split()
INPUTS = 'q k v g beta'.split()
# float32: the same products in another order; bfloat16: the kernels
# cast a cotangent where XLA's transposed chain multiplies it in float32
CLOSE = {'float32': (1e-5, 2e-5), 'bfloat16': (2e-3, 1e-2)}


@pytest.fixture(scope='module', params=['float32', 'bfloat16'])
def prepared(request):
    """At the cell's widths (C = 64, dk = dv = 128), 2 x 2 heads of 4
    chunks, one pair a grid step: ``_prepare``'s outputs and the
    pullback of random cotangents, from XLA and from the kernels."""
    dtype = request.param
    with jax.default_matmul_precision('highest'):
        q, k, v, g, beta = delta_inputs(t=256, h=2, dk=128, dv=128)
        args = tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)
        want, pullback = jax.vjp(
            lambda *a: gated_delta._prepare(*a, 64, 16), *args)
        *got, t32 = gated_delta._prepare_pallas(
            *args, 64, 2, True, keep_t=True)
        keys = jax.random.split(jax.random.PRNGKey(11), len(want))
        cotangents = tuple(jax.random.normal(key, x.shape).astype(x.dtype)
                           for key, x in zip(keys, want))
        back = gated_delta._prepare_pallas_bwd(
            *args, t32, cotangents, 64, 2, True)
        return dtype, want, got, pullback(cotangents), back


def close(got, want, limit):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < limit


@pytest.mark.parametrize('name', OPERANDS)
def test_prepare_kernel_against_xla(prepared, name):
    dtype, want, got, _, _ = prepared
    i = OPERANDS.index(name)
    close(got[i], want[i], CLOSE[dtype][0])


@pytest.mark.parametrize('name', INPUTS)
def test_prepare_backward_in_closed_form(prepared, name):
    dtype, _, _, want, got = prepared
    i = INPUTS.index(name)
    close(got[i], want[i], CLOSE[dtype][1])


def test_the_kernels_solve_on_ill_conditioned_chunks():
    """One key repeated, beta 0.99, hardly any decay: ``A`` is 0.99
    below the diagonal, its powers hold binomials (a Neumann product
    over all 64 rows would cancel 1e17 down to 1e-2). Against the answer
    in float64 the kernel's ``u = T (beta v)``, from joins alone, is no
    worse than ``inv_unit_lower(..., 16)``'s."""
    c, d = 64, 128
    key = jax.random.normal(jax.random.PRNGKey(2), (d,))
    k = jnp.broadcast_to(key / jnp.linalg.norm(key), (1, 2 * c, 1, d))
    q = k * d ** -0.5
    v = jax.random.normal(jax.random.PRNGKey(4), (1, 2 * c, 1, d))
    g = jnp.full((1, 2 * c, 1), -1e-3)
    beta = jnp.full((1, 2 * c, 1), 0.99)
    k64, v64 = (np.asarray(x[0, :, 0], np.float64) for x in (k, v))
    gamma = -1e-3 * np.arange(1, c + 1)
    a_mat = np.tril(0.99 * (k64[:c] @ k64[:c].T)
                    * np.exp(gamma[:, None] - gamma[None]), -1)
    exact = np.linalg.solve(
        np.eye(c) + a_mat, 0.99 * v64.reshape(2, c, d))       # [2,C,d]

    def gap(operands):
        return np.abs(np.asarray(operands[3][0], np.float64)
                      - exact).max() / np.abs(exact).max()

    xla = gap(gated_delta._prepare(q, k, v, g, beta, c, 16))
    kernel = gap(gated_delta._prepare_pallas(q, k, v, g, beta, c, 2, True))
    whole = gap(gated_delta._prepare(q, k, v, g, beta, c, 64))
    assert kernel < xla + 1e-6 and kernel < 1e-5
    assert whole > 1e6 * kernel        # why no whole-block Neumann


# --------------------------------------------------- grouped-query flash
def attention_inputs(h, h_kv, t=256, d=256):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    return (jax.random.normal(ks[0], (1, t, h, d)),
            jax.random.normal(ks[1], (1, t, h_kv, d)),
            jax.random.normal(ks[2], (1, t, h_kv, d)),
            jax.random.normal(ks[3], (1, t, h, d)))


@pytest.mark.parametrize('causal', [True, False], ids=['causal', 'full'])
@pytest.mark.parametrize('block', [128, 256])
def test_grouped_query_flash_against_dense(block, causal):
    """8 query heads a key-value head at head_dim 256; at block 128 the
    backward kernel's last grid axis runs over 8 heads x 2 q-blocks,
    at 256 the one tile is walked in two strips."""
    q, k, v, do = attention_inputs(8, 1)
    want, pull = jax.vjp(functools.partial(
        reference_attention, causal=causal), q, k, v)
    out, lse = flash_attention_forward(
        q, k, v, causal=causal, block_q=block, block_k=block,
        interpret=True, with_lse=True)
    assert rel(out, want) < 1e-5
    grads = flash_attention_backward(
        q, k, v, out, lse, do, causal=causal, block_q=block,
        block_k=block, interpret=True)
    for name, a, b in zip('dq dk dv'.split(), grads, pull(do)):
        assert a.shape == b.shape
        assert rel(a, b) < 1e-5, name


def test_equal_heads_flash_is_what_it_was():
    """Equal head counts: the dense result, the result of the grouped
    path given the same heads repeated, and the plain index maps."""
    from mlcomp_tpu.ops import flash_attention as fa
    q, k, v, do = attention_inputs(2, 2, d=128)
    loss = lambda impl: lambda *a: jnp.sum(  # noqa: E731
        fused_attention(*a, causal=True, impl=impl) * do)
    got = jax.grad(loss('interpret'), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss('dense'), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5
    one = jax.grad(loss('interpret'), argnums=(0, 1, 2))(
        q, k[:, :, :1], v[:, :, :1])
    two = jax.grad(loss('interpret'), argnums=(0, 1, 2))(
        q, jnp.repeat(k[:, :, :1], 2, 2), jnp.repeat(v[:, :, :1], 2, 2))
    assert rel(one[0], two[0]) < 1e-5
    assert rel(one[1], jnp.sum(two[1], 2, keepdims=True)) < 1e-5
    assert fa._kv_head(5, 1) == 5 and fa._kv_head(17, 8) == 2
    assert fa._causal_kv_ix(False)(3, 1, 2) == (3, 2)
    assert fa._causal_kv_ix(False, 8)(17, 1, 2) == (2, 2)
    # heads of 256 take the tile of every other head since PR 34 (the
    # kernels ask for the VMEM their shapes need)
    assert fa._blocks(8192, 1024, 1024, True) == (1024, 1024)


# ------------------------------------------------------------------ rotary
def test_partial_rotary_against_a_hand_written_rotation():
    t, d, rot, theta = 5, 16, 8, 1e7
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, t, 2, d)))
    want = x.copy()
    for pos in range(t):
        for i in range(rot // 2):
            angle = pos * theta ** (-2.0 * i / rot)
            a, b = x[0, pos, :, i], x[0, pos, :, i + rot // 2]
            want[0, pos, :, i] = a * np.cos(angle) - b * np.sin(angle)
            want[0, pos, :, i + rot // 2] = \
                b * np.cos(angle) + a * np.sin(angle)
    got = rotary(jnp.asarray(x), theta, rot)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])
    np.testing.assert_allclose(ref.rotary(jnp.asarray(x), theta, rot),
                               want, atol=1e-5)


# ------------------------------------------------------------ expert layer
def moe_setup(seed=11, n_experts=16, top_k=2, tokens=(2, 24), **over):
    cfg = Qwen3NextConfig(**dict(
        SMALL, n_experts=n_experts, top_k=top_k, experts_held=n_experts,
        expert_offset=0, **over))
    d, f = cfg.d_model, cfg.d_expert
    spec = {'router': ((d, n_experts), jnp.float32),
            'wi_gate': ((n_experts, d, f), jnp.float32),
            'wi_up': ((n_experts, d, f), jnp.float32),
            'wo': ((n_experts, f, d), jnp.float32),
            'shared/wi_gate/kernel': ((d, cfg.d_shared), jnp.float32),
            'shared/wi_up/kernel': ((d, cfg.d_shared), jnp.float32),
            'shared/wo/kernel': ((cfg.d_shared, d), jnp.float32),
            'shared_gate/kernel': ((d, 1), jnp.float32)}
    values = {k: 8 * v for k, v in weights.make_params(seed, spec).items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), tokens + (d,))
    return cfg, values, x


def share_of(cfg, values, offset, held):
    """(module, params) of the share [offset, offset + held)."""
    cfg = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    tree = {'router': values['router'],
            'shared_gate': {'kernel': values['shared_gate/kernel']},
            'shared': {n: {'kernel': values[f'shared/{n}/kernel']}
                       for n in ('wi_gate', 'wi_up', 'wo')}}
    for name in ('wi_gate', 'wi_up', 'wo'):
        tree[name] = values[name][offset:offset + held]
    return SparseMoe(MoeConfig.of(cfg)), tree


def reference_moe(cfg, values, x, offset, held):
    model = dict(dataclasses.asdict(cfg), experts_held=held,
                 expert_offset=offset)
    p = {f'moe/{k}': v for k, v in values.items()}
    for name in ('wi_gate', 'wi_up', 'wo'):
        p[f'moe/{name}'] = values[name][offset:offset + held]
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision='highest')  # noqa: E731,E501
    return jax.jit(lambda x, p: ref.sparse_moe(
        x, p, ref._sizes(model), ein))(x, p)


@pytest.mark.parametrize('impl', ['ragged', 'interpret'])
def test_every_token_on_one_held_expert(impl):
    """The router sends every token to expert 5 first; only expert 5 is
    held: nothing is dropped and the answer is the reference's."""
    cfg, values, x = moe_setup(moe_impl=impl)
    x = jnp.abs(x)
    values['router'] = values['router'].at[:, 5].set(10.0)
    module, params = share_of(cfg, values, 5, 1)
    y, sown = jax.jit(lambda p, x: module.apply(
        {'params': p}, x, mutable=['intermediates']))(params, x)
    counters = {k: float(v[0]) for k, v in sown['intermediates'].items()}
    assert counters['moe.dropped'] == 0
    assert counters['moe.local_assign_share'] == pytest.approx(0.5)
    assert counters['moe.load_max_over_mean'] == pytest.approx(1.0)
    assert rel(y, reference_moe(cfg, values, x, 5, 1)) < 1e-5


def test_a_short_buffer_is_counted():
    """With the buffer cut under what lands here the counter says how
    many pairs were left out (the default sizes it for the worst case)."""
    cfg, values, x = moe_setup(moe_buffer_factor=0.5, tokens=(4, 128))
    x = jnp.abs(x)
    values['router'] = values['router'].at[:, 5].set(10.0)
    module, params = share_of(cfg, values, 5, 1)
    _, sown = jax.jit(lambda p, x: module.apply(
        {'params': p}, x, mutable=['intermediates']))(params, x)
    # 512 pairs land; the buffer holds 0.5 * 512 * 2 / 16 = 32 rows,
    # rounded up to a tile of 128
    assert float(sown['intermediates']['moe.dropped'][0]) == 512 - 128


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the parts of the result that all the
    shares give, the shared expert counted once, add up to what the
    uncut reference gives for the whole layer (model-configs guide,
    section 4)."""
    cfg, values, x = moe_setup()
    whole = reference_moe(cfg, values, x, 0, 16)
    flat = x.reshape(-1, cfg.d_model)
    swiglu = (jax.nn.silu(flat @ values['shared/wi_gate/kernel'])
              * (flat @ values['shared/wi_up/kernel'])) \
        @ values['shared/wo/kernel']
    shared = (jax.nn.sigmoid(flat @ values['shared_gate/kernel'])
              * swiglu).reshape(x.shape)
    parts, landed = 0.0, 0.0
    for offset in range(0, 16, 4):
        module, params = share_of(cfg, values, offset, 4)
        y, sown = jax.jit(lambda p, x, m=module: m.apply(
            {'params': p}, x, mutable=['intermediates']))(params, x)
        assert rel(y, reference_moe(cfg, values, x, offset, 4)) < 1e-5
        parts = parts + (y - shared)
        landed += float(sown['intermediates']['moe.local_assign_share'][0])
    assert rel(parts + shared, whole) < 1e-5
    assert landed == pytest.approx(1.0)


def test_a_partly_filled_buffer_in_both_passes():
    """The megablox kernels leave the rows past the landed pairs
    undefined, forward and backward: what reaches the parameters and the
    input is the ``ragged_dot`` path's, to rounding."""
    cfg, values, x = moe_setup(tokens=(4, 128))

    def grads(impl):
        module, params = share_of(
            dataclasses.replace(cfg, moe_impl=impl), values, 4, 8)
        return jax.jit(jax.grad(
            lambda p, x: jnp.sum(module.apply({'params': p}, x) ** 2),
            argnums=(0, 1)))(params, x)

    got, want = grads('interpret'), grads('ragged')
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 1e-5


def test_data_parallel_devices_see_their_own_rows():
    """On a ``dp`` mesh every kernel runs on its device's sequences
    (``shard_map``): the logits and the counters are the one-device
    model's; a mesh with an ``ep`` axis is refused by name."""
    from jax.sharding import Mesh
    from mlcomp_tpu.parallel.sharding import logical_rules
    import flax.linen as nn
    kwargs = dict(SMALL, n_layers=2, full_attention_interval=2)
    model, params, _, tokens = seeded(kwargs)
    run = lambda m: m.apply({'params': params}, tokens,  # noqa: E731
                            mutable=['intermediates'])
    want, want_sown = jax.jit(lambda: run(model))()
    mesh = Mesh(np.array(jax.devices()[:2]), ('dp',))
    sharded = create_model('qwen3_next', mesh=mesh, **kwargs)
    with mesh, nn.logical_axis_rules(logical_rules(mesh)):
        got, got_sown = jax.jit(lambda: run(sharded))()
    assert rel(got, want) < 1e-5
    for a, b in zip(jax.tree.leaves(got_sown), jax.tree.leaves(want_sown)):
        # per-device max over mean is not the global one: a mean of two
        assert float(a) == pytest.approx(float(b), rel=0.5)
    ep = Mesh(np.array(jax.devices()[:2]), ('ep',))
    with pytest.raises(NotImplementedError, match='ep=2'):
        create_model('qwen3_next', mesh=ep, **kwargs).apply(
            {'params': params}, tokens)


# ------------------------------------------- what a `remat`ted layer holds
#: both mixers' kernels under the interpreter, 128 tokens (a flash
#: tile): the flash kernels are the program's only unnamed ones
KERNELS = dict(SMALL, attn_impl='interpret', delta_impl='interpret',
               moe_impl='ragged')


def kernel_program(**over):
    """(loss of the parameters, parameters) of the small model on the
    kernels' path."""
    model, params, _, tokens = seeded(dict(KERNELS, **over), seq=128)

    def program(p):
        logits = model.apply({'params': p}, tokens).astype(jnp.float32)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])

    return program, params


@pytest.mark.parametrize('layers', [2, 4], ids=['unscanned', 'scanned'])
def test_remat_with_the_policy_gives_the_plain_gradients(layers):
    """Both `nn.remat` sites (periods of a linear and a full layer: one
    is looped, two are scanned): the loss and every gradient leaf with
    the save-by-name policy are those of the model without `remat`."""
    over = dict(n_layers=layers, full_attention_interval=2)
    plain, params = kernel_program(**over)
    loss, grads = jax.jit(jax.value_and_grad(
        kernel_program(remat=True, **over)[0]))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(plain))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for (path, a), b in zip(weights.flat_paths(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(b)) > 0, path
        assert rel(a, b) < 1e-5, path


def test_the_routers_top_k_is_lax_top_k():
    """``routing_top_k`` reads its gradient at the named indices: the
    numbers and the gradient are ``lax.top_k``'s, to the bit."""
    probs = jax.nn.softmax(
        4 * jax.random.normal(jax.random.PRNGKey(5), (48, 16)), -1)
    weigh = jax.random.normal(jax.random.PRNGKey(6), (48, 3))

    def loss(top_k):
        def fn(p):
            top_w, top_i = top_k(p, 3)
            return jnp.sum(weigh * top_w / jnp.sum(top_w, -1, keepdims=True)
                           * (1 + top_i))
        return fn

    for got, want in zip(routing_top_k(probs, 3),
                         jax.lax.top_k(probs, 3)):
        assert (got == want).all()
    got = jax.grad(loss(routing_top_k))(probs)
    want = jax.grad(loss(jax.lax.top_k))(probs)
    assert float(jnp.abs(want).max()) > 0 and (got == want).all()


@functools.lru_cache(maxsize=None)
def backward_census(saved):
    """Equations of the gradient program of one linear and one full
    layer under `remat` holding the names ``saved``: a count by
    primitive, kernels by their name (the flash kernels have none)."""
    census = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == 'pallas_call':
                name = eqn.params['name'] or 'flash'
            if name == 'name':
                census['name:' + eqn.params['name']] += 1
            census[name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(qwen3_next, 'REMAT_SAVED', saved)
        program, params = kernel_program(
            n_layers=2, full_attention_interval=2, remat=True)
        walk(jax.make_jaxpr(jax.grad(program))(params).jaxpr)
    return census


def test_the_backward_pass_runs_no_forward_kernel_again():
    plain = backward_census(())
    held = backward_census(qwen3_next.REMAT_SAVED)
    # the forward pass, `remat`'s forward and the backward's own
    assert plain['gated_delta_prepare'] == 3
    assert plain['gated_delta_fwd'] == 2
    # forward and the backward's own; the scan forward once
    assert held['gated_delta_prepare'] == 2
    assert held['gated_delta_fwd'] == 1
    for census in plain, held:
        assert census['gated_delta_bwd_scan'] == 1
        assert census['gated_delta_prepare_bwd'] == 1
    # the two flash kernels (one backward since PR 34) once each, not
    # the forward twice
    assert plain['flash'] == 3 and held['flash'] == 2
    # a layer routes once: top-k, the argsort of the pairs
    assert plain['top_k'] == 4 and held['top_k'] == 2
    assert plain['sort'] == 4 and held['sort'] == 2


@pytest.mark.parametrize('name', qwen3_next.REMAT_SAVED)
def test_every_saved_name_is_given_and_read(name):
    """A name nothing gives, or that the backward pass does not read,
    is a silent no-op: each name of the list is on a value of the
    forward pass, and without it the gradient program computes more."""
    saved = qwen3_next.REMAT_SAVED
    held = backward_census(saved)
    assert held['name:' + name] > 0
    without = backward_census(tuple(n for n in saved if n != name))
    # a held value costs an equation of its own: the name, and the
    # `reduce_precision` that keeps XLA from merging it away
    work = lambda census: sum(  # noqa: E731
        n for key, n in census.items()
        if not key.startswith('name') and key != 'reduce_precision')
    assert work(without) > work(held)


@pytest.mark.parametrize('remat', [False, True], ids=['plain', 'remat'])
def test_the_names_leave_transformer_lm_as_it_was(remat, monkeypatch):
    """``_fa_fwd`` is ``transformer_lm``'s forward rule too, whose
    `remat` has no policy: its lowered train step with the names is,
    letter for letter, the one without them — but for the number the
    module's symbol table appends to a private function's name
    (``@_take_128`` / ``@_take_126``: a counter of the lowering, which
    two identities more advance; the compiled program has no such
    functions)."""
    import re
    from mlcomp_tpu.ops import flash_attention as fa
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    tokens = jnp.zeros((2, 128), jnp.int32)

    def lowered():
        model = create_model(
            'transformer_lm', vocab_size=64, d_model=32, n_layers=2,
            n_heads=2, d_ff=64, max_seq_len=128, dtype='float32',
            attn_impl='interpret', remat=remat)
        optimizer = make_optimizer({'name': 'adamw', 'lr': 1e-3})[0]
        state = jax.eval_shape(lambda: create_train_state(
            model, optimizer, tokens, jax.random.PRNGKey(1)))
        step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                               self_supervised=True)
        return re.sub(r'@(\w+?)_\d+\b', r'@\1',
                      step.lower(state, tokens, None).as_text())

    named, seen = lowered(), []

    def unnamed(x, name):
        seen.append(name)
        return x

    monkeypatch.setattr(fa, 'checkpoint_name', unnamed)
    assert lowered() == named
    assert {'flash_attn.qkv', 'flash_attn.out', 'flash_attn.lse'} \
        == set(seen)
    assert 'tpu_custom_call' not in named and 'while' in named
