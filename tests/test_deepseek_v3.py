"""The ``deepseek_v3`` model on the CPU, float32, seeded weights, small
sizes: loss and every leaf's gradient against the plain reference
(``benchmark/reference/deepseek_v3.py``) on the ``dense`` / ``ragged``
and the ``interpret`` paths, per layer kind and for the benchmark's five
layers; bfloat16 in place of float32 fails the same tolerance; three
AdamW steps with the load rule against the reference's ``train``; the
interleaved rotary gives the scores of the published
de-interleave-then-halves form; the latent attention's key really is
one rotary vector a token beside every head's own part; the shares'
routed parts and the shared expert, counted once, add up to the uncut
layer; the counters leave the step; what a `remat`ted layer holds by
name; and lfm2_moe's lowered train step is the parent commit's, letter
for letter (qwen3_next's is held by ``tests/test_lfm2_moe.py``)."""

import collections
import dataclasses
import hashlib
import os
import re
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
from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from mlcomp_tpu.models import create_model, deepseek_v3  # noqa: E402
from mlcomp_tpu.models.deepseek_v3 import DeepseekV3Config  # noqa: E402
from mlcomp_tpu.models.decoder_parts import (  # noqa: E402
    MoeConfig, SparseMoe, rotary,
)

#: a dense layer and two sparse ones at a small size (the benchmark's
#: five, one dense and four sparse, are one case below and the cell's
#: rehearsal): 4 of 16 experts held, top-3, a shared expert of 2 x
#: d_expert; score heads of 16 + 8 and value heads of 16 from a latent
#: of 32
SMALL = dict(
    vocab_size=64, d_model=64, n_layers=3, n_dense_layers=1, d_ff=96,
    n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_experts=16, top_k=3, d_expert=24, n_shared_experts=2,
    routed_scaling_factor=2.448, experts_held=4, expert_offset=4,
    dtype='float32')


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision('highest'):
        yield


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def seeded(model_kwargs, seed=7, gain=8.0, seq=32):
    """(module, its parameter tree and the reference's dict) with the
    benchmark's seeded weights, the kernels scaled up so that the gates
    and the router are far from their flat middle."""
    model = create_model('deepseek_v3', **model_kwargs)
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


def lm_loss(model, tokens):
    def loss(p):
        logits = model.apply({'params': p}, tokens).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1])
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
        return -jnp.mean(jnp.mean(picked[..., 0], -1))
    return loss


# ------------------------------------------------ model against reference
#: float32 on both sides: what is left is the order of additions (the
#: sorted buffer against a loop over experts, blocks of queries)
LOSS_TOL, GRAD_TOL = 2e-5, 2e-3


@pytest.mark.parametrize('case,over', [
    ('dense_layer', dict(n_layers=1, n_dense_layers=1)),
    ('sparse_layer', dict(n_layers=1, n_dense_layers=0)),
    ('five_layers_dense_ragged', dict(n_layers=5)),
    ('three_layers_remat', dict(remat=True)),
    # 2 x 64 tokens: the sorted buffer is in whole 128-row tiles
    ('three_layers_interpret', dict(
        moe_impl='interpret', remat=True, seq=64)),
    ('flash_interpret', dict(n_layers=2, attn_impl='interpret', seq=128)),
    ('flash_interpret_remat', dict(
        n_layers=2, attn_impl='interpret', remat=True, seq=128)),
    ('all_experts_held', dict(experts_held=None, expert_offset=0)),
    ('halves_not_pairs', dict(n_layers=2)),
])
def test_model_against_reference(case, over, monkeypatch):
    over = dict(over)
    if case == 'halves_not_pairs':
        # the family has one rotary form, so the class has no field
        monkeypatch.setattr(DeepseekV3Config, 'rope_interleave', False)
    seq = over.pop('seq', 32)
    kwargs = dict(SMALL, **over)
    model, params, values, tokens = seeded(kwargs, seq=seq)
    loss_gap, gaps = gaps_to_reference(model, params, values, tokens,
                                       kwargs)
    if case == 'halves_not_pairs':
        # the reference turns PAIRS: a program that turned halves on
        # these weights is another model, and the tolerance says so
        assert loss_gap > LOSS_TOL and max(gaps.values()) > GRAD_TOL
        return
    assert loss_gap < LOSS_TOL
    for leaf, value in gaps.items():
        assert value < GRAD_TOL, (leaf, value)


def gaps_to_reference(model, params, values, tokens, kwargs):
    loss, grads = jax.jit(jax.value_and_grad(lm_loss(model, tokens)))(
        params)
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, dict(kwargs), lambda x: x),
        has_aux=True))(values)
    grads = dict(weights.flat_paths(grads))
    assert set(grads) == set(want_grads)
    gaps = {}
    for leaf, want_grad in want_grads.items():
        norm = float(jnp.linalg.norm(want_grad))
        # every leaf but the selection bias has a gradient
        assert (norm > 0) != leaf.endswith('expert_bias'), leaf
        gaps[leaf] = float(jnp.linalg.norm(
            grads[leaf].astype(jnp.float32) - want_grad)) / max(norm, 1e-30)
    return abs(float(loss) - float(want)) / abs(float(want)), gaps


def test_bfloat16_in_place_of_float32_fails_the_tolerance():
    """The tolerances above are tight enough that computing in the
    precision below fails them: the same three layers in bfloat16."""
    kwargs = dict(SMALL, dtype='bfloat16')
    loss_gap, gaps = gaps_to_reference(*seeded(kwargs), kwargs)
    assert loss_gap > LOSS_TOL
    assert max(gaps.values()) > GRAD_TOL


# ------------------------------------------------------ latent attention
def test_interleaved_rotary_gives_the_published_forms_scores():
    """``rope_interleave``: the published code de-interleaves the rotary
    dimensions (evens first, then odds) and turns halves; turning
    neighbours in place gives the same q . k for every pair of
    positions, and is the halves form on the de-interleaved input,
    element for element."""
    b, t, h, d = 2, 16, 3, 8
    kq, kk = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, 1, d))
    order = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    pairs = [rotary(x, 1e6, d, interleaved=True) for x in (q, k)]
    halves = [rotary(x[..., order], 1e6, d) for x in (q, k)]
    for a, b_ in zip(pairs, halves):
        np.testing.assert_allclose(a[..., order], b_, rtol=1e-6, atol=1e-6)
    score = lambda q, k: jnp.einsum('bqhd,bkgd->bhqk', q, k)  # noqa: E731
    np.testing.assert_allclose(score(*pairs), score(*halves),
                               rtol=1e-5, atol=1e-5)
    # and it is not the halves form on the input as it lies
    assert rel(pairs[0], rotary(q, 1e6, d)) > 0.1
    # position 0 is not turned; the part past rotary_dim passes
    np.testing.assert_allclose(pairs[0][:, 0], q[:, 0], rtol=1e-6)
    wide = jnp.concatenate([q, q], -1)
    np.testing.assert_array_equal(
        rotary(wide, 1e6, d, interleaved=True)[..., d:], q)
    np.testing.assert_allclose(
        ref.rotary_pairs(q, 1e6), pairs[0], rtol=1e-6, atol=1e-6)


def test_the_rotary_key_is_one_vector_shared_by_every_head(monkeypatch):
    """What reaches ``fused_attention``: q and k 24 wide, v 16 wide;
    the last 8 of every head's key are the same vector, the first 16
    differ head by head; the scale is the score head's."""
    from mlcomp_tpu.ops import flash_attention
    seen = {}
    plain = flash_attention.fused_attention

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, kw=kw)
        return plain(q, k, v, **kw)

    monkeypatch.setattr(flash_attention, 'fused_attention', spy)
    kwargs = dict(SMALL, n_layers=1)
    model, params, _, tokens = seeded(kwargs)
    model.apply({'params': params}, tokens)
    assert seen['q'].shape == seen['k'].shape == (2, 32, 4, 24)
    assert seen['v'].shape == (2, 32, 4, 16)
    assert seen['kw'].get('scale') is None and seen['kw']['causal']
    k = np.asarray(seen['k'])
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, :, head, 16:], k[:, :, 0, 16:])
        assert np.abs(k[:, :, head, :16] - k[:, :, 0, :16]).max() > 0.01


# ------------------------------------------------------------ expert layer
def moe_setup(seed=11, tokens=(2, 24), **over):
    """A layer of 8 experts, top-3, and a shared expert of 2 x 24."""
    cfg = DeepseekV3Config(**dict(
        SMALL, n_experts=8, top_k=3, experts_held=8, expert_offset=0,
        **over))
    d, f, e, fs = cfg.d_model, cfg.d_expert, cfg.n_experts, cfg.d_shared
    spec = {'router': ((d, e), jnp.float32),
            'expert_bias': ((e,), jnp.float32),
            'wi_gate': ((e, d, f), jnp.float32),
            'wi_up': ((e, d, f), jnp.float32),
            'wo': ((e, f, d), jnp.float32),
            'shared/wi_gate/kernel': ((d, fs), jnp.float32),
            'shared/wi_up/kernel': ((d, fs), jnp.float32),
            'shared/wo/kernel': ((fs, d), jnp.float32)}
    values = {k: 8 * v for k, v in weights.make_params(seed, spec).items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), tokens + (d,))
    return cfg, values, x


def share_of(cfg, values, offset, held, **over):
    """(module, params) of the share [offset, offset + held)."""
    cfg = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    tree = {'router': values['router'],
            'expert_bias': values['expert_bias'],
            'shared': {n: {'kernel': values[f'shared/{n}/kernel']}
                       for n in ('wi_gate', 'wi_up', 'wo')}}
    for name in ('wi_gate', 'wi_up', 'wo'):
        tree[name] = values[name][offset:offset + held]
    moe = dataclasses.replace(MoeConfig.of(cfg), **over)
    if not moe.d_shared:
        del tree['shared']
    return SparseMoe(moe), tree


def apply_moe(module, params, x):
    return jax.jit(lambda p, x: module.apply(
        {'params': p}, x, mutable=['intermediates']))(params, x)


def reference_parts(cfg, values, x, offset, held):
    """(the held experts' routed part, the shared expert) by the
    reference."""
    model = dict(dataclasses.asdict(cfg), experts_held=held,
                 expert_offset=offset)
    p = {f'moe/{k}': v for k, v in values.items()}
    for name in ('wi_gate', 'wi_up', 'wo'):
        p[f'moe/{name}'] = values[name][offset:offset + held]
    ein = lambda eq, a, b: jnp.einsum(  # noqa: E731
        eq, a, b, precision='highest')
    return jax.jit(lambda x, p: (
        ref.routed_ffn(x, p, ref._sizes(model), ein)[0],
        ref.shared_ffn(x, p, ein)))(x, p)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2: the ROUTED parts that the four
    shares give, with the shared expert — which every share computes
    alike — counted once, add up to what the uncut reference gives for
    the whole layer; every (token, expert) pair lands on one share."""
    cfg, values, x = moe_setup()
    routed_whole, shared = reference_parts(cfg, values, x, 0, 8)
    whole = routed_whole + shared
    assert rel(shared, whole) > 0.05 and rel(routed_whole, whole) > 0.05
    parts, landed = 0.0, 0.0
    for offset in range(0, 8, 2):
        module, params = share_of(cfg, values, offset, 2)
        y, sown = apply_moe(module, params, x)
        routed, _ = reference_parts(cfg, values, x, offset, 2)
        # a share's output is its routed part plus the shared expert
        assert rel(y, routed + shared) < 1e-5
        assert rel(y, whole) > 0.05             # a part, not the whole
        parts = parts + (y - shared)
        landed += float(sown['intermediates']['moe.local_assign_share'][0])
        assert float(sown['intermediates']['moe.dropped'][0]) == 0
    assert rel(parts + shared, whole) < 1e-5
    assert landed == pytest.approx(1.0)


def test_the_weights_are_the_scores_renormalised_times_the_factor():
    """2.448 is the first ``routed_scaling_factor`` that is not 1: a
    token's weights over ALL experts sum to it (the renormalising
    epsilon is 1e-20), whatever the bias chose."""
    cfg, values, x = moe_setup(d_expert=1, n_shared_experts=0)
    e, d = cfg.n_experts, cfg.d_model
    x = x.at[..., 0].set(1.0)
    values['router'] = values['router'].at[0].set(0.0)
    tree = {'router': values['router'],
            'expert_bias': values['expert_bias'],
            'wi_gate': jnp.zeros((e, d, 1)).at[:, 0, 0].set(10.0),
            'wi_up': jnp.zeros((e, d, 1)).at[:, 0, 0].set(1.0),
            'wo': jnp.eye(e, d)[:, None, :]}     # row e of wo marks e
    y, _ = apply_moe(SparseMoe(MoeConfig.of(cfg)), tree, x)
    by_expert = np.asarray(y.reshape(-1, d)[:, :e]) / float(
        jax.nn.silu(10.0))
    assert ((by_expert > 0).sum(-1) == cfg.top_k).all()
    np.testing.assert_allclose(by_expert.sum(-1), 2.448, rtol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum(
        'nd,de->ne', x.reshape(-1, d), values['router'],
        precision='highest')))
    top = np.argsort(-(scores + np.asarray(values['expert_bias'])),
                     -1)[:, :cfg.top_k]
    want = np.zeros_like(scores)
    np.put_along_axis(want, top, np.take_along_axis(scores, top, -1), -1)
    want = 2.448 * want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(by_expert, want, rtol=2e-5, atol=1e-7)


# ------------------------------------------------------- the train step
def train_step_of(kwargs, optimizer_spec, seq=32):
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    model, _, values, tokens = seeded(kwargs, seq=seq)
    optimizer = make_optimizer(optimizer_spec)[0]
    state = jax.jit(lambda key: create_train_state(
        model, optimizer, tokens, key))(jax.random.PRNGKey(1))
    state = state.replace(params=weights.replace_leaves(
        state.params, values))
    step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                           self_supervised=True)
    return step, state, tokens


@pytest.mark.parametrize('case,over', [
    ('three_layers', dict()),
    ('interpret_remat', dict(moe_impl='interpret', remat=True, seq=64))])
def test_three_steps_with_the_load_rule_against_the_reference(case, over):
    """Three steps of the program's train step (AdamW, then the bias's
    load rule at ``expert_bias_update_rate``) against the reference's
    ``train``: every step's loss, the first gradient's and the three
    steps' change by leaf; the bias has no gradient and moves by more
    than the rate."""
    lr, decay, rate = 1e-2, 0.1, 0.01
    over = dict(over)
    seq = over.pop('seq', 32)
    kwargs = dict(SMALL, expert_bias_update_rate=rate, **over)
    opt = {'name': 'adamw', 'lr': lr, 'b1': 0.9, 'b2': 0.95,
           'weight_decay': decay}
    step, state, tokens = train_step_of(kwargs, opt, seq)
    before = {k: np.asarray(v) for k, v in weights.flat_paths(state.params)}
    biases = sorted(k for k in before if k.endswith('expert_bias'))
    assert len(biases) == 2
    feeds = [{'feed': np.asarray(tokens)}] * 3
    want = ref.train({'model': dict(kwargs), 'optimizer': opt},
                     {k: jnp.asarray(v) for k, v in before.items()}, feeds)
    losses = []
    for i in range(3):
        state, metrics = step(state, tokens, None)
        losses.append(float(metrics['loss']))
        if i == 0:
            moment = dict(weights.flat_paths(state.opt_state[0].mu))
            for leaf, norm in want['grad_norm'].items():
                got = float(jnp.linalg.norm(moment[leaf])) / (1 - 0.9)
                assert got == pytest.approx(norm, rel=2e-3, abs=1e-12), leaf
    # the first loss by the forward tolerance; after an Adam step of
    # +-lr a leaf's element whose gradient is round-off takes either
    # sign, so the later losses get five times that room (bfloat16
    # reads 1e-2)
    assert losses[0] == pytest.approx(want['loss'][0], rel=LOSS_TOL)
    np.testing.assert_allclose(losses, want['loss'], rtol=5 * LOSS_TOL)
    after = dict(weights.flat_paths(state.params))
    for leaf, value in want['delta_norm'].items():
        got = float(np.linalg.norm(
            (np.asarray(after[leaf], np.float64) - before[leaf]).ravel()))
        assert got == pytest.approx(value, rel=2e-3), leaf
    for leaf in biases:
        assert want['grad_norm'][leaf] == 0
        assert want['delta_norm'][leaf] > rate


def test_counters_leave_the_step():
    """``mla_attn.rows``: tokens x attention layers; the router's three
    counters; nothing of the other decoders'."""
    step, state, tokens = train_step_of(
        dict(SMALL, moe_buffer_factor=4.0), {'name': 'adamw', 'lr': 1e-3})
    _, metrics = step(state, tokens, None)
    assert float(metrics['mla_attn.rows']) == tokens.size * 3
    assert float(metrics['moe.dropped']) == 0
    assert 0 < float(metrics['moe.local_assign_share']) < 1
    assert float(metrics['moe.load_max_over_mean']) >= 1
    assert 'gated_delta.chunks' not in metrics
    assert 'short_conv.rows' not in metrics


# ------------------------------------------------------------------ remat
def backward_names(saved):
    """The names the backward pass of a `remat`ted model READS from the
    forward pass, with the policy holding ``saved``."""
    model, params, _, tokens = seeded(dict(SMALL, remat=True))
    before = deepseek_v3.REMAT_SAVED
    deepseek_v3.REMAT_SAVED = saved
    try:
        jaxpr = jax.make_jaxpr(jax.grad(lm_loss(model, tokens)))(params)
    finally:
        deepseek_v3.REMAT_SAVED = before
    census = collections.Counter()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == 'name':
                census[eqn.params['name']] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return census


def test_remat_with_the_policy_gives_the_plain_gradients():
    model, params, _, tokens = seeded(SMALL)
    plain = jax.jit(jax.grad(lm_loss(model, tokens)))(params)
    held = jax.jit(jax.grad(lm_loss(
        create_model('deepseek_v3', **dict(SMALL, remat=True)), tokens)))(
        params)
    for (leaf, a), (_, b) in zip(weights.flat_paths(held),
                                 weights.flat_paths(plain)):
        assert float(jnp.abs(a - b).max()) <= \
            1e-5 * float(jnp.abs(b).max()) + 1e-12, leaf


@pytest.mark.parametrize('name', [
    n for n in deepseek_v3.REMAT_SAVED if not n.startswith('flash_attn.')])
def test_every_saved_name_is_given_in_the_forward_pass(name):
    """A name in ``REMAT_SAVED`` that no value carries holds nothing:
    each is given where the model runs on the CPU (the flash names are
    given by the kernel's forward rule, which
    ``tests/test_qwen3_next.py`` holds). With the name held, the
    backward pass makes the value fewer times."""
    with_all = backward_names(deepseek_v3.REMAT_SAVED)
    without = backward_names(tuple(
        n for n in deepseek_v3.REMAT_SAVED if n != name))
    assert with_all[name] > 0
    assert without[name] > with_all[name]


# ------------------------------------------- the model it shares code with
#: sha256 of lfm2_moe's lowered train step (the five layers of
#: tests/test_lfm2_moe.py's SMALL with the load rule and buffer factor 4,
#: float32, 2 x 32 tokens, AdamW), written by the function below,
#: private functions' counters taken off. Read at a21dd94 until
#: ``SparseMoe`` learnt to move its rows by gathers: at these shapes the
#: buffer holds a row for every (token, expert) pair (128), so the layer
#: takes ``through_gathers`` where it scattered, and the hashes were
#: written anew with that change; ``shared_gate``, the interleaved
#: ``rotary`` and the flash kernels' value head size still trace to what
#: they did with lfm2's settings.
LFM2_STEP_AT_PARENT = {
    False: '1f7cf61e4c1002e8f2349670f781484e883ca444d72e16ddc7867544fe5ff88c',
    True: '240c8d29390700c71a46b3832e4a403e8b05f02c64b9a4dc09b53fee0d06aba3',
}


@pytest.mark.parametrize('remat', [False, True], ids=['plain', 'remat'])
def test_lfm2_moes_lowered_step_is_the_parents(remat):
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    with jax.default_matmul_precision(None):    # as the parent was read
        tokens = jnp.zeros((2, 32), jnp.int32)
        model = create_model(
            'lfm2_moe', vocab_size=64, d_model=128,
            layer_types=['conv', 'full_attention', 'conv', 'conv', 'conv'],
            n_dense_layers=1, d_ff=64, n_heads=4, n_kv_heads=2,
            head_dim=16, n_experts=16, top_k=2, d_expert=16,
            experts_held=8, expert_offset=4, dtype='float32',
            expert_bias_update_rate=0.001, moe_buffer_factor=4.0,
            remat=remat)
        optimizer = make_optimizer({'name': 'adamw', 'lr': 1e-3})[0]
        state = jax.eval_shape(lambda: create_train_state(
            model, optimizer, tokens, jax.random.PRNGKey(1)))
        step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                               self_supervised=True)
        text = re.sub(r'@(\w+?)_\d+\b', r'@\1',
                      step.lower(state, tokens, None).as_text())
    assert 'stablehlo.sort' in text          # the router's top-k is in it
    assert hashlib.sha256(text.encode()).hexdigest() \
        == LFM2_STEP_AT_PARENT[remat]
