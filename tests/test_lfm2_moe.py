"""The ``lfm2_moe`` model on the CPU, float32, seeded weights, small
sizes: loss and every leaf's gradient against the plain reference
(``benchmark/reference/lfm2_moe.py``) on the ``xla`` / ``ragged`` and the
``interpret`` paths, per layer kind and for the benchmark's five layers;
bfloat16 in place of float32 fails the same tolerance; the selection
bias moves the selection and never the weights; the shares add up to the
uncut layer; the tied leaf's gradient is the sum of its two uses;
``expert_bias`` gets no gradient and moves by decay alone, and with a
rate by the load rule besides, as the reference moves it; the counters
leave the step; what a `remat`ted layer holds by name; and qwen3_next's
lowered train step is the parent commit's, letter for letter."""

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
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from mlcomp_tpu.models import create_model, lfm2_moe  # noqa: E402
from mlcomp_tpu.models.lfm2_moe import Lfm2MoeConfig  # noqa: E402
from mlcomp_tpu.models.decoder_parts import (  # noqa: E402
    MoeConfig, SparseMoe, row_tile,
)

FIVE = ['conv', 'full_attention', 'conv', 'conv', 'conv']
SMALL = dict(
    vocab_size=64, d_model=128, layer_types=FIVE, n_dense_layers=1,
    d_ff=64, n_heads=4, n_kv_heads=2, head_dim=16, n_experts=16, top_k=2,
    d_expert=16, experts_held=8, expert_offset=4, dtype='float32')


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
    model = create_model('lfm2_moe', **model_kwargs)
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
    ('dense_conv_layer', dict(layer_types=['conv'], n_dense_layers=1)),
    ('sparse_conv_layer', dict(layer_types=['conv'], n_dense_layers=0)),
    ('sparse_attention_layer',
     dict(layer_types=['full_attention'], n_dense_layers=0)),
    ('five_layers_xla_ragged', dict()),
    ('five_layers_remat', dict(remat=True)),
    ('five_layers_interpret', dict(
        conv_impl='interpret', moe_impl='interpret', remat=True)),
    ('flash_interpret', dict(
        layer_types=['full_attention'], n_dense_layers=0,
        attn_impl='interpret', seq=128)),
    ('all_experts_held', dict(experts_held=None, expert_offset=0)),
])
def test_model_against_reference(case, over):
    over = dict(over)
    seq = over.pop('seq', 32)
    kwargs = dict(SMALL, **over)
    model, params, values, tokens = seeded(kwargs, seq=seq)
    loss_gap, gaps = gaps_to_reference(model, params, values, tokens,
                                       kwargs)
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
    precision below fails them: the same five layers in bfloat16."""
    kwargs = dict(SMALL, dtype='bfloat16')
    loss_gap, gaps = gaps_to_reference(*seeded(kwargs), kwargs)
    assert loss_gap > LOSS_TOL
    assert max(gaps.values()) > GRAD_TOL


# ----------------------------------------------------- the tied embedding
def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(monkeypatch):
    """``embed`` is the table and the head: its gradient is the gather's
    plus the head product's. The two are told apart by giving the
    model's one ``jnp.take`` a second copy of the table to read."""
    kwargs = dict(SMALL, layer_types=['conv', 'full_attention'])
    model, params, values, tokens = seeded(kwargs)
    tied = jax.jit(jax.grad(lm_loss(model, tokens)))(params)
    assert 'lm_head' not in params

    class GatherFrom:
        """``jax.numpy`` with ``take`` reading ``table``."""
        def __init__(self, table):
            self.table = table

        def __getattr__(self, name):
            return getattr(jnp, name)

        def take(self, _, indices, axis=0):
            return jnp.take(self.table, indices, axis=axis)

    def two_uses(p, gathered):
        monkeypatch.setattr(lfm2_moe, 'jnp', GatherFrom(gathered))
        try:
            return lm_loss(model, tokens)(p)
        finally:
            monkeypatch.undo()

    head, gather = jax.grad(two_uses, (0, 1))(params, params['embed'])
    for part in (gather, head['embed']):
        assert rel(part, tied['embed']) > 0.05     # neither alone
    assert rel(gather + head['embed'], tied['embed']) < 1e-5
    for leaf, want in weights.flat_paths(head):
        if leaf != 'embed':
            got = dict(weights.flat_paths(tied))[leaf]
            assert float(jnp.abs(got - want).max()) <= \
                1e-5 * float(jnp.abs(want).max()), leaf


# ------------------------------------------------------------ expert layer
def moe_setup(seed=11, tokens=(2, 24), **over):
    cfg = Lfm2MoeConfig(**dict(
        SMALL, n_experts=32, top_k=4, experts_held=32, expert_offset=0,
        **over))
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    spec = {'router': ((d, e), jnp.float32),
            'expert_bias': ((e,), jnp.float32),
            'wi_gate': ((e, d, f), jnp.float32),
            'wi_up': ((e, d, f), jnp.float32),
            'wo': ((e, f, d), jnp.float32)}
    values = {k: 8 * v for k, v in weights.make_params(seed, spec).items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), tokens + (d,))
    return cfg, values, x


def share_of(cfg, values, offset, held):
    """(module, params) of the share [offset, offset + held)."""
    cfg = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    tree = {'router': values['router'],
            'expert_bias': values['expert_bias']}
    for name in ('wi_gate', 'wi_up', 'wo'):
        tree[name] = values[name][offset:offset + held]
    return SparseMoe(MoeConfig.of(cfg)), tree


def apply_moe(module, params, x):
    return jax.jit(lambda p, x: module.apply(
        {'params': p}, x, mutable=['intermediates']))(params, x)


def reference_moe(cfg, values, x, offset, held):
    model = dict(dataclasses.asdict(cfg), experts_held=held,
                 expert_offset=offset)
    p = {f'moe/{k}': v for k, v in values.items()}
    for name in ('wi_gate', 'wi_up', 'wo'):
        p[f'moe/{name}'] = values[name][offset:offset + held]
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision='highest')  # noqa: E731,E501
    return jax.jit(lambda x, p: ref.sparse_ffn(
        x, p, ref._sizes(model), ein)[0])(x, p)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts as 4 shares of 8 — the benchmark's cut: the parts of
    the sparse ffn that the four shares give add up to what the uncut
    reference gives for the whole layer (there is no shared expert to
    count once), and every (token, expert) pair lands on one share."""
    cfg, values, x = moe_setup()
    whole = reference_moe(cfg, values, x, 0, 32)
    parts, landed = 0.0, 0.0
    for offset in range(0, 32, 8):
        module, params = share_of(cfg, values, offset, 8)
        y, sown = apply_moe(module, params, x)
        assert rel(y, reference_moe(cfg, values, x, offset, 8)) < 1e-5
        assert rel(y, whole) > 0.1              # a part, not the whole
        parts = parts + y
        landed += float(sown['intermediates']['moe.local_assign_share'][0])
        assert float(sown['intermediates']['moe.dropped'][0]) == 0
    assert rel(parts, whole) < 1e-5
    assert landed == pytest.approx(1.0)


def routing_of(cfg, values, x):
    """(indices [N,k] sorted, weights by expert [N,E]) as the program
    routes: from a layer whose experts write one-hot rows."""
    e, d = cfg.n_experts, cfg.d_model
    cfg = dataclasses.replace(cfg, d_expert=1)
    tree = {'router': values['router'],
            'expert_bias': values['expert_bias'],
            # silu(10) * 1 = ~10 for every token; row e of wo marks e
            'wi_gate': jnp.zeros((e, d, 1)), 'wi_up': jnp.zeros((e, d, 1)),
            'wo': jnp.eye(e, d)[:, None, :]}
    # gate = up = a constant: x gets a constant channel that only the
    # experts read
    x = x.at[..., 0].set(1.0)
    tree['wi_gate'] = tree['wi_gate'].at[:, 0, 0].set(10.0)
    tree['wi_up'] = tree['wi_up'].at[:, 0, 0].set(1.0)
    y, _ = apply_moe(SparseMoe(MoeConfig.of(cfg)), tree, x)
    by_expert = y.reshape(-1, d)[:, :e] / float(jax.nn.silu(10.0))
    return by_expert, x


def test_the_bias_moves_the_selection_and_never_the_weights():
    """A bias that lifts expert 3 over every other: 3 enters every
    token's selection, the expert it displaced leaves it, and every
    chosen expert's weight is its UNBIASED sigmoid score renormalised
    over the chosen four (+ 1e-6) — the bias is nowhere in the
    weights."""
    cfg, values, x = moe_setup(tokens=(2, 16))
    values['router'] = values['router'].at[0].set(0.0)  # channel 0 is ours
    lifted = dict(values, expert_bias=values['expert_bias'].at[3].set(5.0))
    plain, x1 = routing_of(cfg, values, x)
    moved, _ = routing_of(cfg, lifted, x)
    scores = jax.nn.sigmoid(jnp.einsum(
        'nd,de->ne', x1.reshape(-1, cfg.d_model), values['router'],
        precision='highest'))
    for weights_by_expert, bias in ((plain, values['expert_bias']),
                                    (moved, lifted['expert_bias'])):
        chosen = np.asarray(weights_by_expert) > 0
        assert (chosen.sum(-1) == cfg.top_k).all()
        top = np.argsort(-np.asarray(scores + bias), -1)[:, :cfg.top_k]
        want = np.zeros_like(chosen)
        np.put_along_axis(want, top, True, -1)
        np.testing.assert_array_equal(chosen, want)
        unbiased = np.where(chosen, np.asarray(scores), 0.0)
        unbiased = unbiased / (unbiased.sum(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(weights_by_expert, unbiased,
                                   rtol=2e-5, atol=1e-7)
    assert (np.asarray(moved)[:, 3] > 0).all()
    assert not (np.asarray(plain)[:, 3] > 0).all()
    # one expert a token changed where 3 was not chosen before
    changed = ((np.asarray(plain) > 0) != (np.asarray(moved) > 0)).sum(-1)
    assert set(changed.tolist()) <= {0, 2} and changed.max() == 2


def train_step_of(kwargs, optimizer_spec):
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    model, _, values, tokens = seeded(kwargs)
    optimizer = make_optimizer(optimizer_spec)[0]
    state = jax.jit(lambda key: create_train_state(
        model, optimizer, tokens, key))(jax.random.PRNGKey(1))
    state = state.replace(params=weights.replace_leaves(
        state.params, values))
    step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                           self_supervised=True)
    return step, state, tokens


def test_expert_bias_has_no_gradient_and_moves_by_decay_alone():
    lr, decay = 1e-2, 0.1
    step, state, tokens = train_step_of(SMALL, {
        'name': 'adamw', 'lr': lr, 'b1': 0.9, 'b2': 0.95,
        'weight_decay': decay})
    before = {k: np.asarray(v) for k, v in weights.flat_paths(state.params)}
    state, _ = step(state, tokens, None)
    after = dict(weights.flat_paths(state.params))
    moments = dict(weights.flat_paths(state.opt_state[0].mu)) \
        if hasattr(state.opt_state[0], 'mu') else None
    biases = [k for k in before if k.endswith('expert_bias')]
    assert len(biases) == 4
    for leaf in biases:
        np.testing.assert_allclose(
            after[leaf], before[leaf] * (1 - lr * decay), rtol=1e-6)
        if moments is not None:
            assert float(jnp.abs(moments[leaf]).max()) == 0
    # a leaf with a gradient moves by about lr whatever its size
    router = 'layer_1/moe/router'
    assert float(jnp.abs(after[router] - before[router]).max()) > 0.5 * lr


@pytest.mark.parametrize('case,over', [
    ('five_layers', dict()),
    ('interpret_remat', dict(conv_impl='interpret', moe_impl='interpret',
                             remat=True))])
def test_the_load_rule_moves_expert_bias_as_the_reference_does(case, over):
    """With ``expert_bias_update_rate`` the bias moves, after AdamW's
    decay, by the rate: up where an expert got fewer of the step's
    pairs than the mean over ALL experts (held or not), down where it
    got more — three steps of the program's train step against the
    reference's ``train``, leaf for leaf, and the first step against
    the rule written out from the router's own scores."""
    lr, decay, rate = 1e-2, 0.1, 0.01
    kwargs = dict(SMALL, expert_bias_update_rate=rate, **over)
    opt = {'name': 'adamw', 'lr': lr, 'b1': 0.9, 'b2': 0.95,
           'weight_decay': decay}
    step, state, tokens = train_step_of(kwargs, opt)
    before = {k: np.asarray(v) for k, v in weights.flat_paths(state.params)}
    biases = sorted(k for k in before if k.endswith('expert_bias'))
    assert len(biases) == 4
    _, loads = jax.jit(lambda p: ref.loss_fn(
        p, tokens, dict(kwargs), lambda x: x))(
        {k: jnp.asarray(v) for k, v in before.items()})
    assert sorted(loads) == biases
    feeds = [{'feed': np.asarray(tokens)}] * 3
    want = ref.train({'model': dict(kwargs), 'optimizer': opt},
                     {k: jnp.asarray(v) for k, v in before.items()}, feeds)
    for i in range(3):
        state, _ = step(state, tokens, None)
        if i == 0:
            after = dict(weights.flat_paths(state.params))
            for leaf in biases:
                load = np.asarray(loads[leaf])
                assert load.sum() == tokens.size * kwargs['top_k']
                moved = rate * np.sign(load.mean() - load)
                assert np.abs(moved).max() == np.float32(rate)
                np.testing.assert_allclose(
                    after[leaf], before[leaf] * (1 - lr * decay) + moved,
                    rtol=1e-6, atol=1e-9)
    after = dict(weights.flat_paths(state.params))
    for leaf, value in want['delta_norm'].items():
        got = float(np.linalg.norm(
            (np.asarray(after[leaf], np.float64)
             - before[leaf]).ravel()))
        assert got == pytest.approx(value, rel=2e-3), leaf
    for leaf in biases:
        assert want['grad_norm'][leaf] == 0
        assert want['delta_norm'][leaf] > rate


def test_leaf_updates_are_added_where_they_were_sown():
    """``train/loop.py``'s ``_add_leaf_updates``: a sown ``(value,)``
    lands on the leaf of its path, boxed or not, and nowhere else."""
    from flax.core import meta as flax_meta
    from mlcomp_tpu.train.loop import _add_leaf_updates
    boxed = flax_meta.Partitioned(jnp.ones(3), names=(None,))
    params = {'a': {'bias': boxed, 'w': jnp.ones(2)}, 'b': jnp.zeros(1)}
    new = _add_leaf_updates(
        params, {'a': {'bias': (jnp.array([1.0, -1.0, 0.0]),)}})
    assert isinstance(new['a']['bias'], flax_meta.Partitioned)
    np.testing.assert_array_equal(new['a']['bias'].value, [2.0, 0.0, 1.0])
    assert new['a']['w'] is params['a']['w'] and new['b'] is params['b']
    assert jax.tree.structure(new) == jax.tree.structure(params)


def test_counters_leave_the_step():
    """``moe.dropped`` 0 at buffer factor 4 (with top-2 of which half
    are held, 4 x the even share is the worst case), the router's
    counters, and ``short_conv.rows``: tokens x conv layers."""
    step, state, tokens = train_step_of(
        dict(SMALL, moe_buffer_factor=4.0), {'name': 'adamw', 'lr': 1e-3})
    _, metrics = step(state, tokens, None)
    assert float(metrics['moe.dropped']) == 0
    assert float(metrics['short_conv.rows']) == tokens.size * 4
    assert 0 < float(metrics['moe.local_assign_share']) < 1
    assert float(metrics['moe.load_max_over_mean']) >= 1
    assert 'gated_delta.chunks' not in metrics


@pytest.mark.parametrize('even_share,rows,tile', [
    (320, 40960, 128),      # qwen3-next-80b-a3b.steady
    (2048, 65536, 512),     # lfm2-8b-a1b.steady
    (511, 65536, 128), (512, 65536, 512), (2048, 65536 + 128, 128),
    (4, 48, 128), (8192, 16384, 512)],
    ids=['qwen_cell', 'lfm2_cell', 'under_a_wide_tile', 'one_wide_tile',
         'not_in_512s', 'tiny', 'all_held'])
def test_the_grouped_products_row_tile_follows_the_even_share(
        even_share, rows, tile):
    """128 rows where an expert's even share is a few hundred — what the
    qwen cell ran before there was a choice — and 512 where the share
    fills whole tiles of 512 and they divide the buffer."""
    assert row_tile(even_share, rows) == tile


# ------------------------------------------------------------------ remat
def backward_names(saved):
    """The names the backward pass of a `remat`ted model READS from the
    forward pass, with the policy holding ``saved``."""
    import collections
    model, params, _, tokens = seeded(dict(SMALL, remat=True))
    before = lfm2_moe.REMAT_SAVED
    lfm2_moe.REMAT_SAVED = saved
    try:
        jaxpr = jax.make_jaxpr(jax.grad(lm_loss(model, tokens)))(params)
    finally:
        lfm2_moe.REMAT_SAVED = before
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
        create_model('lfm2_moe', **dict(SMALL, remat=True)), tokens)))(
        params)
    for (leaf, a), (_, b) in zip(weights.flat_paths(held),
                                 weights.flat_paths(plain)):
        assert float(jnp.abs(a - b).max()) <= \
            1e-5 * float(jnp.abs(b).max()) + 1e-12, leaf


@pytest.mark.parametrize('name', [
    n for n in lfm2_moe.REMAT_SAVED if not n.startswith('flash_attn.')])
def test_every_saved_name_is_given_in_the_forward_pass(name):
    """A name in ``REMAT_SAVED`` that no value carries holds nothing:
    each is given where the model runs on the CPU (the flash names are
    given by the kernel's forward rule, which
    ``tests/test_qwen3_next.py`` holds). With the name held, the
    backward pass makes the value fewer times."""
    with_all = backward_names(lfm2_moe.REMAT_SAVED)
    without = backward_names(tuple(
        n for n in lfm2_moe.REMAT_SAVED if n != name))
    assert with_all[name] > 0
    assert without[name] > with_all[name]


# ------------------------------------------- the model it shares code with
#: sha256 of qwen3_next's lowered train step (SMALL of
#: tests/test_qwen3_next.py, float32, 2 x 32 tokens, AdamW), written by
#: the function below, private functions' counters taken off. Read at
#: 473cefc until ``SparseMoe`` learnt to move its rows by gathers: at
#: these shapes the worst-case buffer holds a row for every (token,
#: expert) pair (128), so the layer takes ``through_gathers`` where it
#: scattered, and the hashes were written anew with that change; the
#: score function, the selection bias, the epsilon, the scaling factor,
#: the optional shared expert and the two names still trace to nothing
#: with qwen's settings.
QWEN_STEP_AT_PARENT = {
    False: '194ac99bb115bfca716ca3acfd43bcd0b8591787e6cc42ec6c1a826ec829963b',
    True: '5aa05baabf4be2e2f1b1e9a18a302a33f9d38960f6424634135e20cdf34c5631',
}


@pytest.mark.parametrize('remat', [False, True], ids=['plain', 'remat'])
def test_qwen3_nexts_lowered_step_is_the_parents(remat):
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    with jax.default_matmul_precision(None):    # as the parent was read
        tokens = jnp.zeros((2, 32), jnp.int32)
        model = create_model(
            'qwen3_next', vocab_size=64, d_model=32, n_layers=4, n_heads=4,
            n_kv_heads=2, head_dim=16, linear_key_heads=2,
            linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
            n_experts=16, top_k=2, d_expert=16, d_shared=16,
            experts_held=8, expert_offset=4, dtype='float32',
            delta_chunk=16, remat=remat)
        optimizer = make_optimizer({'name': 'adamw', 'lr': 1e-3})[0]
        state = jax.eval_shape(lambda: create_train_state(
            model, optimizer, tokens, jax.random.PRNGKey(1)))
        step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                               self_supervised=True)
        text = re.sub(r'@(\w+?)_\d+\b', r'@\1',
                      step.lower(state, tokens, None).as_text())
    assert 'stablehlo.sort' in text          # the router's top-k is in it
    assert hashlib.sha256(text.encode()).hexdigest() \
        == QWEN_STEP_AT_PARENT[remat]
