"""The ``ouro`` model on the CPU, float32, seeded weights, small sizes:
loss and every leaf's gradient against the plain reference
(``benchmark/reference/ouro.py``) — bfloat16 in place of float32, or the
loss without its entropy term, fails the same tolerance; one recurrent
step is a plain sandwich-normed decoder; the parameters do not grow
with the recurrent steps; the exit distribution sums to 1; the expected
loss against a hand computation on two tokens; found by name; a tiny
``jax_train`` through the executor; the counters leave the step; the
op-block table of a small step."""

import collections
import math
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
from benchmark.reference import ouro as ref  # noqa: E402
from mlcomp_tpu.models import create_model, model_names, ouro  # noqa: E402
from mlcomp_tpu.models.base import param_count  # noqa: E402
from mlcomp_tpu.train.loop import (  # noqa: E402
    ENTROPY_WEIGHT, LOSSES, create_train_state, loss_for_task,
    looped_lm_ce, make_train_step,
)
from mlcomp_tpu.train.optim import make_optimizer  # noqa: E402

#: two layers run four times, 4 heads of 16 over 4 key-value heads
SMALL = dict(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
             n_kv_heads=4, head_dim=16, d_ff=96, ut_steps=4,
             dtype='float32')
BETA = ENTROPY_WEIGHT


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision('highest'):
        yield


def seeded(model_kwargs, seed=7, seq=32):
    """(module, its parameter tree, the reference's dict, tokens) with
    the benchmark's seeded weights."""
    model = create_model('ouro', **model_kwargs)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, seq), 0,
                                model_kwargs['vocab_size'])
    tree = meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(1), tokens)['params'])
    spec = ref.param_spec(dict(model_kwargs))
    assert {p: tuple(s) for p, (s, _) in spec.items()} == \
        {p: tuple(s) for p, (s, _) in weights.tree_spec(tree).items()}
    values = weights.make_params(seed, spec)
    return model, weights.replace_leaves(tree, values), values, tokens


def program_loss(model, tokens, beta=BETA):
    return lambda p: looped_lm_ce(model.apply({'params': p}, tokens), tokens,
                                  entropy_weight=beta)[0]


# ------------------------------------------------ model against reference
#: float32 on both sides: what is left is the order of additions (the
#: flash or dense attention against blocks of queries, the exit's
#: logsumexp against log_softmax, sums over the scan against a loop).
#: The largest readings over the cases below: loss 1.1e-7, a leaf's
#: gradient 5.6e-6 (relative to its norm). bfloat16 reads 9.3e-6 and
#: 0.016, the loss without its entropy term 0.029 and 0.84
LOSS_TOL, GRAD_TOL = 2e-6, 5e-5


def gaps_to_reference(model, params, values, tokens, kwargs, beta=BETA):
    loss, grads = jax.jit(jax.value_and_grad(
        program_loss(model, tokens, beta)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, dict(kwargs), BETA,
                              lambda x: x)))(values)
    grads = dict(weights.flat_paths(grads))
    assert set(grads) == set(want_grads)
    gaps = {}
    for leaf, want_grad in want_grads.items():
        norm = float(jnp.linalg.norm(want_grad))
        # every leaf has a gradient but the gate's where one step reads
        # no gate
        if kwargs['ut_steps'] == 1 and '/gate/' in leaf:
            assert norm == 0 and not np.any(np.asarray(grads[leaf]))
            continue
        assert norm > 0, leaf
        gaps[leaf] = float(jnp.linalg.norm(
            grads[leaf].astype(jnp.float32) - want_grad)) / norm
    return abs(float(loss) - float(want)) / abs(float(want)), gaps


@pytest.mark.parametrize('case,over', [
    ('two_layers_four_steps', {}),
    ('one_step', dict(ut_steps=1)),
    ('three_layers_two_steps_grouped_query',
     dict(n_layers=3, ut_steps=2, n_kv_heads=2)),
    ('flash_interpret_remat', dict(attn_impl='interpret', remat=True,
                                   seq=128)),
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


@pytest.mark.parametrize('what', ['bfloat16', 'no_entropy'])
def test_the_tolerance_fails_a_lower_precision_or_a_missing_term(what):
    """The tolerances above are tight enough that computing in the
    precision below fails them, and so does the loss without its
    entropy term."""
    kwargs = dict(SMALL, dtype='bfloat16') if what == 'bfloat16' \
        else SMALL
    beta = 0.0 if what == 'no_entropy' else BETA
    loss_gap, gaps = gaps_to_reference(*seeded(kwargs), kwargs, beta=beta)
    assert loss_gap > LOSS_TOL
    assert max(gaps.values()) > GRAD_TOL


# ------------------------------------------------------------ the loop
def test_one_recurrent_step_is_a_plain_sandwich_decoder():
    """With ``ut_steps`` 1 the model is the L layers once, the final
    norm and the head: the loss is the plain next-token cross-entropy of
    ``norm_final(layer_L(..layer_1(embed)))`` W_head, each layer the
    program's own class applied to its slice of the stacked leaves."""
    kwargs = dict(SMALL, ut_steps=1, n_layers=3)
    model, params, _, tokens = seeded(kwargs)
    cfg = model.cfg
    stacked = params['loop']['layers']
    x = jnp.take(params['embed'], tokens, axis=0)
    for i in range(cfg.n_layers):
        one = jax.tree.map(lambda a, i=i: a[i], stacked)
        x, _ = ouro.OuroLayer(cfg).apply({'params': one}, x, None)
    s, _ = ouro.Exit(cfg).apply({'params': params['loop']['exit']}, x)
    logits = s @ params['lm_head']
    logp = jax.nn.log_softmax(logits[:, :-1])
    want = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    got = program_loss(model, tokens)(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    exits = model.apply({'params': params}, tokens)
    assert exits['states'].shape == (1, 2, 32, 64)
    np.testing.assert_allclose(exits['exit_logp'], 0.0, atol=0)


def test_the_parameters_do_not_grow_with_the_steps():
    counts, trees = [], []
    tokens = jnp.zeros((1, 8), jnp.int32)
    for steps in (1, 2, 4, 6):
        model = create_model('ouro', **dict(SMALL, ut_steps=steps))
        tree = meta.unbox(jax.eval_shape(
            model.init, jax.random.PRNGKey(0), tokens)['params'])
        counts.append(param_count(tree))
        trees.append(weights.tree_spec(tree))
    assert len(set(counts)) == 1 and all(t == trees[0] for t in trees)
    d, f, v, layers = 64, 96, 96, 2
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert counts[0] == layers * layer + 2 * v * d + d + d + 1


@pytest.mark.parametrize('steps', [1, 2, 4, 5])
def test_the_exit_distribution_sums_to_one(steps):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(steps),
                                     (steps, 2, 7))
    p = np.exp(np.asarray(ouro.exit_log_probs(logits), np.float64))
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    lam = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    stay = np.ones_like(lam[0])
    for t in range(steps - 1):          # written out, as the paper does
        np.testing.assert_allclose(p[t], lam[t] * stay, rtol=1e-5)
        stay = stay * (1 - lam[t])
    np.testing.assert_allclose(p[-1], stay, rtol=1e-5)


def test_the_expected_loss_by_hand_on_two_tokens():
    """One sequence of three tokens (two of them predicted), two exits,
    a vocabulary of three: sum_t p(t) CE_t - beta H(p), each number
    written out."""
    tokens = jnp.array([[2, 0, 1]])
    head = jnp.array([[1.0, 0.0, -1.0], [0.5, 2.0, 0.0]])
    states = jnp.array([[[[1.0, 0.0], [0.0, 1.0], [3.0, 3.0]]],
                        [[[0.0, 2.0], [1.0, 1.0], [-3.0, 3.0]]]])
    gates = jnp.array([[[0.3, -1.2, 5.0]], [[9.0, 9.0, 9.0]]])
    exits = {'states': states, 'head': head,
             'exit_logp': ouro.exit_log_probs(gates)}
    loss, metrics = looped_lm_ce(exits, tokens, entropy_weight=0.25)

    def ce(s, target):
        z = [s[0] * head[0][j] + s[1] * head[1][j] for j in range(3)]
        return math.log(sum(math.exp(v) for v in z)) - z[target]

    want = 0.0
    for i, target in ((0, 0), (1, 1)):      # position i predicts i + 1
        lam = 1 / (1 + math.exp(-float(gates[0, 0, i])))
        p = (lam, 1 - lam)      # the last gate is not read
        h = -sum(q * math.log(q) for q in p)
        want += sum(p[t] * ce([float(v) for v in states[t, 0, i]], target)
                    for t in range(2)) - 0.25 * h
    assert float(loss) == pytest.approx(want / 2, rel=1e-6)
    assert float(metrics['loss']) == float(loss)


def test_found_by_name_and_its_loss_by_name():
    assert 'ouro' in model_names()
    assert isinstance(create_model('ouro', **SMALL), ouro.OuroLM)
    assert loss_for_task('looped_lm_ce') is LOSSES['looped_lm_ce'] \
        is looped_lm_ce
    assert ref.BETA == ENTROPY_WEIGHT == 0.1


# ----------------------------------------------------- through the program
def small_step(remat=True):
    model = create_model('ouro', **dict(SMALL, remat=remat))
    opt = make_optimizer({'name': 'adamw', 'lr': 1e-3})[0]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 96)
    state = create_train_state(model, opt, tokens, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, loss_for_task('looped_lm_ce'),
                           self_supervised=True)
    return step, state, tokens


def test_counters_leave_the_step():
    step, state, tokens = small_step()
    _, metrics = step(state, tokens, None)
    assert 1.0 < float(metrics['loop.expected_exit']) < 4.0
    assert float(metrics['loop.layer_rows']) == 2 * 32 * 2 * 4
    assert np.isfinite(float(metrics['loss']))


def test_a_small_jax_train_through_the_executor(tmp_path):
    from mlcomp_tpu.train import JaxTrain

    class Step:
        def start(self, *a, **k):
            pass

        info = debug = error = end_all = start

    ex = JaxTrain(
        checkpoint_dir=str(tmp_path / 'ck'),
        model=dict(SMALL, name='ouro'),
        dataset={'name': 'synthetic_lm', 'n_train': 64, 'n_valid': 16,
                 'seq_len': 32, 'vocab_size': 96},
        loss='looped_lm_ce',
        batch_size=8, mesh={'dp': 1}, main_metric='loss', minimize=True,
        stages=[{'name': 's1', 'epochs': 2,
                 'optimizer': {'name': 'adamw', 'lr': 3e-3}}])
    ex.step, ex.task, ex.session, ex.additional_info = Step(), None, None, {}
    result = ex.work()
    assert result['stage'] == 's1'
    assert result['n_params'] == 2 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64) \
        + 2 * 96 * 64 + 64 + 65
    # below the uniform guess over 96 ids, less the entropy bonus
    assert result['best_score'] < math.log(96)


def test_the_op_blocks_of_a_small_step():
    """Every op of a layer is named under ``attn`` or ``mlp`` and counts
    there, every op of an exit (final norm, gate, head, loss) under
    ``embed_head``; what ``other`` holds inside the loop is the scans'
    own bookkeeping — the weights' slices, the stacked buffers, the
    sums of a shared weight's gradient over its applications, the
    counters."""
    from mlcomp_tpu.telemetry import op_blocks
    step, state, tokens = small_step()
    text = step.lower(state, tokens, None).compile().as_text()
    comps, entry = op_blocks._computations(text)
    blocks = collections.Counter()
    bookkeeping = {'dynamic_slice', 'dynamic_update_slice', 'add_any',
                   'broadcast_in_dim', 'add', 'lt', 'while',
                   'closed_call', ''}
    free = ('parameter', 'constant', 'tuple', 'get-tuple-element',
            'bitcast')            # no op of these takes device time
    for name, rhs, op_name in op_blocks._top_level(comps, entry):
        if op_blocks._OPCODE_RE.search(rhs).group(1) in free:
            continue
        block = op_blocks.block_of(op_name)
        blocks[block] += 1
        path = op_blocks.scopes(op_name)
        if 'attn' in path:
            assert block == 'attention', op_name
        elif 'mlp' in path:
            assert block == 'mlp', op_name
        elif 'exit' in path or 'loss' in path:
            assert block == 'embed_head', op_name
        if block == 'other' and 'loop' in path:
            assert path[-1] in bookkeeping, op_name
    assert blocks['attention'] and blocks['mlp'] and blocks['embed_head']
    exits = [op for _, _, op in op_blocks._top_level(comps, entry)
             if 'exit' in op_blocks.scopes(op)]
    assert any('dot_general' in op for op in exits)     # the head
