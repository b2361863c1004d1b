"""``SparseMoe``'s two ways of moving rows between the tokens and the
expert-sorted buffer (``models/decoder_parts.py``): row gathers by the
sort and its inverse (``through_gathers``) and scatter-adds of the
buffer's rows (``through_scatters``). The shapes alone pick one
(``gathers_rows``); both give one layer's numbers and gradients."""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import pytest

from mlcomp_tpu.models import decoder_parts
from mlcomp_tpu.models.decoder_parts import (
    MoeConfig, SparseMoe, buffer_rows, gathers_rows,
)
from mlcomp_tpu.telemetry.op_blocks import row_scatters

#: lfm2's layer, small: sigmoid scores with a moving selection bias, 8 of
#: 16 experts held, top-4 over 2 x 64 tokens, four times the even share
LAYER = MoeConfig(
    d_model=32, d_expert=16, n_experts=16, top_k=4, experts_held=8,
    expert_offset=4, router_score='sigmoid', norm_topk_eps=1e-6,
    expert_bias=True, expert_bias_update_rate=0.001, moe_buffer_factor=4.0,
    dtype='float32', moe_impl='ragged')

CASES = {
    # (a) every (token, expert) pair has a row: 512 pairs, 512 rows
    'whole_buffer': dict(),
    # (b) a buffer cut under what lands: 128 rows, pairs drop
    'cut_buffer': dict(moe_buffer_factor=0.5),
    # (c) the grouped products leave rows past the groups undefined
    'undefined_rows': dict(nan_past_groups=True),
    # (d) eight pairs a row: the rule keeps the scatters
    'many_pairs_a_row': dict(moe_buffer_factor=0.25, tokens=(2, 256)),
}


def nan_past_groups(grouped_matmul):
    """``grouped_matmul`` with its rows past the groups NaN, as undefined
    as megablox leaves them."""
    def wrapped(lhs, rhs, group_sizes, *args, **kwargs):
        out = grouped_matmul(lhs, rhs, group_sizes, *args, **kwargs)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, jnp.nan)
    return wrapped


def run(cfg, params, x, cost):
    """(loss, sown, gradients of the parameters and x) with the form
    forced through ``SCATTER_ROW_COST``, and the compiled program's
    text."""
    kept = decoder_parts.SCATTER_ROW_COST
    decoder_parts.SCATTER_ROW_COST = cost
    try:
        layer = SparseMoe(cfg, name='moe')

        def loss(p, x):
            y, sown = layer.apply({'params': p}, x, mutable=[
                'intermediates', 'leaf_updates'])
            return jnp.sum(y ** 2), sown

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))
        text = step.lower(params, x).compile().as_text()
        return step(params, x), text
    finally:
        decoder_parts.SCATTER_ROW_COST = kept


@pytest.mark.parametrize('case', sorted(CASES))
def test_the_gathers_give_the_scatters_numbers(case, monkeypatch):
    over = dict(CASES[case])
    tokens = over.pop('tokens', (2, 64))
    if over.pop('nan_past_groups', False):
        monkeypatch.setattr(decoder_parts, 'grouped_matmul', nan_past_groups(
            decoder_parts.grouped_matmul))
    cfg = dataclasses.replace(LAYER, **over)
    x = jax.random.normal(jax.random.PRNGKey(1), tokens + (cfg.d_model,))
    params = flax.core.meta.unbox(
        SparseMoe(cfg).init(jax.random.PRNGKey(2), x)['params'])
    # the layer takes the form the rule gives for its shapes, by its own
    # constant; the rule reads the pairs and the rows and nothing else
    pairs, rows = x.shape[0] * x.shape[1] * cfg.top_k, buffer_rows(
        cfg, x.shape[0] * x.shape[1])
    cost = decoder_parts.SCATTER_ROW_COST
    assert gathers_rows(pairs, rows) == (pairs <= cost * rows)
    assert gathers_rows(int(cost * rows), rows)
    assert not gathers_rows(int(cost * rows) + 1, rows)
    _, own = run(cfg, params, x, cost)
    assert row_scatters(own) == (
        0 if gathers_rows(pairs, rows) else 2)
    (got, text), (want, scattered) = (run(cfg, params, x, c)
                                      for c in (float('inf'), 0.0))
    assert row_scatters(text) == 0
    assert row_scatters(scattered) == 2
    (loss, sown), grads = got
    (want_loss, want_sown), want_grads = want
    dropped = float(sown['intermediates']['moe.dropped'][0])
    assert dropped == float(want_sown['intermediates']['moe.dropped'][0])
    assert (dropped > 0) == (case in ('cut_buffer', 'many_pairs_a_row'))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    # x, the router, the three expert weights, and the selection bias's
    # load (its leaf update); the counters
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path((grads, sown)),
            jax.tree_util.tree_leaves_with_path((want_grads, want_sown))):
        assert bool(jnp.all(jnp.isfinite(a))), path
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale, path
    assert float(jnp.max(jnp.abs(grads[0]['router']))) > 0
    assert float(jnp.max(jnp.abs(sown['leaf_updates']['expert_bias'][0]))) \
        > 0


def test_the_slots_are_the_sorts_inverse():
    """Each (token, expert) pair's row of the buffer, by a running count,
    is where the stable sort put it; ``rows`` where it landed elsewhere
    or did not fit."""
    local = jax.random.randint(jax.random.PRNGKey(3), (4096,), 0, 9)
    sizes = jnp.bincount(local, length=9)[:8]
    for rows in (4096, 1024):
        order = jnp.argsort(local, stable=True)
        at = jnp.argsort(order)
        fits = (at < min(int(jnp.sum(sizes)), rows))
        want = jnp.where(fits, at, rows)
        got = decoder_parts.buffer_slots(local, sizes, rows)
        assert bool(jnp.all(got == want))


def scatter(shape, dims='{0}'):
    return (f'{shape}{{1,0}} scatter(%a, %i, %u), update_window_dims={{1}}, '
            f'inserted_window_dims={{0}}, scatter_dims_to_operand_dims={dims},'
            f' index_vector_dim=1, to_apply=%add')


@pytest.mark.parametrize('scope,inner,fused,count', [
    ('layer_1/moe', scatter('f32[16384,2048]'), True, 1),
    ('transpose(jvp(layer_1))/moe', scatter('bf16[16384,2048]'), False, 1),
    # the embedding's gradient: rows too, outside the layer
    ('Lfm2MoeLM', scatter('f32[16384,2048]'), True, 0),
    # the router's top-k gradient: into (token, expert) cells
    ('transpose(jvp(layer_1))/moe', scatter('f32[16384,32]', '{0,1}'),
     True, 0),
    # the experts' sizes: a count into a vector
    ('layer_1/moe', scatter('s32[9]'), False, 0),
    ('layer_1/moe', 'f32[16384,2048]{1,0} gather(%a, %i), offset_dims={1}',
     True, 0),
], ids=['combine', 'dispatch_backward', 'embedding', 'router', 'sizes',
        'gather'])
def test_row_scatters_counts_the_routings_scatters_of_rows(scope, inner,
                                                           fused, count):
    """The gauge ``step.wide_scatters``: a top-level instruction of
    ``moe_routing`` that is, or fuses, a scatter of whole rows; the
    insides of a fusion count as their fusion."""
    meta = f', metadata={{op_name="jit(step)/{scope}/scatter-add"}}'
    top = (inner.split(' ', 1)[0]
           + ' fusion(%p), kind=kCustom, calls=%fused_computation.1'
           if fused else inner)
    text = ('ENTRY %main (p: f32[2]) -> f32[2] {\n'
            f'  %x.1 = {top}{meta}\n}}\n'
            '%fused_computation.1 (p: f32[2]) -> f32[2] {\n'
            f'  ROOT %y.1 = {inner}{meta}\n}}\n')
    assert row_scatters(text) == count
