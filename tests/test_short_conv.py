"""``ops/short_conv.py`` on the CPU, float32: the kernels (interpret
mode) against the ``xla`` form against ``jax.grad`` of the equations
written out as a sum over shifted copies — the forward and all four
cotangents (dB, dC, dX, dtaps) — at a length that is not one tile and
with two sequences; nothing leaks from one sequence to the next; the
first and the last tile's halos are zeros; bfloat16 operands agree to
bfloat16's rounding; shapes the kernels do not take."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.ops.short_conv import (
    gated_short_conv, reference_short_conv,
)

C = 128


def written_out(bcx, taps):
    """y_t = C_t * sum_j taps_j (B X)_{t-(K-1)+j}, one shifted copy a
    tap, zeros before the first token."""
    c = taps.shape[1]
    gate_b, gate_c, x = bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]
    bx = gate_b * x
    k, t = taps.shape[0], bcx.shape[1]
    z = 0.0
    for j in range(k):
        back = k - 1 - j
        z = z + taps[j] * jnp.concatenate(
            [jnp.zeros_like(bx[:, :back]), bx[:, :t - back]], 1)
    return gate_c * z


def operands(seed=0, b=2, t=96, k=3, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, t, 3 * C), dtype),
            jax.random.normal(keys[1], (k, C), jnp.float32),
            jax.random.normal(keys[2], (b, t, C), dtype))


def everything(fn, bcx, taps, g):
    """{name: array}: the result and the four cotangents under g."""
    y, pull = jax.vjp(fn, bcx, taps)
    d_bcx, d_taps = pull(g.astype(y.dtype))
    return {'y': y, 'dB': d_bcx[..., :C], 'dC': d_bcx[..., C:2 * C],
            'dX': d_bcx[..., 2 * C:], 'dtaps': d_taps}


def gap(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


FORMS = {
    'interpret_3_tiles': lambda a, w: gated_short_conv(
        a, w, impl='interpret', block_t=32),
    'interpret_one_tile': lambda a, w: gated_short_conv(
        a, w, impl='interpret'),
    'xla': lambda a, w: gated_short_conv(a, w, impl='xla'),
}


@pytest.mark.parametrize('what', ['y', 'dB', 'dC', 'dX', 'dtaps'])
@pytest.mark.parametrize('form', sorted(FORMS))
@pytest.mark.parametrize('k', [3, 4], ids=['3_taps', '4_taps'])
def test_forward_and_cotangents_against_the_written_out_sum(k, form, what):
    """Float32 throughout: the three forms differ by the order of a few
    additions, so 1e-5 of the largest entry holds them (bfloat16 in any
    of them reads 1e-2)."""
    bcx, taps, g = operands(k=k)
    got = everything(FORMS[form], bcx, taps, g)[what]
    want = everything(written_out, bcx, taps, g)[what]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert gap(got, want) < 1e-5


@pytest.mark.parametrize('form', ['interpret_3_tiles', 'xla'])
def test_nothing_leaks_from_one_sequence_to_the_next(form):
    """Another first sequence leaves the second one's result and
    cotangents as they were, to the bit: its first tokens see zeros, not
    the sequence before."""
    bcx, taps, g = operands()
    other = bcx.at[0].set(operands(seed=5)[0][0])
    one = everything(FORMS[form], bcx, taps, g)
    two = everything(FORMS[form], other, taps, g)
    for name in ('y', 'dB', 'dC', 'dX'):
        np.testing.assert_array_equal(one[name][1], two[name][1])
        assert gap(one[name][0], two[name][0]) > 1e-2
    # and the first token of a sequence reads only its own tap
    y = FORMS[form](bcx, taps)
    np.testing.assert_allclose(
        y[:, 0], bcx[:, 0, C:2 * C] * taps[-1]
        * bcx[:, 0, :C] * bcx[:, 0, 2 * C:], rtol=1e-6)


def test_the_halo_crosses_every_tile_boundary():
    """An impulse in the last row of a tile shows in the next tile's
    first two rows (forward), and a cotangent in a tile's first row
    reaches the two rows before it (backward)."""
    bcx = jnp.zeros((1, 96, 3 * C)).at[:, 31, :].set(1.0) \
        .at[:, :, C:2 * C].set(1.0)
    taps = jnp.array([[3.0], [2.0], [1.0]]) * jnp.ones((3, C))
    fn = FORMS['interpret_3_tiles']
    y = fn(bcx, taps)
    np.testing.assert_array_equal(y[0, 30:35, 0], [0, 1, 2, 3, 0])
    g = jnp.zeros((1, 96, C)).at[:, 32, :].set(1.0)
    d_bcx = jax.vjp(fn, jnp.ones((1, 96, 3 * C)), taps)[1](g)[0]
    np.testing.assert_array_equal(d_bcx[0, 29:34, 0], [0, 3, 2, 1, 0])


@pytest.mark.parametrize('what', ['y', 'dB', 'dC', 'dX', 'dtaps'])
def test_bfloat16_operands(what):
    """bfloat16 in and out, float32 in between, in both forms: they
    round the same numbers once, so they agree to a bfloat16 ulp; the
    float32 result lies 2**-8 away, which the float32 test above would
    not pass."""
    bcx, taps, g = operands(dtype=jnp.bfloat16)
    got = everything(FORMS['interpret_3_tiles'], bcx, taps, g)[what]
    want = everything(FORMS['xla'], bcx, taps, g)[what]
    assert got.dtype == want.dtype
    assert gap(got, want) < 2 ** -7
    if what != 'dtaps':
        assert got.dtype == jnp.bfloat16
        exact = everything(written_out, *(
            v.astype(jnp.float32) for v in (bcx, taps, g)))[what]
        assert 1e-5 < gap(got, exact) < 2 ** -6


def test_shapes_the_kernels_do_not_take():
    bcx, taps, _ = operands(t=40)           # 40 rows: not tiles of 16
    with pytest.raises(ValueError, match='rows in 16s'):
        gated_short_conv(bcx, taps, impl='interpret')
    # `auto` falls back to the equations (and off the TPU always does)
    np.testing.assert_array_equal(gated_short_conv(bcx, taps),
                                  reference_short_conv(bcx, taps))
    with pytest.raises(ValueError, match='against taps'):
        gated_short_conv(bcx, taps[:, :64])
