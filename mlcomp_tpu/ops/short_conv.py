"""Gated short convolution (the LFM2 conv mixer's middle) as one op.

``bcx`` [B,T,3C] holds three gates-and-values side by side, in thirds
``B | C | X`` as the mixer's input projection writes them; ``taps``
[K,C] one tap a channel. Causal, depthwise, no bias, no activation::

    z_t = sum_{j<K} taps_j * (B * X)_{t-(K-1)+j}     (zeros before t = 0)
    y_t = C_t * z_t                                   y [B,T,C]

Three tokens-by-C operands in, one out, no matrix product: the op is
bound by the bytes it moves, not by the MXU. Everything between the
load and the store is float32 (the v5e's vector unit has no bfloat16
arithmetic; float32 inputs give float32 throughout), in the ``xla`` form
and in the kernels alike.

On the TPU (``impl='pallas'``) it is two kernels under one
``custom_vjp``, each over whole-width tiles of ``block_t`` rows that
read B, C and X where they lie in ``bcx`` and walk the channels a
lane-aligned piece at a time:

- ``short_conv_fwd``: a tile of rows with the ``K - 1`` rows BEFORE it
  (the halo: the last rows of the 16-row block that ends where the tile
  starts; zeros for a sequence's first tile), writes ``C * z``;
- ``short_conv_bwd``: from ``g`` it makes ``z`` again from B and X (``z``
  is not held), ``dC = g * z``, ``dz = g * C``, ``d(BX)_t = sum_j taps_j
  * dz_{t+(K-1)-j}`` — its halo is the ``K - 1`` rows AFTER the tile —,
  ``dB = d(BX) * X``, ``dX = d(BX) * B``, written where B, C, X lie, and
  ``dtaps_j = sum_t dz_t * (BX)_{t-(K-1)+j}`` accumulated in float32
  across the row tiles and the batch in a block that stays in VMEM.

A sequence is a row of the batch: nothing crosses from one to the next
(``docs/lfm2_moe.md``; packed documents inside one row are not known to
the op). Off the TPU (``impl='xla'``) the equations above in
``jax.numpy``, differentiated by jax.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of the block a halo is read from: a whole tile of every dtype
#: the op takes (float32 8 rows, bfloat16 16)
HALO = 16
#: channels a kernel works on at once: its float32 temporaries are
#: [block_t, LANES] each
LANES = 512


def reference_short_conv(bcx, taps):
    """The equations of the module docstring in ``jax.numpy``."""
    f32 = jnp.float32
    c = bcx.shape[-1] // 3
    k, t = taps.shape[0], bcx.shape[1]
    b, gate, x = (bcx[..., i * c:(i + 1) * c].astype(f32)
                  for i in range(3))
    bx = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    z = sum(bx[:, j:j + t] * taps[j].astype(f32) for j in range(k))
    return (gate * z).astype(bcx.dtype)


def _shifted(tile, halo, shift: int, back: bool):
    """``tile`` [R,L] moved by ``shift`` rows: forward (``back`` False)
    row r reads row r - shift and the first rows read the LAST rows of
    ``halo`` [HALO,L]; backward row r reads row r + shift and the last
    rows read the FIRST rows of ``halo``."""
    if shift == 0:
        return tile
    rows = tile.shape[0]
    out = pltpu.roll(tile, shift if not back else rows - shift, axis=0)
    row = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    for r in range(shift):
        if back:
            out = jnp.where(row == rows - shift + r, halo[r:r + 1], out)
        else:
            at = HALO - shift + r
            out = jnp.where(row == r, halo[at:at + 1], out)
    return out


def _pieces(channels: int):
    step = LANES if channels % LANES == 0 else channels
    return [(c0, step) for c0 in range(0, channels, step)]


def _third(ref, i: int, channels: int, c0: int, n: int):
    """Channels [c0, c0 + n) of third ``i`` (B, C, X) of a tile of
    ``bcx``, in float32."""
    at = i * channels + c0
    return ref[0, :, at:at + n].astype(jnp.float32)


def _moved_bx(cur_ref, before_ref, first, k, channels, c0, n):
    """``B * X`` of the tile moved back by K-1 .. 0 rows, one per tap,
    the rows before the tile from the halo (zeros for a first tile)."""
    bx = _third(cur_ref, 0, channels, c0, n) \
        * _third(cur_ref, 2, channels, c0, n)
    halo = jnp.where(first, 0.0, _third(before_ref, 0, channels, c0, n)
                     * _third(before_ref, 2, channels, c0, n))
    return [_shifted(bx, halo, k - 1 - j, back=False) for j in range(k)]


def _fwd_kernel(cur_ref, before_ref, taps_ref, out_ref, *, channels, k):
    first = pl.program_id(1) == 0
    for c0, n in _pieces(channels):
        moved = _moved_bx(cur_ref, before_ref, first, k, channels, c0, n)
        z = sum(taps_ref[j:j + 1, c0:c0 + n] * moved[j] for j in range(k))
        out_ref[0, :, c0:c0 + n] = (
            _third(cur_ref, 1, channels, c0, n) * z).astype(out_ref.dtype)


def _bwd_kernel(cur_ref, before_ref, after_ref, g_ref, g_after_ref,
                taps_ref, d_ref, dtaps_ref, *, channels, k, n_tiles):
    f32 = jnp.float32
    tile = pl.program_id(1)
    first, last = tile == 0, tile == n_tiles - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _init():
        dtaps_ref[:] = jnp.zeros_like(dtaps_ref)

    for c0, n in _pieces(channels):
        b, gate, x = (_third(cur_ref, i, channels, c0, n)
                      for i in range(3))
        g = g_ref[0, :, c0:c0 + n].astype(f32)
        moved = _moved_bx(cur_ref, before_ref, first, k, channels, c0, n)
        z = sum(taps_ref[j:j + 1, c0:c0 + n] * moved[j] for j in range(k))
        dz = g * gate
        dz_after = jnp.where(
            last, 0.0, g_after_ref[0, :, c0:c0 + n].astype(f32)
            * _third(after_ref, 1, channels, c0, n))
        dbx = sum(taps_ref[j:j + 1, c0:c0 + n]
                  * _shifted(dz, dz_after, k - 1 - j, back=True)
                  for j in range(k))
        for i, value in enumerate((dbx * x, g * z, dbx * b)):
            d_ref[0, :, i * channels + c0:i * channels + c0 + n] = \
                value.astype(d_ref.dtype)
        for j in range(k):
            dtaps_ref[j:j + 1, c0:c0 + n] += jnp.sum(
                dz * moved[j], axis=0, keepdims=True)


def _fit_rows(t: int, want: int) -> int:
    """The most rows <= ``want``, a multiple of HALO, that divide t."""
    for rows in range(min(want, t) // HALO * HALO, 0, -HALO):
        if t % rows == 0:
            return rows
    raise ValueError(f'{t} rows do not divide into tiles of {HALO}')


def tiles(bcx) -> bool:
    """Whether the kernels take this shape: rows in tiles of 16,
    channels in whole lanes."""
    return bcx.shape[1] % HALO == 0 and bcx.shape[2] % (3 * 128) == 0


def _params(interpret):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=64 << 20),
        interpret=interpret)


def _specs(rows, width, n_tiles):
    """(this tile, the 16-row block that ends where it starts, the one
    that starts where it ends) of a [B,T,width] operand; the first and
    the last tile name a block that exists and mask what they read."""
    per = rows // HALO
    return (
        pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, HALO, width),
                     lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
        pl.BlockSpec((1, HALO, width),
                     lambda b, i: (b, jnp.minimum((i + 1) * per,
                                                  n_tiles * per - 1), 0)))


def _forward(bcx, taps, block_t, interpret):
    b, t, width = bcx.shape
    channels, k = width // 3, taps.shape[0]
    rows = _fit_rows(t, block_t)
    cur, before, _ = _specs(rows, width, t // rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, channels=channels, k=k),
        out_shape=jax.ShapeDtypeStruct((b, t, channels), bcx.dtype),
        grid=(b, t // rows),
        in_specs=[cur, before,
                  pl.BlockSpec((k, channels), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, rows, channels),
                               lambda b, i: (b, i, 0)),
        name='short_conv_fwd', **_params(interpret),
    )(bcx, bcx, taps.astype(jnp.float32))


def _backward(bcx, taps, g, block_t, interpret):
    b, t, width = bcx.shape
    channels, k = width // 3, taps.shape[0]
    rows = _fit_rows(t, block_t)
    n_tiles = t // rows
    cur, before, after = _specs(rows, width, n_tiles)
    g_cur, _, g_after = _specs(rows, channels, n_tiles)
    whole = pl.BlockSpec((k, channels), lambda b, i: (0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, channels=channels, k=k,
                          n_tiles=n_tiles),
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((k, channels), jnp.float32)],
        grid=(b, n_tiles),
        in_specs=[cur, before, after, g_cur, g_after, whole],
        out_specs=[cur, whole],
        name='short_conv_bwd', **_params(interpret),
    )(bcx, bcx, bcx, g, g, taps.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _short_conv(bcx, taps, block_t, interpret):
    return _forward(bcx, taps, block_t, interpret)


def _sc_fwd(bcx, taps, block_t, interpret):
    return _forward(bcx, taps, block_t, interpret), (bcx, taps)


def _sc_bwd(block_t, interpret, residuals, g):
    bcx, taps = residuals
    d_bcx, d_taps = _backward(bcx, taps, g, block_t, interpret)
    return d_bcx, d_taps.astype(taps.dtype)


_short_conv.defvjp(_sc_fwd, _sc_bwd)


def gated_short_conv(bcx, taps, impl: str = 'auto', block_t: int = 512):
    """``C * conv(B * X)`` over bcx [B,T,3C] (thirds B | C | X) with
    taps [K,C], K - 1 <= 16 (module docstring):

    - ``pallas``: the two kernels (TPU)
    - ``interpret``: the kernels under the Pallas interpreter (tests)
    - ``xla``: the equations in ``jax.numpy``
    - ``auto``: the kernels on a TPU where the shape tiles, else ``xla``

    The result is named ``short_conv.out`` for a caller's save-by-name
    ``remat`` policy (without one a name lowers to nothing)."""
    if bcx.shape[-1] != 3 * taps.shape[-1] or taps.shape[0] - 1 > HALO:
        raise ValueError(f'bcx {bcx.shape} against taps {taps.shape}')
    if impl == 'auto':
        impl = 'pallas' if (tiles(bcx) and jax.default_backend()
                            == 'tpu') else 'xla'
    if impl == 'xla':
        return checkpoint_name(reference_short_conv(bcx, taps),
                               'short_conv.out')
    if not tiles(bcx):
        raise ValueError(
            f'the short-conv kernels need rows in 16s and channels in '
            f'128s, got {bcx.shape}')
    return checkpoint_name(
        _short_conv(bcx, taps, block_t, impl == 'interpret'),
        'short_conv.out')


__all__ = ['gated_short_conv', 'reference_short_conv']
