"""Fused causal flash attention as Pallas TPU kernels: one forward, one
backward.

SURVEY.md §2.2: the reference delegates all device math to torch/CUDA;
the TPU build promises custom ops via Pallas. This is the first: an
online-softmax attention that never materialises the [T, T] score
matrix in HBM — scores live in VMEM one tile at a time, flowing through
the MXU per tile. Grouped-query attention is an index: query head ``i``
reads key-value head ``i // group``. The score head and the value head
may be of two sizes (latent attention: q, k [B, T, H, 192], v, o, do
[B, T, H, 128]; dq and dk come back as q and k, dv as v): V is never
padded to the score head's width and P V, dP and dV run at the value
head's.

What both kernels share:
- MXU products take the INPUT dtype (bf16 pairs multiply exactly, f32
  accumulation via preferred_element_type); P and dS round to the input
  dtype for the products they feed (standard flash practice, exact for
  f32 inputs); masked scores are ``NEG_INF``; the residual is the
  logsumexp a row, lane-tiled.
- THE TILE WALK (``_walk_tile``, ``_diagonal_strips``). Causal tiles
  are square, so the diagonal crosses tile (i, i) corner to corner and
  no other. A tile under it runs whole with NO iota / compare / select;
  a tile above it is skipped (and its DMA elided: the index map re-names
  the resident tile); the diagonal's own tile is walked in static row
  strips, strip ``a`` against the tile's first ``(a + 1) * sub`` keys,
  the mask on its last ``sub`` columns' worth of pairs only. Four
  strips run 10 of a tile's 16 sub-tiles. ``executed_pairs`` counts
  what a walk multiplies: the backward runs 1.12 x the pairs causal
  attention requires at T = 2,048 and 1.03 x at 8,192 (1.50 and 1.125
  with every live tile whole, as before PR 34).
- tiles of 1024 x 1024 at every head size, each kernel asking for the
  scoped VMEM its shapes need (``_vmem_limit``); tile, strips, span and
  limit are functions of (T, the two head sizes, dtype, the kernel's
  products) and of nothing else.
- WHAT IS EXECUTED OVER THE REQUIRED PAIRS AT 192 / 128 (T = 8,192): the
  walk's x1.06 forward (the diagonal tile in two strips) and x1.03
  backward, and, in VMEM only, the lanes of the 192-wide q, k, dq, dk
  padded to 256: S, dQ and dK take the MXU passes of a 256-deep head, 3
  passes a pair forward for the 2.5 that 192 + 128 would need and 8
  backward for 6.5. Nothing else: no padded V, no second S.

Forward (``_fa_kernel``): grid (batch*heads, q-blocks, k-blocks), the
LAST axis sequential so VMEM scratch carries the running max /
normaliser / accumulator across k-blocks; K and V stream tile by tile.
It is bound by the VPU's passes over the scores at head sizes up to 128
(2 products a pair), so there the diagonal tile runs as ONE strip —
strips measured slower — and in two at 256 (``_strips``).

Backward (``_fa_bwd_kernel``): ONE kernel. Grid (batch*kv-heads, spans,
(query head of the group, q-block)): a key-value head's K and V stay in
VMEM whole while its group's q-blocks pass, dK and dV accumulate beside
them in float32 and are written once a head, dQ accumulates over the
k-blocks of its q-block in a loop INSIDE the step (no grid step for a
dead tile). S, P, dP and dS are made once a strip and feed all three
gradients: 5 products a pair where the dq and dk/dv kernels this
replaces ran 7, one ``exp`` where they ran two; the dS correction
``delta = rowsum(dO * O)`` is made in the step from the q-block's own
rows, not by an XLA fusion into a lane-tiled array. The residents (K, V,
the dK / dV blocks, two float32 accumulators: 50 MB a head of 8,192 x
256) are counted (``_resident_bytes``) and a sequence too long for
``RESIDENT_BYTES`` goes through the same kernel in spans of keys, dQ
summed from one float32 partial a span. Residuals are (q, k, v, out,
lse): O(T) extra memory.

Measured on a v5e (my chip runs, PR 34, ``scripts/flash_probe.py``;
bf16, causal, the kernels' own device time from a trace, and as a share
of 197 TFLOP/s on the FLOPs attention REQUIRES; before -> after):
- 4 x 2,048 tokens, 16 heads of 128 (``olmo-1b``): forward 0.875 ->
  0.776 ms (39.9 -> 45.0%), backward 2.220 -> 1.158 ms (31.4 -> 60.3%)
- 2 x 8,192, 32 over 8 heads of 64 (``lfm2-8b-a1b``): forward 9.862 ->
  9.451 ms (28.3 -> 29.5%), backward 25.966 -> 15.767 ms (21.5 -> 35.4%)
- 2 x 8,192, 16 over 2 heads of 256 (``qwen3-next-80b-a3b``): forward
  10.488 -> 7.367 ms (53.2 -> 75.8%), backward 24.840 -> 15.008 ms
  (44.9 -> 74.4%)
- 2 x 8,192, 32 heads with scores of 192 and values of 128
  (``kanana-2-30b-a3b``; PR 35, new — required FLOPs ``2 pairs (192 +
  128)`` a head forward, twice that backward): forward 12.98 ms (53.8%;
  13.30 with the diagonal tile whole), backward 24.64 ms (56.6%); with
  their layout copies 15.7 and 29.6 ms a call
The backward runs at 85–96% of the MXU's rate on the products it
executes (half the rate at head size 64, whose products are 64 deep or
wide); what is left in the forward is the VPU's share of a tile, which
the compiler does not overlap with the MXU's. It runs at T = 32,768
where the dense backward cannot compile (its [T, T] probability tensor
alone is 8.6 GB at 16k).

``fused_attention`` is the entry point the models use: it picks the
kernels on TPU, the interpreter in tests, and the dense jnp path
anywhere else or for shapes the kernels do not tile.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def reference_attention(q, k, v, causal: bool = True,
                        scale: Optional[float] = None):
    """Dense softmax attention over q, k [B, T, H, Dqk] and v
    [B, T, H, Dv] (the two head sizes may differ) — the numerics the
    kernel must reproduce, and the fallback/backward path."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    group = q.shape[2] // k.shape[2]
    if group > 1:           # each key-value head serves `group` heads
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _scores(q, k, scale, masked_from=None):
    """One strip of scaled scores in float32. ``masked_from`` is the
    column of the strip's first row at which the causal mask starts
    (row ``r`` sees columns ``<= masked_from + r``): only a strip the
    diagonal crosses passes it, so a strip wholly under the diagonal
    pays no iota / compare / select."""
    # operands stay in the input dtype (bf16 for bf16 models): the MXU
    # multiplies bf16 pairs exactly and accumulates in f32 via
    # preferred_element_type
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked_from is not None:
        ahead = lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            - lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(ahead > masked_from, NEG_INF, s)
    return s


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
               block_q, block_k, n_k, strips, emit_lse):
    if emit_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
        lse_ref = None
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def strip(r0, rows, cols, masked):
        """Online-softmax update of q rows ``[r0, r0 + rows)`` with the
        tile's first ``cols`` keys."""
        at = slice(r0, r0 + rows)
        v = v_ref[0, :cols]
        s = _scores(q_ref[0, at], k_ref[0, :cols], scale,
                    cols - rows if masked else None)
        m_prev = m_scr[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[at, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p rounds to the value dtype for the MXU (standard flash
        # practice; exact when inputs are f32)
        acc_scr[at] = acc_scr[at] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[at] = jnp.broadcast_to(m_new, (rows, m_scr.shape[1]))
        l_scr[at] = jnp.broadcast_to(l_new, (rows, l_scr.shape[1]))

    _walk_tile(i_q, i_k, causal, block_q, block_k, strips, strip)

    @pl.when(i_k == n_k - 1)
    def _finalise():
        norm = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / norm).astype(o_ref.dtype)
        if emit_lse:
            # logsumexp per query row, replicated across the 128-lane
            # dim (TPU blocks need (8, 128)-aligned trailing dims —
            # the layout jax's own flash kernel uses for residuals)
            lse_ref[0] = jnp.broadcast_to(
                m_scr[:, :1] + jnp.log(norm[:, :1]), lse_ref.shape[1:])


# ---------------------------------------------------------- the tile walk
# Causal tiles are square (``_blocks``), so tile (i_q, i_k) is wholly
# under the diagonal where i_k < i_q, crossed by it where i_k == i_q and
# dead above. The kernels' predicates, the index-map clamp and the
# count the tests hold (``executed_pairs``) all derive from the three
# helpers below, so they cannot drift apart (a divergence would DMA the
# wrong tile for a live step, a correctness bug, not just lost elision).

def _last_live_k(i_q):
    """Highest k-block with any unmasked element for q-block ``i_q``:
    the tile the diagonal crosses. Every k-block below it is whole."""
    return i_q


def _strips(products: int, d: int, dv: int) -> int:
    """Strips a diagonal tile is walked in, from the MXU passes a pair
    costs the kernel: of its products the larger half (S forward; S, dK
    and dQ backward) is as deep or wide as the score head ``d``, the
    rest (P V; dP and dV) as the value head ``dv``, each in 128-deep
    passes. A strip saves the products above the diagonal and
    pays for it in short operand streams and in K transposed once a
    strip, so it pays where the MXU binds. On the chip (PR 34, the
    kernels alone, ms at 1 / 2 / 4 strips a 1024-tile): the forward at
    head size 128 (4 x 2,048 x 16 heads) 0.770 / 0.875 / 0.910, at 64
    (2 x 8,192 x 32) 9.45 / 9.86 / 9.99, at 256 (2 x 8,192 x 16) 7.74 /
    7.37 / 7.44; the backward 1.438 / 1.217 / 1.126, 16.89 / 16.00 /
    15.64 and 16.24 / 15.36 / 14.94. PR 35, score heads of 192 over
    value heads of 128 (2 x 8,192 x 32; 3 passes a pair forward, 8
    backward): the forward 13.30 / 12.98 / 12.98, the backward 26.39 /
    25.09 / 24.64 — two strips forward from 3 passes on."""
    work = -(-products // 2) * (_lanes(d) // 128) \
        + products // 2 * (_lanes(dv) // 128)
    return 1 if work < 3 else 2 if work < 5 else 4


def _diagonal_strips(block: int, strips: int):
    """The static walk of a tile the diagonal crosses, as (first row,
    rows, keys): strip ``a`` multiplies its ``sub`` rows by the tile's
    first ``(a + 1) * sub`` keys only — four strips run 10 of the
    tile's 16 sub-tiles, one runs the tile whole — and only its last
    ``sub`` columns hold masked pairs. ``sub`` is in whole 128s (a
    strip's keys are the score strip's lanes)."""
    sub = _fit_block(block, max(128, block // strips))
    return [(a * sub, sub, (a + 1) * sub) for a in range(block // sub)]


def _walk_tile(i_q, i_k, causal, block_q, block_k, strips, strip):
    """Run ``strip(first row, rows, keys, masked)`` over tile
    (i_q, i_k) of a grid whose last axis is the k-block: whole and
    unmasked where the tile lies under the diagonal (or nothing is
    causal), by ``_diagonal_strips`` where the diagonal crosses it,
    not at all above it."""
    if not causal:
        strip(0, block_q, block_k, False)
        return

    @pl.when(i_k < _last_live_k(i_q))
    def _whole():
        strip(0, block_q, block_k, False)

    @pl.when(i_k == _last_live_k(i_q))
    def _diagonal():
        for r0, rows, cols in _diagonal_strips(block_q, strips):
            strip(r0, rows, cols, True)


def executed_pairs(t: int, block: int, strips: int, causal: bool) -> int:
    """(query, key) pairs a kernel multiplies for one head of ``t``
    tokens in tiles of ``block`` — what its walk EXECUTES, against the
    ``t (t + 1) / 2`` a causal head requires (``t * t`` otherwise)."""
    if not causal:
        return t * t
    diagonal = sum(rows * cols for _r0, rows, cols
                   in _diagonal_strips(block, strips))
    return sum(_last_live_k(i_q) * block * block + diagonal
               for i_q in range(t // block))


def _kv_head(bh, group: int):
    """The key-value head (folded with the batch) that query head ``bh``
    reads: with grouped-query attention ``group`` query heads share
    one. Equal head counts leave the index as it is."""
    return bh if group == 1 else bh // group


def _causal_kv_ix(causal: bool, group: int = 1):
    """Index map for operands streamed over k-blocks (grid order
    (bh, iq, ik)). ``pl.when`` skips a masked block's COMPUTE but
    Pallas still copies the tiles the index map names — half the K/V
    HBM traffic for nothing in causal attention. Clamping to the last
    live k-block makes every dead step re-name the tile already
    resident in VMEM, and Pallas elides copies whose block index is
    unchanged. Kernels read the TRUE ik from program_id, so masking
    and skip logic are unaffected."""
    if not causal:
        return lambda bh, iq, ik: (_kv_head(bh, group), ik, 0)
    return lambda bh, iq, ik: (
        _kv_head(bh, group), jnp.minimum(ik, _last_live_k(iq)), 0)


def _kv_group(q, k) -> int:
    """Query heads a key-value head serves (1 = equal head counts)."""
    h, h_kv = q.shape[2], k.shape[2]
    if h % h_kv:
        raise ValueError(f'{h} query heads over {h_kv} key-value heads')
    return h // h_kv


def _fold(x):
    """[B, T, H, D] -> [B*H, T, D]: contiguous (seq, head_dim) tiles."""
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _unfold(x, b: int):
    """[B*H, T, D] -> [B, T, H, D]."""
    _, t, d = x.shape
    return jnp.transpose(x.reshape(b, -1, t, d), (0, 2, 1, 3))


def _lanes(d: int) -> int:
    """A head's width in VMEM: the minor dimension pads to whole 128s."""
    return -(-d // 128) * 128


def _fit_block(t: int, want: int) -> int:
    """Largest multiple of 128 ≤ want that divides t (any t % 128 == 0
    admits at least 128 itself, so tileability == t % 128 == 0)."""
    start = (min(want, t) // 128) * 128
    for cand in range(start, 127, -128):
        if t % cand == 0:
            return cand
    raise ValueError(f'seq len {t} not divisible by any 128-multiple '
                     f'block ≤ {want}')


def _blocks(t: int, block_q: int, block_k: int, causal: bool):
    """The tile of a call, from the sequence and what the caller
    wants: square where the mask is causal, so that the diagonal
    crosses only tiles (i, i) and crosses them corner to corner — the
    static walk of ``_diagonal_strips``. The default, 1024, is every
    head size's since PR 34: heads of 256 took 512-tiles to fit the
    default scoped VMEM, and at 2 x 8,192 x 16 heads their forward ran
    10.49 ms for 7.37 at 1024 (a grid step costs ~0.35 us, dead ones
    too), their backward 15.19 for 14.94."""
    block_q, block_k = _fit_block(t, block_q), _fit_block(t, block_k)
    if causal:
        block_q = block_k = min(block_q, block_k)
    return block_q, block_k


def flash_attention_forward(q, k, v, causal: bool = True,
                            scale: Optional[float] = None,
                            block_q: int = 1024, block_k: int = 1024,
                            interpret: bool = False,
                            with_lse: bool = False):
    """Pallas forward over q [B, T, H, D], k [B, T, Hkv, D] and v
    [B, T, Hkv, Dv] — the value head may be of another size than the
    score head, the result is [B, T, H, Dv] — (grouped-query attention
    where Hkv < H: query head ``i`` reads key-value head
    ``i // (H / Hkv)``). T must divide by both block
    sizes (caller falls back to dense otherwise). ``with_lse`` also
    returns the per-row logsumexp [B, H, T] the fused backward needs."""
    b, t, h, d = q.shape
    d_v = v.shape[-1]
    group = _kv_group(q, k)
    scale = scale if scale is not None else d ** -0.5
    block_q, block_k = _blocks(t, block_q, block_k, causal)
    n_q, n_k = t // block_q, t // block_k

    qf, kf, vf = _fold(q), _fold(k), _fold(v)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, strips=_strips(2, d, d_v),
        emit_lse=with_lse)

    # causal dead-tile DMA elision for the streamed k/v operands (see
    # _causal_kv_ix)
    kv_ix = _causal_kv_ix(causal, group)

    out_shape = [jax.ShapeDtypeStruct((b * h, t, d_v), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d_v),
                              lambda bh, iq, ik: (bh, iq, 0))]
    if with_lse:
        # lse is only materialised when the caller needs residuals —
        # inference forwards skip the [B*H, T, 128] write entirely
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, t, 128), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, block_q, 128), lambda bh, iq, ik: (bh, iq, 0)))

    result = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_ix),
            pl.BlockSpec((1, block_k, d_v), kv_ix),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # normaliser
            pltpu.VMEM((block_q, d_v), jnp.float32),   # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            # resident: the K and V tiles, double-buffered
            vmem_limit_bytes=_vmem_limit(
                2 * block_k * (_lanes(d) + _lanes(d_v))
                * q.dtype.itemsize, block_q, block_k, d, d_v,
                q.dtype.itemsize)),
        interpret=interpret,
    )(qf, kf, vf)

    out = _unfold(result[0], b)
    if with_lse:
        return out, result[1][:, :, 0].reshape(b, h, t)
    return out


def _fa_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                   delta_scr, *,
                   scale, causal, block_q, block_k, n_q, n_k, strips):
    """One q-block of one query head against the ``n_k`` k-blocks of
    its key-value head that this span holds in VMEM: S, P, dP and dS
    are made once a strip and feed all three gradients."""
    first_k = pl.program_id(1) * n_k     # the span's first k-block
    # the last grid axis: the group's query heads one after the other,
    # each over its q-blocks
    step = pl.program_id(2)
    i_q = step % n_q

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_scr[:] = jnp.zeros_like(dq_scr)
    # delta_i = rowsum(dO_i · O_i), the dS correction term, made here
    # from the q-block's own rows and kept lane-tiled like lse: no
    # [bh, t, 128] array of it in HBM, no XLA fusion to make one
    delta_scr[:] = jnp.broadcast_to(jnp.sum(
        do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1, keepdims=True), delta_scr.shape)

    def strip(r0, rows, k0, cols, masked):
        """q rows ``[r0, r0 + rows)`` against the span's keys
        ``[k0, k0 + cols)``."""
        at = slice(r0, r0 + rows)
        keys = pl.ds(k0, cols)
        q, do = q_ref[0, at], do_ref[0, at]
        k, v = k_ref[0, keys], v_ref[0, keys]
        # the probabilities exactly as the forward made them, from the
        # saved logsumexp; p and ds round to the input dtype for the
        # gradient products (standard flash practice; exact when
        # inputs are f32)
        s = _scores(q, k, scale, cols - rows if masked else None)
        p = jnp.exp(s - lse_ref[0, at, :1])
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_scr[at, :1])).astype(q.dtype)
        p = p.astype(q.dtype)
        dv_scr[keys] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [cols, d]
        dk_scr[keys] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dq_scr[at] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    def whole(i_k, carry):
        strip(0, block_q, pl.multiple_of(i_k * block_k, block_k),
              block_k, False)
        return carry

    if causal:
        # of the tiles under the diagonal, those this span holds; then
        # the diagonal's own tile by strips, if it is the span's
        here = _last_live_k(i_q) - first_k
        lax.fori_loop(0, jnp.clip(here, 0, n_k), whole, None)

        @pl.when((here >= 0) & (here < n_k))
        def _diagonal():
            k0 = pl.multiple_of(here * block_k, block_k)
            for r0, rows, cols in _diagonal_strips(block_q, strips):
                strip(r0, rows, k0, cols, True)
    else:
        lax.fori_loop(0, n_k, whole, None)

    dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalise():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# What one backward call may keep in VMEM for a key-value head's K and
# V, their gradients' blocks and the two float32 accumulators: half of a
# v5e core's 128 MiB, which leaves the q-side tiles, the score-sized
# temporaries and the compiler's own room. A longer sequence goes
# through in spans of this size (``_span``).
RESIDENT_BYTES = 64 << 20


def _resident_bytes(span: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM that ``span`` keys of one key-value head hold through the
    backward: K and the dK block at the score head's width, V and the
    dV block at the value head's (each double-buffered by the pipeline)
    and the two float32 accumulators, lanes padded to 128."""
    return span * (_lanes(d) + _lanes(dv)) * (4 * itemsize + 4)


def _span(t: int, block_k: int, d: int, dv: int, itemsize: int) -> int:
    """Keys the backward holds at once: the whole sequence where it
    fits ``RESIDENT_BYTES`` (every shape the cells run), else its
    largest part in whole k-blocks that does."""
    n_k = t // block_k
    for spans in range(1, n_k + 1):
        if n_k % spans == 0 and _resident_bytes(
                t // spans, d, dv, itemsize) <= RESIDENT_BYTES:
            return t // spans
    return block_k


def _vmem_limit(resident, block_q, block_k, d, dv, itemsize) -> int:
    """A kernel's scoped-VMEM limit from its shapes (the default, 16 MB,
    holds neither a 1024 x 1024 tile at head size 256 nor a resident
    head): what stays through the walk, the q-side tiles (q and the dq
    block at the score head's width, O and dO at the value head's,
    double-buffered, the lane-tiled row statistics, a float32
    accumulator at the wider of the two), eight score-sized float32
    temporaries (S, P, dP, dS, their rounded copies and the transposes
    the MXU is fed), and a quarter on top."""
    tiles = block_q * ((_lanes(d) + _lanes(dv)) * 4 * itemsize
                       + max(_lanes(d), _lanes(dv)) * 4 + 4 * 128 * 4)
    need = resident + tiles + 8 * block_q * block_k * 4
    return min(max(need + need // 4, 32 << 20), 110 << 20)


def flash_attention_backward(q, k, v, out, lse, do,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = 1024, block_k: int = 1024,
                             interpret: bool = False):
    """Fused flash backward: O(T) residuals (just out + lse), the
    probability tiles reconstructed in VMEM from lse exactly as the
    forward computed them. ONE kernel: a key-value head's K and V stay
    in VMEM while its ``group`` query heads' q-blocks pass, dK and dV
    accumulate beside them in float32 and dQ over the k-blocks of each
    q-block, so every tile's S, P, dP and dS are made once (5 products
    a tile). A sequence too long to stay (``_span``) goes through in
    spans, each adding a float32 partial of dQ that is summed here."""
    b, t, h, d = q.shape
    d_v = v.shape[-1]
    group = _kv_group(q, k)
    h_kv = h // group
    scale = scale if scale is not None else d ** -0.5
    block_q, block_k = _blocks(t, block_q, block_k, causal)
    n_q = t // block_q
    span = _span(t, block_k, d, d_v, q.dtype.itemsize)
    spans = t // span

    qf, kf, vf, of, dof = (_fold(x) for x in (q, k, v, out, do))
    # the row statistic lives lane-tiled ([bh, t, 128]) so its blocks
    # meet the TPU (8, 128) trailing-dim constraint
    lsef = jnp.broadcast_to(
        lse.reshape(b * h, t)[..., None], (b * h, t, 128))

    # grid (key-value head, span, (query head of the group, q-block)):
    # step ``j`` of the last axis reads q-block ``j % n_q`` of query
    # head ``bh * group + j // n_q``
    def q_ix(bh, s, j):
        return (bh * group + j // n_q, j % n_q, 0)

    # q and dq at the score head's width, O and dO at the value head's
    q_spec = pl.BlockSpec((1, block_q, d), q_ix)
    o_spec = pl.BlockSpec((1, block_q, d_v), q_ix)
    row_spec = pl.BlockSpec((1, block_q, 128), q_ix)
    k_spec = pl.BlockSpec((1, span, d), lambda bh, s, j: (bh, s, 0))
    v_spec = pl.BlockSpec((1, span, d_v), lambda bh, s, j: (bh, s, 0))

    dq, dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          n_k=span // block_k, strips=_strips(5, d, d_v)),
        out_shape=[
            # one partial of dq a span; a single span's is dq itself
            jax.ShapeDtypeStruct(
                (spans, b * h, t, d),
                q.dtype if spans == 1 else jnp.float32),
            jax.ShapeDtypeStruct((b * h_kv, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, t, d_v), v.dtype),
        ],
        grid=(b * h_kv, spans, group * n_q),
        in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, s, j: (s,) + q_ix(bh, s, j)),
            k_spec, v_spec,
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((span, d), jnp.float32),
                        pltpu.VMEM((span, d_v), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=_vmem_limit(
                _resident_bytes(span, d, d_v, q.dtype.itemsize), block_q,
                block_k, d, d_v, q.dtype.itemsize)),
        interpret=interpret,
    )(qf, kf, vf, of, dof, lsef)
    dq = dq[0] if spans == 1 else jnp.sum(dq, axis=0).astype(q.dtype)

    return _unfold(dq, b), _unfold(dk, b), _unfold(dv, b)


def blockwise_attention(q, k, v, causal: bool = True,
                        scale: Optional[float] = None,
                        block_k: int = 512):
    """Online-softmax attention as a checkpointed ``lax.scan`` over
    k-blocks — the pure-jnp twin of the kernel. Differentiable with
    ~D/block_k of the dense backward's residual memory (the scan
    carries). Production gradients go through the FUSED Pallas backward
    (``flash_attention_backward``); this remains the memory-efficient
    jnp alternative for non-Pallas platforms (the headline BENCH
    comparison is against dense+remat attention, not this path)."""
    b, t, h, d = q.shape
    d_v = v.shape[-1]        # the value head, which the result has
    scale = scale if scale is not None else d ** -0.5
    block_k = _fit_block(t, block_k) if t % 128 == 0 else t
    n_k = t // block_k

    qf = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)  # [B,H,T,D]
    kf = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32)
    vf = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32)
    k_blocks = kf.reshape(b, h, n_k, block_k, d).transpose(2, 0, 1, 3, 4)
    v_blocks = vf.reshape(
        b, h, n_k, block_k, d_v).transpose(2, 0, 1, 3, 4)

    q_pos = lax.broadcasted_iota(jnp.int32, (t, block_k), 0)

    @jax.checkpoint
    def block(carry, inputs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, i_k = inputs
        s = jnp.einsum('bhqd,bhkd->bhqk', qf, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = i_k * block_k + lax.broadcasted_iota(
                jnp.int32, (t, block_k), 1)
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            'bhqk,bhkd->bhqd', p, v_blk)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    acc0 = jnp.zeros((b, h, t, d_v), jnp.float32)
    (m, l, acc), _ = lax.scan(
        block, (m0, l0, acc0),
        (k_blocks, v_blocks, jnp.arange(n_k)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention(q, k, v, causal, scale, interpret):
    return flash_attention_forward(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret)


def _fa_fwd(q, k, v, causal, scale, interpret):
    out, lse = flash_attention_forward(q, k, v, causal=causal,
                                       scale=scale, interpret=interpret,
                                       with_lse=True)
    # named for a caller's save-by-name ``remat`` policy; without one a
    # name is an identity that lowers to nothing
    q, k, v = (checkpoint_name(x, 'flash_attn.qkv') for x in (q, k, v))
    out = checkpoint_name(out, 'flash_attn.out')
    return out, (q, k, v, out, checkpoint_name(lse, 'flash_attn.lse'))


def _fa_bwd(causal, scale, interpret, residuals, g):
    # fused flash backward: residuals are just (inputs, out, lse) —
    # O(T) extra memory; P tiles reconstructed in VMEM from lse
    q, k, v, out, lse = residuals
    return flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                    scale=scale, interpret=interpret)


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def fused_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, impl: str = 'auto'):
    """Attention over q, k [B, T, H, Dqk] and v [B, T, Hkv, Dv] (the
    result is [B, T, H, Dv]) with implementation selection:

    - ``pallas``: the fused kernel (TPU)
    - ``interpret``: the kernel under the Pallas interpreter (tests)
    - ``dense``: the jnp reference
    - ``auto``: kernel on TPU when shapes tile, dense otherwise
    """
    t, d = q.shape[1], q.shape[3]
    tiles = t >= 128 and t % 128 == 0
    if impl == 'auto':
        impl = 'pallas' if (tiles and jax.default_backend() == 'tpu') \
            else 'dense'
    if impl == 'dense':
        return reference_attention(q, k, v, causal=causal, scale=scale)
    if not tiles:
        raise ValueError(
            f'pallas attention needs seq divisible by 128, got {t}')
    return _flash_attention(q, k, v, causal, scale, impl == 'interpret')


__all__ = ['fused_attention', 'flash_attention_forward',
           'reference_attention']
