"""Fused causal flash attention as a Pallas TPU kernel.

SURVEY.md §2.2: the reference delegates all device math to torch/CUDA;
the TPU build promises custom ops via Pallas. This is the first: an
online-softmax attention forward that never materialises the [T, T]
score matrix in HBM — scores live in VMEM one (block_q, block_k) tile
at a time, flowing through the MXU per tile.

Kernel structure (the canonical TPU flash layout):
- grid = (batch*heads, T/block_q, T/block_k); the LAST axis is
  sequential ("arbitrary" dimension semantics) so VMEM scratch carries
  the running max / normaliser / accumulator across k-blocks
- causal blocks strictly above the diagonal are skipped whole
  (``pl.when`` on the block predicate — ~2x fewer tiles)
- MXU dots take the INPUT dtype (bf16 pairs multiply exactly, f32
  accumulation via preferred_element_type — bit-identical to f32-cast
  operand dots at a multiple of the FLOP rate; back-to-back on the
  chip the forward ran 1.8x faster than the f32-cast version); the
  final normalised block is cast back on write

Backward: FUSED Pallas kernels — residuals are just (q, k, v, out,
lse), O(T) extra memory; P tiles are reconstructed exactly in VMEM
from the saved logsumexp. Two kernels: dq accumulates over k-blocks,
dk/dv over q-blocks, both skipping causal-dead tiles; p/ds round to
the input dtype for the gradient dots (standard flash practice, exact
for f32 inputs). Measured on the chip (B=1, H=16, D=64 bf16): fwd+bwd
16 ms at seq 8,192 — 3.9x the tokens/sec of dense+remat attention in
the full-model BENCH — and runs at seq 32,768 where the dense backward
cannot compile (its [T, T] probability tensor alone is 8.6 GB at 16k).
Block defaults re-swept on-chip in round 5 AFTER the dead-tile DMA
elision landed: forward 1024x1024 (12.9 vs 14.3 ms at the old
512x1024, B=1/H=16/T=8192/D=64 with lse; 2048x1024 measured 10.0
standalone but exceeds the 16 MB scoped-vmem limit inside the full
model — 17.25 MB — so it is not the default), backward 1024x1024
(14.3 vs 15.8 at the old 512x512; larger backward tiles also fail
VMEM). The
earlier "larger backward blocks 2-5x slower" anomaly was the
causally-DEAD tile DMA — pl.when skips compute, not the BlockSpec
copies — which the clamped index maps now elide; with dead tiles no
longer fetched, bigger tiles amortize better and the anomaly is gone.

``fused_attention`` is the entry point the transformer uses: it picks
the kernel on TPU, the interpreter in tests, and the dense jnp path
anywhere else or for shapes the kernel doesn't tile.
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
    """Dense softmax attention over [B, T, H, D] — the numerics the
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
               block_q, block_k, n_k, emit_lse):
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

    # causal: skip whole blocks above the diagonal (shared rule —
    # the index-map clamps derive from the same helpers)
    live = _block_live(i_q, i_k, block_q, block_k, causal)

    @pl.when(live)
    def _accumulate():
        # operands stay in the input dtype (bf16 for bf16 models): the
        # MXU multiplies bf16 pairs exactly and accumulates in f32 via
        # preferred_element_type, so `s` is bit-identical to the old
        # f32-cast dot at a multiple of the FLOP rate
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i_q * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i_k * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p rounds to the value dtype for the MXU (standard flash
        # practice; exact when inputs are f32)
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

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


def _last_live_k(i_q, block_q: int, block_k: int):
    """Highest k-block index with any unmasked element for q-block
    ``i_q`` — THE causal liveness rule. The kernels' skip predicates
    and the index-map clamps below both derive from it, so they cannot
    drift apart (a divergence would DMA the wrong tile for a live
    step, a correctness bug, not just lost elision)."""
    return ((i_q + 1) * block_q - 1) // block_k


def _first_live_q(i_k, block_q: int, block_k: int):
    """Dual: lowest live q-block index for k-block ``i_k``."""
    return (i_k * block_k) // block_q


def _block_live(i_q, i_k, block_q: int, block_k: int, causal: bool):
    """The kernels' skip predicate: does tile (i_q, i_k) contain any
    unmasked element?"""
    return (i_k <= _last_live_k(i_q, block_q, block_k)) \
        if causal else True


def _kv_head(bh, group: int):
    """The key-value head (folded with the batch) that query head ``bh``
    reads: with grouped-query attention ``group`` query heads share
    one. Equal head counts leave the index as it is."""
    return bh if group == 1 else bh // group


def _causal_kv_ix(block_q: int, block_k: int, causal: bool,
                  group: int = 1):
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

    def ix(bh, iq, ik):
        return (_kv_head(bh, group),
                jnp.minimum(ik, _last_live_k(iq, block_q, block_k)), 0)
    return ix


def _causal_q_ix(block_q: int, block_k: int, causal: bool,
                 group: int = 1, n_q: int = 0):
    """Dual of ``_causal_kv_ix`` for operands streamed over q-blocks
    (grid order (bh, ik, iq)): the dead steps sit BELOW the diagonal
    start, so clamp iq from below to this k-block's first live
    q-block. With grouped-query attention the grid runs over key-value
    heads and its last axis over (query head of the group, q-block):
    step ``j`` reads q-block ``j % n_q`` of query head
    ``bh * group + j // n_q``."""
    if group == 1:
        if not causal:
            return lambda bh, ik, iq: (bh, iq, 0)

        def ix(bh, ik, iq):
            return (bh,
                    jnp.maximum(iq, _first_live_q(ik, block_q, block_k)),
                    0)
        return ix

    def grouped(bh, ik, j):
        iq = j % n_q
        if causal:
            iq = jnp.maximum(iq, _first_live_q(ik, block_q, block_k))
        return (bh * group + j // n_q, iq, 0)
    return grouped


def _kv_group(q, k) -> int:
    """Query heads a key-value head serves (1 = equal head counts)."""
    h, h_kv = q.shape[2], k.shape[2]
    if h % h_kv:
        raise ValueError(f'{h} query heads over {h_kv} key-value heads')
    return h // h_kv


def _head_block(d: int, want: int) -> int:
    """Heads wider than 128 take tiles of at most 512: at 1024 x 1024
    the backward kernels' score-sized temporaries and the doubled q/k/v
    tiles pass the scoped VMEM limit."""
    return want if d <= 128 else min(want, 512)


def _fit_block(t: int, want: int) -> int:
    """Largest multiple of 128 ≤ want that divides t (any t % 128 == 0
    admits at least 128 itself, so tileability == t % 128 == 0)."""
    start = (min(want, t) // 128) * 128
    for cand in range(start, 127, -128):
        if t % cand == 0:
            return cand
    raise ValueError(f'seq len {t} not divisible by any 128-multiple '
                     f'block ≤ {want}')


def flash_attention_forward(q, k, v, causal: bool = True,
                            scale: Optional[float] = None,
                            block_q: int = 1024, block_k: int = 1024,
                            interpret: bool = False,
                            with_lse: bool = False):
    """Pallas forward over q [B, T, H, D] and k, v [B, T, Hkv, D]
    (grouped-query attention where Hkv < H: query head ``i`` reads
    key-value head ``i // (H / Hkv)``). T must divide by both block
    sizes (caller falls back to dense otherwise). ``with_lse`` also
    returns the per-row logsumexp [B, H, T] the fused backward needs."""
    b, t, h, d = q.shape
    group = _kv_group(q, k)
    scale = scale if scale is not None else d ** -0.5
    block_q = _fit_block(t, _head_block(d, block_q))
    block_k = _fit_block(t, _head_block(d, block_k))
    n_q, n_k = t // block_q, t // block_k

    # [B, T, H, D] -> [B*H, T, D]: contiguous (seq, head_dim) tiles
    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * x.shape[2], t, d)

    qf, kf, vf = fold(q), fold(k), fold(v)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, emit_lse=with_lse)

    # causal dead-tile DMA elision for the streamed k/v operands (see
    # _causal_kv_ix)
    kv_ix = _causal_kv_ix(block_q, block_k, causal, group)

    out_shape = [jax.ShapeDtypeStruct((b * h, t, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d),
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
            pl.BlockSpec((1, block_k, d), kv_ix),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # normaliser
            pltpu.VMEM((block_q, d), jnp.float32),     # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(qf, kf, vf)

    if with_lse:
        out, lse = result
        out = jnp.transpose(out.reshape(b, h, t, d), (0, 2, 1, 3))
        return out, lse[:, :, 0].reshape(b, h, t)
    out = result[0]
    return jnp.transpose(out.reshape(b, h, t, d), (0, 2, 1, 3))


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    i_q, i_k, *, scale, causal, block_q, block_k):
    """Rebuild this tile's probabilities and dS exactly as the forward
    computed them — shared by both backward kernels so their numerics
    cannot drift apart."""
    # operands stay in the input dtype: bf16 pairs multiply exactly on
    # the MXU with f32 accumulation (preferred_element_type), matching
    # the old f32-cast dots bit-for-bit at a multiple of the FLOP rate;
    # p/ds round to the input dtype for the gradient dots (standard
    # flash practice; exact when inputs are f32)
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = i_q * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = i_k * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos > q_pos, NEG_INF, s)
    p = jnp.exp(s - lse_ref[0][:, :1])
    dov = lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dov - delta_ref[0][:, :1])
    return q, k, do, p.astype(q.dtype), ds.astype(q.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, scale, causal, block_q,
                      block_k, n_k):
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _block_live(i_q, i_k, block_q, block_k, causal)

    @pl.when(live)
    def _accumulate():
        _q, k, _do, _p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i_q, i_k,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k)
        dq_scr[:] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(i_k == n_k - 1)
    def _finalise():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                       block_q, block_k, n_q, group=1):
    i_k = pl.program_id(1)
    # the last grid axis: q-blocks, and with grouped-query attention
    # the group's query heads one after the other (see _causal_q_ix)
    step = pl.program_id(2)
    i_q = step if group == 1 else step % n_q

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _block_live(i_q, i_k, block_q, block_k, causal)

    @pl.when(live)
    def _accumulate():
        q, _k, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i_q, i_k,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k)
        dv_scr[:] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]
        dk_scr[:] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(step == group * n_q - 1)
    def _finalise():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def flash_attention_backward(q, k, v, out, lse, do,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = 1024, block_k: int = 1024,
                             interpret: bool = False):
    """Fused flash backward: O(T) residuals (just out + lse), the
    probability tiles reconstructed in VMEM from lse exactly as the
    forward computed them. Two kernels: dq accumulates over k-blocks,
    dk/dv accumulate over q-blocks."""
    b, t, h, d = q.shape
    group = _kv_group(q, k)
    h_kv = h // group
    scale = scale if scale is not None else d ** -0.5
    block_q = _fit_block(t, _head_block(d, block_q))
    block_k = _fit_block(t, _head_block(d, block_k))
    n_q, n_k = t // block_q, t // block_k

    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * x.shape[2], t, d)

    qf, kf, vf, of, dof = fold(q), fold(k), fold(v), fold(out), fold(do)
    # row statistics live lane-tiled ([bh, t, 128]) so their blocks meet
    # the TPU (8, 128) trailing-dim constraint
    lsef = jnp.broadcast_to(
        lse.reshape(b * h, t)[..., None], (b * h, t, 128))
    # delta_i = rowsum(dO_i · O_i) — the dS correction term
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (b * h, t, 128))

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0))
    row_spec = pl.BlockSpec((1, block_q, 128),
                            lambda bh, iq, ik: (bh, iq, 0))

    # dead-tile DMA elision, same as the forward: dq streams k/v
    kv_ix = _causal_kv_ix(block_q, block_k, causal, group)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid=(b * h, n_q, n_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d), kv_ix),
            pl.BlockSpec((1, block_k, d), kv_ix),
            q_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    # dk/dv streams q/do/lse/delta with iq innermost (see _causal_q_ix)
    q_ix = _causal_q_ix(block_q, block_k, causal, group, n_q)
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          group=group),
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, t, d), v.dtype),
        ],
        grid=(b * h_kv, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_ix),
            k_spec, k_spec,
            pl.BlockSpec((1, block_q, d), q_ix),
            pl.BlockSpec((1, block_q, 128), q_ix),
            pl.BlockSpec((1, block_q, 128), q_ix),
        ],
        out_specs=[k_spec, k_spec],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    def unfold(x):
        return jnp.transpose(x.reshape(b, -1, t, d), (0, 2, 1, 3))

    return unfold(dq), unfold(dk), unfold(dv)


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
    scale = scale if scale is not None else d ** -0.5
    block_k = _fit_block(t, block_k) if t % 128 == 0 else t
    n_k = t // block_k

    qf = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)  # [B,H,T,D]
    kf = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32)
    vf = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32)
    k_blocks = kf.reshape(b, h, n_k, block_k, d).transpose(2, 0, 1, 3, 4)
    v_blocks = vf.reshape(b, h, n_k, block_k, d).transpose(2, 0, 1, 3, 4)

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
    acc0 = jnp.zeros((b, h, t, d), jnp.float32)
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
    """Attention over [B, T, H, D] with implementation selection:

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
