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
  logsumexp a query, [B*H, 1, T] (a row of tokens; PR 36 — it was
  written lane-tiled, sliced, and broadcast to 128 lanes again for the
  backward: 268 MB each way a layer at kanana's shape).
- THE OPERANDS' LAYOUT (PR 36; ``_panels_for``, ``_folded``). q, k and
  v enter as the projections lay them out, and are held so for the
  backward (``flash_attn.qkv``): where a head's width is not a whole
  number of 128s (64, 192) as PANELS [B*H, D, T] — the T-minor layout
  XLA gives q_proj's, k_proj's and kv_b_proj's matmuls, so the fold is a
  bitcast, and a panel pads no lane — else as ROWS [B*H, T, D], as a
  fused qkv writes them (``olmo-1b``: panels there add 9 copies a
  layer). O and dO are rows always; dq, dk, dv leave as their operands
  came. Both orientations run the same kernel bodies: a panel q-block
  is turned into rows once in VMEM (and dO into a panel in the
  backward), every other product contracts the axis the block has
  (``_tokens``, ``_mm``, ``_outer``): S = q kT and dK^T = qT dS are plain
  products of panels, dQ = dS kT^T. The layout code runs inside the
  scope ``LAYOUT_SCOPE``; the copies XLA cannot make bitcasts carry it
  (``layout_copies``, the gauge ``step.flash_layout_copies``: a kanana
  step holds 15, 3 a layer, where the parent held 45).
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
  backward, and, in VMEM only, the 192-deep contraction of S and the
  192-wide rows of dQ padded to 256 lanes (dK^T streams 192 panel rows
  and pads nothing since PR 36). Nothing else: no padded V, no second
  S.

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
PR 36 (my chip run, call 1; operands laid out as the cells' projections
lay them, parent -> change): at kanana's shape, panels, forward 12.98 ->
12.64 ms (55.2%), backward 24.64 -> 22.97 ms (60.8%), with the cell's
copies 15.68 -> 13.11 and 29.57 -> 23.86 ms; at lfm2's, panels, forward
9.46 -> 9.45, backward 15.77 -> 12.68 ms (44.0%), with copies 10.58 ->
9.81 and 17.67 -> 13.40; qwen's and OLMo's (rows) within 3%, the lse
row taking 0.1–0.2 ms of copies off each call.
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


# ------------------------------------------------- the operands' layout
# q, k and v enter the kernels in one of two orientations, picked by the
# head sizes alone (``_panels_for``): ROWS [B*H, T, D], a head's tokens
# one row each, or PANELS [B*H, D, T], a head's [D, T] panel with the
# tokens minor. ``tok`` is the token axis of such a block (0 rows, 1
# panels). O and dO are rows in both; dq, dk and dv leave as their
# operands came. The kernel bodies are the same for both: what differs
# is the BlockSpecs, which axis each product contracts (``_tokens``,
# ``_outer``, ``_mm``) and the q rows (and dO's panel) that a panel
# q-block is turned into once in VMEM.

def _panels_for(d: int, dv: int) -> bool:
    """Whether q, k, v go in as panels: where a head's rows would pad
    lanes (a width that is not a whole number of 128s: 64, 192). XLA
    lays a projection's [B, T, H, D] output out as panels there, so a
    panel is a bitcast of it where a row is a copy; a row of 64 or 192
    also pads to 128 or 256 lanes in HBM and VMEM, a panel does not.
    Heads of whole 128s keep the rows a fused projection writes
    (``olmo-1b``'s qkv), where panels would add the copies."""
    return bool(d % 128 or dv % 128)


def _tokens(ref, sl, tok: int):
    """Tokens ``sl`` of a q, k or v block ``ref`` ([1, T, D] or
    [1, D, T]), in the orientation it came."""
    return ref[0, :, sl] if tok else ref[0, sl]


def _mm(a, b, ca: int, cb: int, wide: bool):
    """a x b over a's axis ``ca`` and b's ``cb``, in float32. ``wide``
    (under the interpreter) gives the product float32 operands: the
    same sums, since a product of two bf16 numbers is exact in float32,
    which XLA:CPU runs in every form (it refuses bf16 operands for a
    product it has folded a transpose into)."""
    if wide:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _outer(x, g, tok: int, wide: bool):
    """The gradient of a strip's keys from ``g`` [rows, cols] and the
    strip's rows of ``x`` (q, or dO, in the operand's orientation): dK
    [cols, D] = dS^T q for rows, dK^T [D, cols] = qT dS for panels."""
    return _mm(x, g, 1, 0, wide) if tok else _mm(g, x, 0, 0, wide)


def _scores(q, k, scale, tok, wide, masked_from=None):
    """One strip of scaled scores in float32, q's rows against the keys
    of ``k`` (rows: q kT^T; a panel: q kT, no transpose of K).
    ``masked_from`` is the
    column of the strip's first row at which the causal mask starts
    (row ``r`` sees columns ``<= masked_from + r``): only a strip the
    diagonal crosses passes it, so a strip wholly under the diagonal
    pays no iota / compare / select."""
    # operands stay in the input dtype (bf16 for bf16 models): the MXU
    # multiplies bf16 pairs exactly and accumulates in f32 via
    # preferred_element_type
    s = _mm(q, k, 1, 1 - tok, wide) * scale
    if masked_from is not None:
        ahead = lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            - lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(ahead > masked_from, NEG_INF, s)
    return s


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
               block_q, block_k, n_k, strips, emit_lse, tok, wide):
    lse_ref = rest[0] if emit_lse else None
    rest = rest[emit_lse:]
    q_scr = rest[0] if tok else None
    m_scr, l_scr, acc_scr = rest[tok:]
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        if tok:
            # a panel's rows, turned once a q-block and kept across the
            # k loop
            q_scr[:] = q_ref[0].T
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def strip(r0, rows, cols, masked):
        """Online-softmax update of q rows ``[r0, r0 + rows)`` with the
        tile's first ``cols`` keys."""
        at = slice(r0, r0 + rows)
        v = _tokens(v_ref, slice(0, cols), tok)
        s = _scores(q_scr[at] if tok else q_ref[0, at],
                    _tokens(k_ref, slice(0, cols), tok), scale, tok, wide,
                    cols - rows if masked else None)
        m_prev = m_scr[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[at, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p rounds to the value dtype for the MXU (standard flash
        # practice; exact when inputs are f32)
        acc_scr[at] = acc_scr[at] * corr + _mm(
            p.astype(v.dtype), v, 1, tok, wide)
        m_scr[at] = jnp.broadcast_to(m_new, (rows, m_scr.shape[1]))
        l_scr[at] = jnp.broadcast_to(l_new, (rows, l_scr.shape[1]))

    _walk_tile(i_q, i_k, causal, block_q, block_k, strips, strip)

    @pl.when(i_k == n_k - 1)
    def _finalise():
        norm = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / norm[:, :1]).astype(o_ref.dtype)
        if emit_lse:
            # logsumexp a query row, written as a row of tokens: the
            # lane-tiled column turned once a q-block, so the residual
            # is [bh, 1, t], not 128 lanes of copies
            lse_ref[0] = (m_scr[:] + jnp.log(norm)).T[:1]


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
    """(head, token block) of the key and value blocks streamed over
    k-blocks (grid order (bh, iq, ik)). ``pl.when`` skips a masked
    block's COMPUTE but Pallas still copies the tiles the index map
    names — half the K/V
    HBM traffic for nothing in causal attention. Clamping to the last
    live k-block makes every dead step re-name the tile already
    resident in VMEM, and Pallas elides copies whose block index is
    unchanged. Kernels read the TRUE ik from program_id, so masking
    and skip logic are unaffected."""
    if not causal:
        return lambda bh, iq, ik: (_kv_head(bh, group), ik)
    return lambda bh, iq, ik: (
        _kv_head(bh, group), jnp.minimum(ik, _last_live_k(iq)))


def _kv_group(q, k) -> int:
    """Query heads a key-value head serves (1 = equal head counts), of
    folded operands [B*H, ...] and [B*Hkv, ...]."""
    h, h_kv = q.shape[0], k.shape[0]
    if h % h_kv:
        raise ValueError(f'{h} query heads over {h_kv} key-value heads')
    return h // h_kv


def _fold(x):
    """[B, T, H, D] -> [B*H, T, D]: a head's rows, as O and dO go in
    and out."""
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _unfold(x, b: int):
    """[B*H, T, D] -> [B, T, H, D]."""
    _, t, d = x.shape
    return jnp.transpose(x.reshape(b, -1, t, d), (0, 2, 1, 3))


def _panels(x):
    """[B, T, H, D] -> [B*H, D, T]: a head's [D, T] panel, tokens minor,
    as q, k, v and their gradients go in and out. It is the layout XLA
    gives a projection's [B, T, H, D] output ({1,3,2,0}: per head a
    [D, T] panel), so no copy stands between the producer and the
    kernel, and a panel of 192 or 64 rows pads no lane where a row of
    192 pads to 256."""
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 3, 1)).reshape(b * h, d, t)


def _unpanel(x, b: int):
    """[B*H, D, T] -> [B, T, H, D]."""
    _, d, t = x.shape
    return jnp.transpose(x.reshape(b, -1, d, t), (0, 3, 1, 2))


def _folded(x, panels: bool):
    """q, k or v [B, T, H, D] as the kernels read it."""
    return _panels(x) if panels else _fold(x)


def _unfolded(x, b: int, panels: bool):
    """A gradient as the kernel wrote it, back to [B, T, H, D]."""
    return _unpanel(x, b) if panels else _unfold(x, b)


def _spec(panels: bool, width: int, block: int, ix):
    """BlockSpec of a q, k or v block of ``block`` tokens at head size
    ``width``, whose (head, token block) the grid step's ``ix`` names."""
    if panels:
        return pl.BlockSpec((1, width, block),
                            lambda *g: (ix(*g)[0], 0, ix(*g)[1]))
    return pl.BlockSpec((1, block, width),
                        lambda *g: (ix(*g)[0], ix(*g)[1], 0))


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


def _forward(qf, kf, vf, panels: bool, causal: bool, scale: float,
             block_q: int, block_k: int, interpret: bool, with_lse: bool):
    """The forward kernel over q [B*H, ..], k and v [B*Hkv, ..], folded
    as ``panels`` says (``_folded``): the rows of O [B*H, T, Dv] and,
    ``with_lse``, the logsumexp a query row [B*H, 1, T]."""
    tok = int(panels)
    bh, t = qf.shape[0], qf.shape[1 + tok]
    d, d_v = qf.shape[2 - tok], vf.shape[2 - tok]
    group = _kv_group(qf, kf)
    block_q, block_k = _blocks(t, block_q, block_k, causal)
    n_q, n_k = t // block_q, t // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, strips=_strips(2, d, d_v),
        emit_lse=with_lse, tok=tok, wide=interpret)

    # causal dead-tile DMA elision for the streamed k/v operands (see
    # _causal_kv_ix)
    kv_ix = _causal_kv_ix(causal, group)

    out_shape = [jax.ShapeDtypeStruct((bh, t, d_v), qf.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d_v),
                              lambda bh, iq, ik: (bh, iq, 0))]
    if with_lse:
        # lse is only materialised when the caller needs residuals
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, 1, block_q), lambda bh, iq, ik: (bh, 0, iq)))

    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bh, n_q, n_k),
        in_specs=[
            _spec(panels, d, block_q, lambda bh, iq, ik: (bh, iq)),
            _spec(panels, d, block_k, kv_ix),
            _spec(panels, d_v, block_k, kv_ix),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_q, d), qf.dtype)] * tok + [
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # normaliser
            pltpu.VMEM((block_q, d_v), jnp.float32),   # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            # resident: the K and V tiles, double-buffered
            vmem_limit_bytes=_vmem_limit(
                2 * block_k * (_lanes(d) + _lanes(d_v))
                * qf.dtype.itemsize, block_q, block_k, d, d_v,
                qf.dtype.itemsize)),
        interpret=interpret,
    )(qf, kf, vf)


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
    scale = scale if scale is not None else d ** -0.5
    panels = _panels_for(d, v.shape[-1])
    result = _forward(*(_folded(x, panels) for x in (q, k, v)), panels,
                      causal, scale, block_q, block_k, interpret, with_lse)
    out = _unfold(result[0], b)
    if with_lse:
        return out, result[1].reshape(b, h, t)
    return out


def _fa_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, dk_ref, dv_ref, *scratch,
                   scale, causal, block_q, block_k, n_q, n_k, strips, tok,
                   wide):
    """One q-block of one query head against the ``n_k`` k-blocks of
    its key-value head that this span holds in VMEM: S, P, dP and dS
    are made once a strip and feed all three gradients, which
    accumulate in their operands' orientation (dQ as rows, turned once
    a step where q is a panel)."""
    q_scr, dot_scr = scratch[:2] if tok else (None, None)
    dq_scr, dk_scr, dv_scr, lse_scr, delta_scr = scratch[2 * tok:]
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
    if tok:
        # a panel q-block's rows and dO's panel, each turned once a step
        q_scr[:] = q_ref[0].T
        dot_scr[:] = do_ref[0].T
    # lse from its row of tokens, and delta_i = rowsum(dO_i · O_i), the
    # dS correction term, from the q-block's own rows: both lane-tiled
    # columns in VMEM, no [bh, t, 128] array of either in HBM
    lse_scr[:] = jnp.broadcast_to(lse_ref[0], (128, block_q)).T
    delta_scr[:] = jnp.broadcast_to(jnp.sum(
        do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1, keepdims=True), delta_scr.shape)

    def strip(r0, rows, k0, cols, masked):
        """q rows ``[r0, r0 + rows)`` against the span's keys
        ``[k0, k0 + cols)``."""
        at = slice(r0, r0 + rows)
        keys = pl.ds(k0, cols)
        k, v = _tokens(k_ref, keys, tok), _tokens(v_ref, keys, tok)
        # the probabilities exactly as the forward made them, from the
        # saved logsumexp; p and ds round to the input dtype for the
        # gradient products (standard flash practice; exact when
        # inputs are f32)
        s = _scores(q_scr[at] if tok else q_ref[0, at], k, scale, tok,
                    wide, cols - rows if masked else None)
        p = jnp.exp(s - lse_scr[at, :1])
        dp = _mm(do_ref[0, at], v, 1, 1 - tok, wide)
        ds = (p * (dp - delta_scr[at, :1])).astype(k.dtype)
        p = p.astype(k.dtype)
        to = (slice(None), keys) if tok else (keys,)
        dv_scr[to] += _outer(dot_scr[:, at] if tok else do_ref[0, at],
                             p, tok, wide)
        dk_scr[to] += _outer(_tokens(q_ref, at, tok), ds, tok,
                             wide) * scale
        dq_scr[at] += _mm(ds, k, 1, tok, wide) * scale

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

    dq = dq_scr[:].astype(dq_ref.dtype)
    dq_ref[0, 0] = dq.T if tok else dq

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


def _backward(qf, kf, vf, of, lse, dof, panels: bool, causal: bool,
              scale: float, block_q: int, block_k: int, interpret: bool):
    """The backward kernel over the forward's q, k, v (folded as
    ``panels`` says), its rows of O and dO [B*H, T, Dv] and its lse
    [B*H, 1, T]: dq, dk, dv, each folded as its operand."""
    tok = int(panels)
    bh, t = qf.shape[0], qf.shape[1 + tok]
    d, d_v = qf.shape[2 - tok], vf.shape[2 - tok]
    group = _kv_group(qf, kf)
    block_q, block_k = _blocks(t, block_q, block_k, causal)
    n_q = t // block_q
    span = _span(t, block_k, d, d_v, qf.dtype.itemsize)
    spans = t // span

    # grid (key-value head, span, (query head of the group, q-block)):
    # step ``j`` of the last axis reads q-block ``j % n_q`` of query
    # head ``bh * group + j // n_q``
    def q_ix(bh, s, j):
        return bh * group + j // n_q, j % n_q

    def kv_ix(bh, s, j):
        return bh, s

    # q and dq at the score head's size, O and dO at the value head's
    q_spec = _spec(panels, d, block_q, q_ix)
    o_spec = _spec(False, d_v, block_q, q_ix)
    lse_spec = _spec(True, 1, block_q, q_ix)
    k_spec = _spec(panels, d, span, kv_ix)
    v_spec = _spec(panels, d_v, span, kv_ix)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          n_k=span // block_k, strips=_strips(5, d, d_v),
                          tok=tok, wide=interpret),
        out_shape=[
            # one partial of dq a span; a single span's is dq itself
            jax.ShapeDtypeStruct(
                (spans,) + qf.shape,
                qf.dtype if spans == 1 else jnp.float32),
            jax.ShapeDtypeStruct(kf.shape, kf.dtype),
            jax.ShapeDtypeStruct(vf.shape, vf.dtype),
        ],
        grid=(kf.shape[0], spans, group * n_q),
        in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, lse_spec],
        out_specs=[
            pl.BlockSpec((1,) + q_spec.block_shape,
                         lambda bh, s, j: (s,) + q_spec.index_map(
                             bh, s, j)),
            k_spec, v_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), qf.dtype),      # a panel's q rows
            pltpu.VMEM((d_v, block_q), dof.dtype),   # dO's panel
        ] * tok + [
            pltpu.VMEM((block_q, d), jnp.float32),   # dq, rows
            pltpu.VMEM(k_spec.block_shape[1:], jnp.float32),
            pltpu.VMEM(v_spec.block_shape[1:], jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),  # lse
            pltpu.VMEM((block_q, 128), jnp.float32),  # delta
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=_vmem_limit(
                _resident_bytes(span, d, d_v, qf.dtype.itemsize),
                block_q, block_k, d, d_v, qf.dtype.itemsize)),
        interpret=interpret,
    )(qf, kf, vf, of, dof, lse)
    return (dq[0] if spans == 1
            else jnp.sum(dq, axis=0).astype(qf.dtype)), dk, dv


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
    scale = scale if scale is not None else d ** -0.5
    panels = _panels_for(d, v.shape[-1])
    grads = _backward(*(_folded(x, panels) for x in (q, k, v)),
                      _fold(out), lse.reshape(b * h, 1, t), _fold(do),
                      panels, causal, scale, block_q, block_k, interpret)
    return tuple(_unfolded(g, b, panels) for g in grads)


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


# The scope of the op's layout code: the panels and rows it makes of
# the caller's [B, T, H, D] and back, and the names of what it holds.
# The kernels themselves stay outside it, named by the caller's scope
# (their ops' names are what the roofline readers match). A copy XLA
# cannot turn into a bitcast carries it (``layout_copies``).
LAYOUT_SCOPE = 'flash_layout'


def layout_copies(hlo_text: str, scope: str = LAYOUT_SCOPE) -> int:
    """The ``copy`` instructions of a compiled program's text that the
    flash op's layout code left in it (the gauge
    ``step.flash_layout_copies``): those at the top level of a
    computation whose ``op_name`` runs through ``scope``. A copy
    inside a fused computation moves no bytes of its own."""
    copies, fused = 0, False
    for line in hlo_text.splitlines():
        if line.startswith(('%', 'ENTRY')):     # a computation's header
            # ``%fused_computation.3``, ``%bitcast_fusion.1``
            fused = 'fus' in line.split(' ', 1)[0]
        elif not fused and ' copy(' in line and f'/{scope}/' in line:
            copies += 1
    return copies


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, scale, interpret, panels):
    return flash_attention_forward(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret)


def _fa_fwd(q, k, v, causal, scale, interpret, panels):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    with jax.named_scope(LAYOUT_SCOPE):
        # named for a caller's save-by-name ``remat`` policy; without
        # one a name is an identity that lowers to nothing. The folded
        # operands are held, so the backward reads them as they were
        qf, kf, vf = (checkpoint_name(_folded(x, panels), 'flash_attn.qkv')
                      for x in (q, k, v))
    of, lse = _forward(qf, kf, vf, panels, causal, scale, 1024, 1024,
                       interpret, with_lse=True)
    with jax.named_scope(LAYOUT_SCOPE):
        of = checkpoint_name(of, 'flash_attn.out')
        lse = checkpoint_name(lse, 'flash_attn.lse')
        return _unfold(of, q.shape[0]), (qf, kf, vf, of, lse)


def _fa_bwd(causal, scale, interpret, panels, residuals, g):
    # fused flash backward: residuals are just (inputs, out, lse) —
    # O(T) extra memory; P tiles reconstructed in VMEM from lse
    qf, kf, vf, of, lse = residuals
    scale = scale if scale is not None else qf.shape[2 - panels] ** -0.5
    with jax.named_scope(LAYOUT_SCOPE):
        dof = _fold(g)
    grads = _backward(qf, kf, vf, of, lse, dof, panels, causal, scale,
                      1024, 1024, interpret)
    with jax.named_scope(LAYOUT_SCOPE):
        return tuple(_unfolded(x, g.shape[0], panels) for x in grads)


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
    return _flash_attention(q, k, v, causal, scale, impl == 'interpret',
                            _panels_for(d, v.shape[3]))


__all__ = ['fused_attention', 'flash_attention_forward',
           'reference_attention']
