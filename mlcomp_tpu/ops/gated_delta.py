"""Gated delta rule (Gated DeltaNet's linear attention) in chunked form.

Per head, with a state ``S`` in R^{dk x dv} (key x value), ``S_0 = 0``
and, for every token ``t``: a log-decay ``g_t <= 0``, a write strength
``beta_t``, a key ``k_t``, a value ``v_t`` and a query ``q_t``::

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

Token by token that is ``T`` dependent rank-one updates. The chunked
form (Yang et al., Gated Delta Networks, arXiv:2412.06464, section 3)
does a chunk of ``C`` tokens with matrix products and carries ONE state
from chunk to chunk. With ``gamma`` the running sum of ``g`` inside the
chunk and ``S`` the state the chunk starts from::

    A     = strict_lower(beta_i exp(gamma_i - gamma_j) k_i.k_j)
    T     = (I + A)^-1                       (unit lower triangular)
    U, W  = T (beta v),  T (beta exp(gamma) k)
    D     = U - W S                          (the chunk's deltas d_t)
    O     = (q exp(gamma)) S + lower(exp(gamma_t - gamma_i) q_t.k_i) D
    S'    = exp(gamma_C) S + (k exp(gamma_C - gamma))^T D

Everything up to ``U, W`` and the masked ``q k^T`` is local to a chunk.
What is sequential — ``D``, ``O`` and ``S'``, chunk after chunk — is the
scan. On the TPU (``impl='pallas'``) the op is four kernels under one
``custom_vjp``: ``gated_delta_prepare`` makes the operands of a pair of
chunks in VMEM (the running sum, ``A``, the float32 inverse by block
joins, ``U``, ``W``, the masked ``q k^T``) and writes them where the
scan reads them;
``gated_delta_fwd`` keeps the state in VMEM in float32 and saves the
state each group of chunks starts from; ``gated_delta_bwd_scan`` walks
the chunks backwards, recomputes ``D`` within the chunk from the saved
state, and carries ``dS``; ``gated_delta_prepare_bwd`` pulls the
operands' cotangents back to the inputs in closed form
(``dA = -strict_lower(T^T dT T^T)``), with the ``T`` that the
backward's own ``gated_delta_prepare`` hands it. Off the TPU
(``impl='xla'``) the chunk-local part is batched XLA products that XLA
differentiates itself (``_prepare``, the oracle the kernels are tested
against) and the recurrence a checkpointed ``lax.scan``.

The state, the decays and every accumulation are float32; the matrix
products take their operands in the input dtype (bfloat16 in a bfloat16
model, so float32 inputs give float32 throughout).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = lax.Precision.HIGHEST


# ------------------------------------------------------- within a chunk
def inv_unit_lower(mat, base: int = 16):
    """Inverse of unit lower-triangular matrices [..., n, n] (float32).

    Blocks of ``base`` are inverted by the finite Neumann product
    ``(I - A)(I + A^2)(I + A^4)...`` (exact: the strict part ``A`` is
    nilpotent), and halves are joined by ``X21 = -X22 L21 X11`` — all
    matrix products, no row-by-row substitution."""
    n = mat.shape[-1]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    if n <= base:
        eye = jnp.eye(n, dtype=mat.dtype)
        neg = eye - mat                     # -A
        out, power, span = eye + neg, neg, 1
        while 2 * span < n:
            power = mm(power, power)
            out = mm(out, eye + power)
            span *= 2
        return out
    h = n // 2
    x11 = inv_unit_lower(mat[..., :h, :h], base)
    x22 = inv_unit_lower(mat[..., h:, h:], base)
    x21 = -mm(mm(x22, mat[..., h:, :h]), x11)
    top = jnp.concatenate([x11, jnp.zeros_like(mat[..., :h, h:])], -1)
    return jnp.concatenate(
        [top, jnp.concatenate([x21, x22], -1)], -2)


def _prepare(q, k, v, g, beta, chunk: int, solve_base: int):
    """[B,T,H,*] inputs -> the chunk-local operands of the scan, each
    [B*H, N, C, *]: (qg, kd, w, u, p, a)."""
    b, t, h, _ = q.shape
    n = t // chunk
    dtype = q.dtype
    f32 = jnp.float32

    def fold(x):            # [B,T,H,...] -> [B*H, N, C, ...]
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((b * h, n, chunk) + x.shape[3:])

    q, k, v = fold(q), fold(k), fold(v)
    g, beta = fold(g.astype(f32)), fold(beta.astype(f32))
    gamma = jnp.cumsum(g, axis=-1)                      # [BH,N,C]
    diff = gamma[..., :, None] - gamma[..., None, :]    # gamma_t - gamma_i
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = jnp.exp(jnp.where(col <= row, diff, -jnp.inf))  # incl. diag
    kk = jnp.einsum('...cd,...ed->...ce', k, k,
                    preferred_element_type=f32)
    a_mat = jnp.where(col < row, beta[..., :, None] * kk * lower, 0.0)
    t_mat = inv_unit_lower(
        a_mat + jnp.eye(chunk, dtype=f32), solve_base).astype(dtype)
    decay = jnp.exp(gamma)[..., None]
    bv = (v.astype(f32) * beta[..., None]).astype(dtype)
    bk = (k.astype(f32) * (beta[..., None] * decay)).astype(dtype)
    u = jnp.einsum('...ce,...ed->...cd', t_mat, bv,
                   preferred_element_type=f32).astype(dtype)
    w = jnp.einsum('...ce,...ed->...cd', t_mat, bk,
                   preferred_element_type=f32).astype(dtype)
    p = (jnp.einsum('...cd,...ed->...ce', q, k,
                    preferred_element_type=f32) * lower).astype(dtype)
    last = gamma[..., -1:]
    qg = (q.astype(f32) * decay).astype(dtype)
    kd = (k.astype(f32)
          * jnp.exp(last - gamma)[..., None]).astype(dtype)
    a = jnp.exp(last[..., 0])                            # [BH,N]
    return qg, kd, w, u, p, a


# ---------------------------------------------- chunk to chunk, in XLA
def _scan_xla(qg, kd, w, u, p, a):
    """The recurrence over chunks as a checkpointed ``lax.scan``."""
    f32 = jnp.float32
    dtype = qg.dtype
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)

    @jax.checkpoint
    def body(state, xs):
        qg_c, kd_c, w_c, u_c, p_c, a_c = xs
        sb = state.astype(dtype)
        d = u_c.astype(f32) - dot('bcd,bde->bce', w_c, sb)
        db = d.astype(dtype)
        o = dot('bcd,bde->bce', qg_c, sb) + dot('bct,bte->bce', p_c, db)
        state = a_c[:, None, None] * state \
            + dot('bcd,bce->bde', kd_c, db)
        return state, o.astype(dtype)

    bh, _, _, dk = qg.shape
    state = jnp.zeros((bh, dk, u.shape[-1]), f32)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (qg, kd, w, u, p, a))
    _, out = lax.scan(body, state, xs)
    return jnp.moveaxis(out, 0, 1)


# ------------------------------------------- chunk to chunk, the kernel
def _dot(x, y, dims):
    return lax.dot_general(x, y, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN = ((1,), (0,))      # x @ y
_NT = ((1,), (1,))      # x @ y.T
_TN = ((0,), (0,))      # x.T @ y


def _fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, a_ref,
                o_ref, s_ref, state, *, group):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    dtype = qg_ref.dtype
    s_ref[0, 0] = state[...]        # the state this group of chunks starts from
    for c in range(group):
        s = state[...]
        sb = s.astype(dtype)
        d = u_ref[0, c].astype(jnp.float32) - _dot(w_ref[0, c], sb, _NN)
        db = d.astype(dtype)
        o = _dot(qg_ref[0, c], sb, _NN) + _dot(p_ref[0, c], db, _NN)
        o_ref[0, c] = o.astype(o_ref.dtype)
        state[...] = a_ref[0, c][:, :1] * s + _dot(kd_ref[0, c], db, _TN)


def _bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, a_ref, s_ref, do_ref,
                dqg_ref, dkd_ref, dw_ref, du_ref, dp_ref, da_ref,
                dstate, starts, *, group):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    dtype = qg_ref.dtype

    def deltas(c, s):
        d = u_ref[0, c].astype(jnp.float32) \
            - _dot(w_ref[0, c], s.astype(dtype), _NN)
        return d.astype(dtype)

    # forwards through the group again: the state each chunk starts from
    s = s_ref[0, 0]
    for c in range(group):
        starts[c] = s
        if c < group - 1:
            s = a_ref[0, c][:, :1] * s \
                + _dot(kd_ref[0, c], deltas(c, s), _TN)
    # and backwards, the deltas recomputed within each chunk
    for c in reversed(range(group)):
        s = starts[c]
        sb = s.astype(dtype)
        db = deltas(c, s)
        ds_next = dstate[...]               # d loss / d (state after chunk)
        dsb = ds_next.astype(dtype)
        do = do_ref[0, c]
        dd = _dot(p_ref[0, c], do, _TN) + _dot(kd_ref[0, c], dsb, _NN)
        ddb = dd.astype(dtype)
        dqg_ref[0, c] = _dot(do, sb, _NT).astype(dqg_ref.dtype)
        dp_ref[0, c] = _dot(do, db, _NT).astype(dp_ref.dtype)
        dkd_ref[0, c] = _dot(db, dsb, _NT).astype(dkd_ref.dtype)
        dw_ref[0, c] = (-_dot(ddb, sb, _NT)).astype(dw_ref.dtype)
        du_ref[0, c] = ddb.astype(du_ref.dtype)
        da_ref[0, c] = jnp.full(da_ref.shape[2:], jnp.sum(ds_next * s),
                                jnp.float32)
        dstate[...] = (_dot(qg_ref[0, c], do, _TN)
                       + a_ref[0, c][:, :1] * ds_next
                       - _dot(w_ref[0, c], ddb, _TN))


def _fit(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is at most ``want``."""
    return max(g for g in range(1, min(n, want) + 1) if n % g == 0)


def _lane(a):
    """[BH,N] float32 -> [BH,N,1,128], lane-replicated for a block."""
    return jnp.broadcast_to(a[..., None, None], a.shape + (1, 128))


def _specs(group, chunk, dk, dv, index):
    return {
        'k': pl.BlockSpec((1, group, chunk, dk), index),
        'v': pl.BlockSpec((1, group, chunk, dv), index),
        'p': pl.BlockSpec((1, group, chunk, chunk), index),
        'a': pl.BlockSpec((1, group, 1, 128), index),
        's': pl.BlockSpec((1, 1, dk, dv), index),
    }


def _scan_pallas_fwd(qg, kd, w, u, p, a, group, interpret):
    """-> (o [BH,N,C,dv], the state each GROUP of chunks starts from
    [BH, N/group, dk, dv] float32)."""
    bh, n, chunk, dk = qg.shape
    dv = u.shape[-1]
    sp = _specs(group, chunk, dk, dv, lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, group=group),
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((bh, n // group, dk, dv),
                                        jnp.float32)],
        grid=(bh, n // group),
        in_specs=[sp['k'], sp['k'], sp['k'], sp['v'], sp['p'], sp['a']],
        out_specs=[sp['v'], sp['s']],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
        name='gated_delta_fwd',
    )(qg, kd, w, u, p, _lane(a))


def _scan_pallas_bwd(qg, kd, w, u, p, a, states, do, group, interpret):
    bh, n, chunk, dk = qg.shape
    dv = u.shape[-1]
    last = n // group - 1
    # the chunks backwards: grid step j works on block last - j
    sp = _specs(group, chunk, dk, dv, lambda i, j: (i, last - j, 0, 0))
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, group=group),
        out_shape=[jax.ShapeDtypeStruct(qg.shape, qg.dtype),
                   jax.ShapeDtypeStruct(kd.shape, kd.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct((bh, n, 1, 128), f32)],
        grid=(bh, n // group),
        in_specs=[sp['k'], sp['k'], sp['k'], sp['v'], sp['p'], sp['a'],
                  sp['s'], sp['v']],
        out_specs=[sp['k'], sp['k'], sp['k'], sp['v'], sp['p'], sp['a']],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                        pltpu.VMEM((group, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
        name='gated_delta_bwd_scan',
    )(qg, kd, w, u, p, _lane(a), states, do)
    return tuple(out[:5]) + (out[5][:, :, 0, 0],)


# ---------------------------------------------- within a chunk, the kernel
# ``_prepare`` again in VMEM, two consecutive chunks at a time: the same
# mathematics with the same casts, the inverse in float32 at full
# precision. A pair's [C,C] matrices lie side by side on the lanes,
# ``wide`` [C,2C] = [X1 | X2]; a product takes its right-hand side as
# ``diag(Y1, Y2)`` [2C,2C], so that ``[X1 | X2] diag(Y1, Y2)`` =
# ``[X1 Y1 | X2 Y2]`` fills a 128 x 128 tile where C is 64. Rows of q, k,
# v stay stacked [2C,d] as the tokens lie, and ``stacked stacked^T`` is
# ``diag`` with cross terms that ``_wide`` leaves out. The gates come as
# rows [1,2C]; a column is a masked sum along the lanes, a running sum
# one along the sublanes, so nothing is transposed.
def _dot32(x, y, dims=_NN):
    return lax.dot_general(x, y, (dims, ((), ())), precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def _diag(x, left):
    """wide [C,2C] -> [2C,2C] with the halves on the diagonal."""
    zero = jnp.zeros_like(x)
    return jnp.concatenate(
        [jnp.where(left, x, zero), jnp.where(left, zero, x)], 0)


def _wide(x, left):
    """[2C,2C] -> its diagonal blocks side by side, [C,2C]."""
    half = x.shape[0] // 2
    return jnp.where(left, x[:half], x[half:])


def _inv_unit_lower_pairs(a_mats, row, col, left):
    """``(I + A)^-1`` of strictly lower float32 ``A``, wide, C a power
    of two, by ``inv_unit_lower``'s joins alone, each on the whole
    block: ``I - A`` inverts the 2 x 2 diagonal blocks exactly, and
    blocks of 2, 4, ... are joined to twice their size by ``X - X L X``
    with ``L`` the part of ``A`` the join brings in. Ten products
    whatever the base, so the base is the smallest: no power of ``A``
    is ever formed. (``i // s == j // s`` is ``i ^ j < s``.) A list of
    pairs goes through level by level: a pair's products depend on each
    other, those of different pairs fill the MXU's pipeline."""
    apart = jnp.bitwise_xor(row, col)
    outs = [jnp.where(row == col, 1.0, jnp.where(apart < 2, -a, 0.0))
            for a in a_mats]
    size = 2
    while size < row.shape[0]:
        join = (apart >= size) & (apart < 2 * size)
        steps = [_dot32(out, _diag(jnp.where(join, a, 0.0), left))
                 for out, a in zip(outs, a_mats)]
        outs = [out - _dot32(step, _diag(out, left))
                for out, step in zip(outs, steps)]
        size *= 2
    return outs


def _pair_local(q, k, v, g_row, beta_row):
    """A pair's q, k [2C,dk], v [2C,dv] and gates [1,2C] -> what
    ``_prepare`` makes of them up to ``A``, by name: [C,C] matrices
    wide, per-token factors as columns [2C,1] (``*_col``) or rows."""
    f32 = jnp.float32
    dtype = q.dtype
    chunk = q.shape[0] // 2
    row = lax.broadcasted_iota(jnp.int32, (chunk, 2 * chunk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, 2 * chunk), 1)
    col = lane & (chunk - 1)
    left = lane < chunk

    def columns(x, keep):       # [1,2C] -> each half's [C,1]
        x = jnp.where(keep, x, 0.0)
        return (jnp.sum(jnp.where(left, x, 0.0), 1, keepdims=True),
                jnp.sum(jnp.where(left, 0.0, x), 1, keepdims=True))

    beta_cols = columns(beta_row, row == col)
    gamma_cols = columns(g_row, col <= row)
    gamma = jnp.where(left, *gamma_cols)
    gamma_row = jnp.sum(jnp.where(row == col, gamma, 0.0), 0,
                        keepdims=True)
    lasts = [jnp.sum(jnp.where(row[:, :1] == chunk - 1, x, 0.0), 0,
                     keepdims=True) for x in gamma_cols]        # [1,1]
    beta_col = jnp.concatenate(beta_cols, 0)
    gamma_col = jnp.concatenate(gamma_cols, 0)
    last_col = jnp.concatenate(
        [jnp.broadcast_to(x, (chunk, 1)) for x in lasts], 0)
    lower = jnp.exp(jnp.where(col <= row, gamma - gamma_row, -jnp.inf))
    kk = _wide(_dot(k, k, _NT), left)
    decay_col = jnp.exp(gamma_col)
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    return dict(
        row=row, col=col, left=left, beta_row=beta_row, beta_col=beta_col,
        gamma=gamma, gamma_row=gamma_row, lower=lower, kk=kk,
        a_mat=jnp.where(
            col < row, jnp.where(left, *beta_cols) * kk * lower, 0.0),
        decay_col=decay_col, tail_col=jnp.exp(last_col - gamma_col),
        q32=q32, k32=k32, v32=v32, qk=_wide(_dot(q, k, _NT), left),
        bv=(v32 * beta_col).astype(dtype),
        bk=(k32 * (beta_col * decay_col)).astype(dtype),
        a=[jnp.exp(x) for x in lasts])


def _pairs_local(q_ref, k_ref, v_ref, g_ref, beta_ref, group, chunk):
    """-> per pair of the group: its rows of q, k, v, ``_pair_local``."""
    out = []
    for pair in range(group // 2):
        tokens = slice(2 * pair * chunk, 2 * (pair + 1) * chunk)
        out.append((tokens, _pair_local(
            q_ref[0, tokens], k_ref[0, tokens], v_ref[0, tokens],
            g_ref[0, pair], beta_ref[0, pair])))
    return out


def _halves(ref, pair, value):
    """Store a stacked [2C,d] value as the pair's two chunks."""
    chunk = value.shape[0] // 2
    ref[0, 2 * pair] = value[:chunk].astype(ref.dtype)
    ref[0, 2 * pair + 1] = value[chunk:].astype(ref.dtype)


def _prepare_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                    qg_ref, kd_ref, w_ref, u_ref, p_ref, a_ref, *t_ref,
                    group):
    chunk = p_ref.shape[-1]
    dtype = q_ref.dtype
    local = _pairs_local(q_ref, k_ref, v_ref, g_ref, beta_ref, group,
                         chunk)
    _, x = local[0]
    inverses = _inv_unit_lower_pairs(
        [x['a_mat'] for _, x in local], x['row'], x['col'], x['left'])
    for pair, ((_, x), t32) in enumerate(zip(local, inverses)):
        t = _diag(t32.astype(dtype), x['left'])
        _halves(u_ref, pair, _dot(t, x['bv'], _NN))
        _halves(w_ref, pair, _dot(t, x['bk'], _NN))
        _halves(qg_ref, pair, x['q32'] * x['decay_col'])
        _halves(kd_ref, pair, x['k32'] * x['tail_col'])
        p = (x['qk'] * x['lower']).astype(dtype)
        p_ref[0, 2 * pair] = p[:, :chunk]
        p_ref[0, 2 * pair + 1] = p[:, chunk:]
        for c, a in enumerate(x['a']):
            a_ref[0, 2 * pair + c] = jnp.broadcast_to(a, a_ref.shape[2:])
        for ref in t_ref:       # the backward's call asks for T
            ref[0, pair] = t32


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref,
                        dqg_ref, dkd_ref, dw_ref, du_ref, dp_ref, da_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *,
                        group):
    """The pullback of ``_prepare_kernel`` in closed form: with
    ``dT = du (beta v)^T + dw (beta e^gamma k)^T`` the inverse gives
    ``dA = -strict_lower(T^T dT T^T)``; the rest is element-wise, row
    sums minus column sums for ``gamma`` and a reverse running sum back
    to ``g``. The inverse's side is worked on transposed (``dT^T``,
    ``dA^T = -strict_upper(T dT^T T)``, ``A^T``), because a wide
    left-hand side cannot be: every transposed factor is at hand, as
    ``k k^T`` is symmetric. ``T`` comes from the forward kernel."""
    f32 = jnp.float32
    chunk = dp_ref.shape[-1]
    dtype = q_ref.dtype

    def stacked(ref, pair):
        return jnp.concatenate([ref[0, 2 * pair], ref[0, 2 * pair + 1]], 0)

    def rows_sum(x):
        return jnp.sum(x, 1, keepdims=True)

    local = _pairs_local(q_ref, k_ref, v_ref, g_ref, beta_ref, group,
                         chunk)
    _, x = local[0]
    row, col, left = x['row'], x['col'], x['left']
    # the inverse, transposed; level by level as in the forward
    inverses = [t_ref[0, pair] for pair in range(len(local))]
    seeds = [(stacked(du_ref, pair), stacked(dw_ref, pair))
             for pair in range(len(local))]
    steps = [_dot32(t32, _diag(_wide(
        _dot(x['bv'], du, _NT) + _dot(x['bk'], dw, _NT), left), left))
        for (_, x), t32, (du, dw) in zip(local, inverses, seeds)]
    d_ats = [jnp.where(row < col, -_dot32(step, _diag(t32, left)), 0.0)
             for step, t32 in zip(steps, inverses)]
    ts = [_diag(t32.astype(dtype), left) for t32 in inverses]
    d_bvs = [_dot(t, du, _TN) for t, (du, _) in zip(ts, seeds)]
    d_bks = [_dot(t, dw, _TN) for t, (_, dw) in zip(ts, seeds)]
    for pair, (tokens, x) in enumerate(local):
        q, k = q_ref[0, tokens], k_ref[0, tokens]
        beta_col, decay_col = x['beta_col'], x['decay_col']
        k32, tail_col = x['k32'], x['tail_col']
        d_bv, d_bk = d_bvs[pair], d_bks[pair]
        lower_t = jnp.exp(jnp.where(
            row <= col, x['gamma_row'] - x['gamma'], -jnp.inf))
        d_at_kk = d_ats[pair] * x['kk'] * lower_t
        d_kkt = _diag(
            (d_ats[pair] * x['beta_row'] * lower_t).astype(dtype), left)
        # the masked q k^T
        d_p = jnp.concatenate(
            [dp_ref[0, 2 * pair], dp_ref[0, 2 * pair + 1]], 1).astype(f32)
        d_qk = _diag((d_p * x['lower']).astype(dtype), left)
        # d loss / d (gamma_i - gamma_j), through p's decays and A's
        pairs = d_p * x['qk'] * x['lower'] - d_at_kk * x['beta_row']
        d_qg = stacked(dqg_ref, pair).astype(f32)
        d_kd = stacked(dkd_ref, pair).astype(f32)
        by_bk = rows_sum(d_bk * k32)
        by_kd = rows_sum(d_kd * k32) * tail_col
        d_gamma_col = (by_bk * beta_col * decay_col
                       + rows_sum(d_qg * x['q32']) * decay_col - by_kd)
        d_beta_col = rows_sum(d_bv * x['v32']) + by_bk * decay_col
        across = jnp.sum(pairs, 0, keepdims=True)       # column sums
        d_gammas = []
        for c, keep in enumerate((left, ~left)):
            half = slice(c * chunk, (c + 1) * chunk)
            d_last = jnp.sum(by_kd[half], 0, keepdims=True) \
                + da_ref[0, 2 * pair + c][:, :1] * x['a'][c]
            d_gammas.append(
                d_gamma_col[half] + rows_sum(jnp.where(keep, pairs, 0.0))
                - rows_sum(jnp.where(keep & (row == col), across, 0.0))
                + jnp.where(row[:, :1] == chunk - 1, d_last, 0.0))
        dq_ref[0, tokens] = (_dot(d_qk, k, _NN)
                             + d_qg * decay_col).astype(dtype)
        dk_ref[0, tokens] = (
            _dot(d_qk, q, _TN) + _dot(d_kkt, k, _NN) + _dot(d_kkt, k, _TN)
            + d_bk * (beta_col * decay_col) + d_kd * tail_col).astype(dtype)
        dv_ref[0, tokens] = (d_bv * beta_col).astype(dtype)
        # gamma is g's running sum: g_t gets every gamma_i with i >= t
        dg_ref[0, pair] = jnp.sum(jnp.where(
            row >= col, jnp.where(left, *d_gammas), 0.0), 0, keepdims=True)
        dbeta_ref[0, pair] = jnp.sum(d_at_kk, 0, keepdims=True) + jnp.sum(
            jnp.where(row == col, jnp.where(
                left, d_beta_col[:chunk], d_beta_col[chunk:]), 0.0), 0,
            keepdims=True)


def _prepare_call(kernel, name, inputs, given, out_like, chunk, group,
                  interpret):
    """A grid of (sequence x head, group of chunks) over q, k, v where
    they lie, [B,T,H,*] read as [B,T,H*d] in blocks of one head's
    width, and over the gates as rows of a pair [B*H,N/2,1,2C].
    ``given`` (kind, array) and the outputs named by ``out_like``
    follow: 'q', 'v', 'g' as the inputs; 'k', 'v_folded', 'p', 'a' as
    ``_specs`` has them; 't' a pair's wide float32 ``T``."""
    q, k, v, g, beta = inputs
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    f32 = jnp.float32
    if chunk & (chunk - 1) or group % 2:
        raise ValueError(
            f'the kernels work on pairs of chunks and join blocks of 2, '
            f'4, ...: chunk {chunk} has to be a power of two, the group '
            f'{group} even')

    def wide(d):
        return pl.BlockSpec((1, group * chunk, d),
                            lambda i, j: (i // h, j, i % h))

    def index(i, j):
        return (i, j, 0, 0)

    folded = _specs(group, chunk, dk, dv, index)
    kinds = {
        'q': (wide(dk), (b, t, h * dk), q.dtype),
        'v': (wide(dv), (b, t, h * dv), q.dtype),
        'g': (pl.BlockSpec((1, group // 2, 1, 2 * chunk), index),
              (b * h, n // 2, 1, 2 * chunk), f32),
        't': (pl.BlockSpec((1, group // 2, chunk, 2 * chunk), index),
              (b * h, n // 2, chunk, 2 * chunk), f32),
        'k': (folded['k'], (b * h, n, chunk, dk), q.dtype),
        'v_folded': (folded['v'], (b * h, n, chunk, dv), q.dtype),
        'p': (folded['p'], (b * h, n, chunk, chunk), q.dtype),
        'a': (folded['a'], (b * h, n, 1, 128), f32),
    }

    def gates(x):           # [B,T,H] -> [B*H, N/2, 1, 2C]
        return jnp.moveaxis(x, 2, 1).reshape(kinds['g'][1])

    return pl.pallas_call(
        functools.partial(kernel, group=group),
        out_shape=[jax.ShapeDtypeStruct(*kinds[kind][1:])
                   for kind in out_like],
        grid=(b * h, n // group),
        in_specs=[wide(dk), wide(dk), wide(dv), kinds['g'][0],
                  kinds['g'][0]] + [kinds[kind][0] for kind, _ in given],
        out_specs=[kinds[kind][0] for kind in out_like],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')),
        interpret=interpret,
        name=name,
    )(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), gates(g), gates(beta),
      *(x for _, x in given))


def _prepare_pallas(q, k, v, g, beta, chunk, group, interpret,
                    keep_t=False):
    """``_prepare`` as the kernel ``gated_delta_prepare``; with
    ``keep_t`` the float32 ``T`` follows, for ``_prepare_pallas_bwd``."""
    out = list(_prepare_call(
        _prepare_kernel, 'gated_delta_prepare', (q, k, v, g, beta), [],
        ['k', 'k', 'k', 'v_folded', 'p', 'a'] + ['t'] * keep_t, chunk,
        group, interpret))
    out[5] = out[5][:, :, 0, 0]
    return tuple(out)


def _prepare_pallas_bwd(q, k, v, g, beta, t32, cotangents, chunk, group,
                        interpret):
    """The cotangents of ``_prepare``'s outputs -> those of its inputs
    (the kernel ``gated_delta_prepare_bwd``)."""
    b, t, h, _ = q.shape
    dqg, dkd, dw, du, dp, da = cotangents
    dq, dk, dv, dg, dbeta = _prepare_call(
        _prepare_bwd_kernel, 'gated_delta_prepare_bwd', (q, k, v, g, beta),
        [('t', t32), ('k', dqg), ('k', dkd), ('k', dw), ('v_folded', du),
         ('p', dp), ('a', _lane(da))],
        ['q', 'q', 'v', 'g', 'g'], chunk, group, interpret)

    def gates(x):           # [B*H, N/2, 1, 2C] -> [B,T,H]
        return jnp.moveaxis(x.reshape(b, h, t), 1, 2)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            gates(dg), gates(dbeta))


# The kernels' rule covers the whole op: what is saved for the backward
# pass is the inputs and the group states alone, and the chunk-local
# operands are made again there. Both passes go through the heads a
# block at a time (``lax.map``), so the operands of one block of heads
# are live at once, not those of all.
def _head_blocks(x, heads):
    """[B,T,H,...] -> [H/heads, B,T,heads,...]."""
    b, t, h = x.shape[:3]
    x = x.reshape((b, t, h // heads, heads) + x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta_pallas(q, k, v, g, beta, static):
    return _delta_fwd_rule(q, k, v, g, beta, static)[0]


def _delta_fwd_rule(q, k, v, g, beta, static):
    chunk, group, heads, interpret = static
    b, t, h, _ = q.shape

    def one(block):
        operands = _prepare_pallas(*block, chunk, group, interpret)
        return _scan_pallas_fwd(*operands, group, interpret)

    # named, so that a caller's ``remat`` can hold them by a
    # save-by-name policy and not run the forward kernels again
    # (without such a policy a name is an identity that lowers to
    # nothing)
    inputs = tuple(checkpoint_name(x, 'gated_delta.inputs')
                   for x in (q, k, v, g, beta))
    out, states = lax.map(
        one, tuple(_head_blocks(x, heads) for x in inputs))
    # [H/heads, B*heads, N, C, dv] -> [B,T,H,dv]
    out = out.reshape(h // heads, b, heads, t, v.shape[-1])
    out = jnp.transpose(out, (1, 3, 0, 2, 4)).reshape(
        b, t, h, v.shape[-1])
    return (checkpoint_name(out, 'gated_delta.out'),
            (inputs, checkpoint_name(states, 'gated_delta.states')))


def _delta_bwd_rule(static, residuals, do):
    chunk, group, heads, interpret = static
    inputs, states = residuals
    b, t, h, dv = do.shape

    def one(args):
        block, states, do = args
        *operands, t32 = _prepare_pallas(*block, chunk, group, interpret,
                                         keep_t=True)
        # [B,T,heads,dv] -> [B*heads, N, C, dv], as the operands lie
        do = jnp.moveaxis(do, 2, 1).reshape(
            b * heads, t // chunk, chunk, dv)
        return _prepare_pallas_bwd(*block, t32, _scan_pallas_bwd(
            *operands, states, do.astype(operands[3].dtype), group,
            interpret), chunk, group, interpret)

    grads = lax.map(one, (
        tuple(_head_blocks(x, heads) for x in inputs), states,
        _head_blocks(do, heads)))
    # [H/heads, B,T,heads,...] -> [B,T,H,...]
    return tuple(
        jnp.moveaxis(x, 0, 2).reshape(y.shape).astype(y.dtype)
        for x, y in zip(grads, inputs))


_delta_pallas.defvjp(_delta_fwd_rule, _delta_bwd_rule)


# ---------------------------------------------------------------- entry
def chunk_count(batch: int, seq: int, heads: int, chunk: int = 64) -> int:
    """Chunks one call works through (the ``gated_delta.chunks``
    counter): sequences x heads x chunks a sequence."""
    return batch * heads * (-(-seq // chunk))


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     impl: str = 'auto', group: int = 8,
                     solve_base: int = 16, head_block: int = 8):
    """The gated delta rule over q, k [B,T,H,dk], v [B,T,H,dv], g and
    beta [B,T,H]; returns o [B,T,H,dv] in q's dtype.

    ``impl``: ``pallas`` (the TPU kernels), ``interpret`` (the same
    kernels under the Pallas interpreter, for tests), ``xla`` (a
    checkpointed ``lax.scan`` over chunks), ``auto`` (the kernels on a
    TPU, the scan elsewhere). ``group``: chunks a grid step of the
    kernels works through; ``head_block``: heads whose chunk-local
    operands are live at once; ``solve_base``: see ``inv_unit_lower``
    (the ``xla`` path's; the kernel inverts by joins alone)."""
    if impl == 'auto':
        impl = 'pallas' if jax.default_backend() == 'tpu' else 'xla'
    if impl not in ('pallas', 'interpret', 'xla'):
        raise ValueError(f'unknown gated_delta_rule impl {impl!r}')
    b, t, h, _ = q.shape
    # the kernels work on pairs of chunks
    pad = -t % (chunk if impl == 'xla' else 2 * chunk)
    if pad:
        # padded tokens write nothing (beta 0) and decay nothing (g 0)
        widths = [(0, 0), (0, pad)]
        q, k, v = (jnp.pad(x, widths + [(0, 0), (0, 0)])
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, widths + [(0, 0)]) for x in (g, beta))
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    g = g.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    if impl == 'xla':
        out = _scan_xla(*_prepare(q, k, v, g, beta, chunk, solve_base))
        out = jnp.moveaxis(
            out.reshape(b, h, t + pad, v.shape[-1]), 1, 2)
    else:
        pairs = _fit((t + pad) // (2 * chunk), max(group // 2, 1))
        static = (chunk, 2 * pairs, _fit(h, head_block),
                  impl == 'interpret')
        out = _delta_pallas(q, k, v, g, beta, static)
    return out[:, :t]


def reference_gated_delta(q, k, v, g, beta):
    """The recurrence token by token in float32 — what the chunked form
    has to reproduce."""
    f32 = jnp.float32
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs            # [B,H,*]
        state = state * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (v_t - jnp.einsum(
            'bhkv,bhk->bhv', state, k_t, precision=HIGHEST))
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum('bhkv,bhk->bhv', state, q_t,
                                 precision=HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0)
               for x in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    _, out = lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1)


__all__ = ['gated_delta_rule', 'reference_gated_delta', 'chunk_count',
           'inv_unit_lower']
