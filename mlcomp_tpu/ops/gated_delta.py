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

Everything up to ``U, W`` and the masked ``q k^T`` is local to a chunk:
batched matrix products that XLA lays onto the MXU and differentiates
itself (``_prepare``). What is sequential — ``D``, ``O`` and ``S'``,
chunk after chunk — is the kernel: ``gated_delta_fwd`` keeps the state
in VMEM in float32 and saves the state each chunk starts from;
``gated_delta_bwd_scan`` walks the chunks backwards, recomputes ``D``
within the chunk from the saved state, and carries ``dS``. Off the TPU
the same recurrence is a checkpointed ``lax.scan`` (``impl='xla'``).

The state, the decays and every accumulation are float32; the matrix
products take their operands in the input dtype (bfloat16 in a bfloat16
model, so float32 inputs give float32 throughout).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

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
    chunk, group, solve_base, heads, interpret = static
    b, t, h, _ = q.shape

    def one(block):
        operands = _prepare(*block, chunk, solve_base)
        return _scan_pallas_fwd(*operands, group, interpret)

    inputs = (q, k, v, g, beta)
    out, states = lax.map(
        one, tuple(_head_blocks(x, heads) for x in inputs))
    # [H/heads, B*heads, N, C, dv] -> [B,T,H,dv]
    out = out.reshape(h // heads, b, heads, t, v.shape[-1])
    out = jnp.transpose(out, (1, 3, 0, 2, 4)).reshape(
        b, t, h, v.shape[-1])
    return out, (inputs, states)


def _delta_bwd_rule(static, residuals, do):
    chunk, group, solve_base, heads, interpret = static
    inputs, states = residuals
    b, t, h, dv = do.shape

    def one(args):
        block, states, do = args
        operands, pullback = jax.vjp(
            lambda *x: _prepare(*x, chunk, solve_base), *block)
        # [B,T,heads,dv] -> [B*heads, N, C, dv], as _prepare folds
        do = jnp.moveaxis(do, 2, 1).reshape(
            b * heads, t // chunk, chunk, dv)
        return pullback(_scan_pallas_bwd(
            *operands, states, do.astype(operands[3].dtype), group,
            interpret))

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
    operands are live at once; ``solve_base``: see ``inv_unit_lower``."""
    if impl == 'auto':
        impl = 'pallas' if jax.default_backend() == 'tpu' else 'xla'
    if impl not in ('pallas', 'interpret', 'xla'):
        raise ValueError(f'unknown gated_delta_rule impl {impl!r}')
    b, t, h, _ = q.shape
    pad = -t % chunk
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
        static = (chunk, _fit((t + pad) // chunk, group), solve_base,
                  _fit(h, head_block), impl == 'interpret')
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
