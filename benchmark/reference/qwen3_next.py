"""Plain reference for the ``qwen3_next`` family: the decoder of
``Qwen/Qwen3-Next-80B-A3B-Instruct`` (``config.json``, ``model_type:
qwen3_next``), its next-token loss, backward pass and AdamW update, in
float32 with matmul precision ``highest``. Imports nothing of the
program.

``x`` is [B,T,d_model]; RMSNorm with ``eps`` 1e-6; no projection has a
bias; pre-norm residual layers ``x += mixer(norm(x)); x += moe(norm(x))``;
layer ``i`` holds full attention where ``(i + 1) %
full_attention_interval == 0``, a gated DeltaNet mixer otherwise; every
layer is sparse.

1. **Gated DeltaNet.** ``q, k, v, z = split(x W_qkvz)`` (key width twice,
   value width twice), ``b, a = split(x W_ba)``; ``q, k, v <-
   silu(causal depthwise conv(concat(q, k, v)))`` (kernel 4, left padding
   3, no bias); ``beta = sigmoid(b)``; ``g = -exp(A_log) softplus(a +
   dt_bias)`` per value head. ``q, k`` are repeated from the key heads
   to the value heads, L2-normalised over the head, ``q`` scaled by
   ``dk ** -0.5``. Per head, state ``S`` (key x value), ``S_0 = 0``, for
   every token in turn: ``S <- exp(g_t) S``; ``d_t = beta_t (v_t - S^T
   k_t)``; ``S <- S + k_t d_t^T``; ``o_t = S^T q_t``. Output ``(rmsnorm(o)
   w_norm) silu(z)`` per head, then ``W_out``. The recurrence here is
   that loop, token by token — no chunked algebra, so that it shares
   nothing with the program's — under ``jax.checkpoint`` by blocks of
   tokens (its backward would otherwise keep a state per token).
2. **Gated attention.** ``q, gate = split(x W_q)`` per head; per-head
   RMSNorm of ``q`` and ``k``; rotary positions on the first
   ``partial_rotary_factor`` of the head (halves rotated against each
   other, ``rope_theta`` 1e7); causal softmax of ``q k^T head_dim **
   -0.5``, each key-value head serving ``n_heads / n_kv_heads`` query
   heads; ``(attn sigmoid(gate)) W_o``. Computed a block of queries at a
   time against all keys, so that the scores fit.
3. **Sparse experts.** ``p = softmax(x W_r)`` over ALL ``n_experts``;
   the ``top_k`` largest kept and renormalised to sum 1; ``y = sum over
   the chosen experts THAT ARE HELD of p_e E_e(x) + sigmoid(x w_sg)
   E_shared(x)``, ``E(x) = W_down(silu(W_gate x) W_up x)``. The held
   experts are ``[expert_offset, expert_offset + experts_held)``: the
   chip's share of a layer under expert parallelism. What the absent
   experts would add is left out, here as in the program. Every held
   expert is evaluated on every token and weighted (weight 0 where it
   was not chosen), a block of tokens at a time: no capacity, no
   sorting, nothing dropped.
4. Embedding, final RMSNorm, an untied head over the slice of the
   vocabulary, mean next-token cross-entropy.

Departures from the published model (the configuration's ``assumed``):
no multi-token-prediction module; no router auxiliary loss; norm gains
multiply as ``scale`` (the checkpoint stores ``scale - 1``); the fused
projections split in the order written above (the checkpoint
interleaves them per key head — a storage order).
"""

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-6
TOKEN_BLOCK = 128       # tokens of the recurrence under one checkpoint
QUERY_BLOCK = 512       # queries of attention scored at once


def _sizes(model: dict) -> dict:
    m = {k: model.get(k, d) for k, d in (
        ('full_attention_interval', 4), ('n_heads', 16), ('n_kv_heads', 2),
        ('head_dim', 256), ('partial_rotary_factor', 0.25),
        ('rope_theta', 1e7), ('linear_key_heads', 16),
        ('linear_value_heads', 32), ('linear_key_dim', 128),
        ('linear_value_dim', 128), ('linear_conv_kernel', 4),
        ('n_experts', 512), ('top_k', 10), ('d_expert', 512),
        ('d_shared', 512), ('expert_offset', 0))}
    m.update(vocab=int(model['vocab_size']), d=int(model['d_model']),
             layers=int(model['n_layers']))
    m['held'] = int(model.get('experts_held') or m['n_experts'])
    m['key_w'] = m['linear_key_heads'] * m['linear_key_dim']
    m['val_w'] = m['linear_value_heads'] * m['linear_value_dim']
    return m


def layer_names(model: dict):
    """[(prefix of the layer's leaves, stacked periods or None, index in
    the stack, holds full attention)] in the order the layers run —
    the program's parameter tree: ``layer_<i>/`` where the depth is one
    period (or ``scan_layers`` is off), else whole periods stacked under
    ``periods/layer_<j>/`` with the rest behind them."""
    m = _sizes(model)
    interval = m['full_attention_interval']
    periods = m['layers'] // interval
    scan = model.get('scan_layers', 'auto')
    scanned = periods > 1 if scan == 'auto' else bool(scan) and periods > 0
    out = []
    for i in range(m['layers']):
        full = (i + 1) % interval == 0
        if scanned and i < periods * interval:
            out.append((f'periods/layer_{i % interval}/', periods,
                        i // interval, full))
        else:
            out.append((f'layer_{i}/', None, None, full))
    return out


def layer_spec(m: dict, full: bool) -> dict:
    d, f, fs, held = m['d'], m['d_expert'], m['d_shared'], m['held']
    spec = {'norm_mixer/scale': (d,), 'norm_moe/scale': (d,),
            'moe/router': (d, m['n_experts']),
            'moe/wi_gate': (held, d, f), 'moe/wi_up': (held, d, f),
            'moe/wo': (held, f, d),
            'moe/shared/wi_gate/kernel': (d, fs),
            'moe/shared/wi_up/kernel': (d, fs),
            'moe/shared/wo/kernel': (fs, d),
            'moe/shared_gate/kernel': (d, 1)}
    if full:
        h, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
        spec.update({
            'full_attn/q_proj/kernel': (d, h, 2 * hd),
            'full_attn/k_proj/kernel': (d, hkv, hd),
            'full_attn/v_proj/kernel': (d, hkv, hd),
            'full_attn/q_norm/scale': (hd,),
            'full_attn/k_norm/scale': (hd,),
            'full_attn/o_proj/kernel': (h, hd, d)})
    else:
        hv = m['linear_value_heads']
        spec.update({
            'linear_attn/in_proj_qkvz/kernel':
                (d, 2 * m['key_w'] + 2 * m['val_w']),
            'linear_attn/in_proj_ba/kernel': (d, 2 * hv),
            'linear_attn/conv':
                (m['linear_conv_kernel'], 2 * m['key_w'] + m['val_w']),
            'linear_attn/A_log': (hv,), 'linear_attn/dt_bias': (hv,),
            'linear_attn/norm/scale': (m['linear_value_dim'],),
            'linear_attn/out_proj/kernel': (m['val_w'], d)})
    return spec


def param_spec(model: dict) -> dict:
    m = _sizes(model)
    f32 = jnp.float32
    spec = {'embed': ((m['vocab'], m['d']), f32),
            'norm_final/scale': ((m['d'],), f32),
            'lm_head/kernel': ((m['d'], m['vocab']), f32)}
    for prefix, stacked, _, full in layer_names(model):
        for name, shape in layer_spec(m, full).items():
            lead = () if stacked is None else (stacked,)
            spec[prefix + name] = (lead + shape, f32)
    return spec


# ------------------------------------------------------------------ blocks
def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def rotary(x, theta: float, rotary_dim: int):
    """x [B,T,H,D]: dimension i < rotary_dim / 2 and its partner i +
    rotary_dim / 2 turn by the angle t * theta ** (-2 i / rotary_dim);
    dimensions from rotary_dim on pass."""
    half = rotary_dim // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                     / rotary_dim)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary_dim:]], -1)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token: q, k [B,T,H,dk], v
    [B,T,H,dv], g and beta [B,T,H] -> o [B,T,H,dv]."""
    b, t, h, dk = q.shape
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum('bhkv,bhk->bhv', state, k_t,
                          precision=common.HIGHEST)
        d_t = b_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * d_t[..., None, :]
        return state, jnp.einsum('bhkv,bhk->bhv', state, q_t,
                                 precision=common.HIGHEST)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(
        (t // block, block) + x.shape[:1] + x.shape[2:])
        for x in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(tokens, state, xs)
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


def gated_delta_net(y, p, m, ein, rnd):
    b, t, _ = y.shape
    hk, hv = m['linear_key_heads'], m['linear_value_heads']
    dk, dv = m['linear_key_dim'], m['linear_value_dim']
    key_w, val_w = m['key_w'], m['val_w']
    qkvz = ein('btd,df->btf', y, p['linear_attn/in_proj_qkvz/kernel'])
    ba = ein('btd,df->btf', y, p['linear_attn/in_proj_ba/kernel'])
    mixed, z = qkvz[..., :2 * key_w + val_w], qkvz[..., -val_w:]
    kernel = p['linear_attn/conv']
    width = kernel.shape[0]
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + t] * kernel[j]
                            for j in range(width)))
    q = mixed[..., :key_w].reshape(b, t, hk, dk)
    k = mixed[..., key_w:2 * key_w].reshape(b, t, hk, dk)
    v = mixed[..., 2 * key_w:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p['linear_attn/A_log']) * jax.nn.softplus(
        ba[..., hv:] + p['linear_attn/dt_bias'])

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    o = delta_rule(rnd(q), rnd(k), rnd(v), g, beta)
    o = rms_norm(o, p['linear_attn/norm/scale']) \
        * jax.nn.silu(z.reshape(b, t, hv, dv))
    return ein('btf,fd->btd', o.reshape(b, t, val_w),
               p['linear_attn/out_proj/kernel'])


def gated_attention(y, p, m, ein):
    b, t, _ = y.shape
    h, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
    qg = ein('btd,dhk->bthk', y, p['full_attn/q_proj/kernel'])
    q, gate = qg[..., :hd], qg[..., hd:]
    k = ein('btd,dhk->bthk', y, p['full_attn/k_proj/kernel'])
    v = ein('btd,dhk->bthk', y, p['full_attn/v_proj/kernel'])
    rot = int(hd * m['partial_rotary_factor'])
    q = rotary(rms_norm(q, p['full_attn/q_norm/scale']),
               m['rope_theta'], rot)
    k = rotary(rms_norm(k, p['full_attn/k_norm/scale']),
               m['rope_theta'], rot)
    # query head i reads key-value head i // (h / hkv)
    q = q.reshape(b, t, hkv, h // hkv, hd)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def queries(start, q_blk):
        s = ein('bqgjd,bkgd->bgjqk', q_blk, k) * hd ** -0.5
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
        return ein('bgjqk,bkgd->bqgjd', w, v)

    starts = jnp.arange(0, t, block)
    blocks = jnp.moveaxis(
        q.reshape(b, t // block, block, hkv, h // hkv, hd), 1, 0)
    out = jax.lax.map(lambda a: queries(*a), (starts, blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, h, hd)
    return ein('bthk,hkd->btd', out * jax.nn.sigmoid(gate),
               p['full_attn/o_proj/kernel'])


def combine_weights(probs, m):
    """[N, held]: the renormalised top-k weight of each held expert for
    each token, 0 where the expert was not among the token's top k."""
    top_w, top_i = jax.lax.top_k(probs, m['top_k'])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    held = m['expert_offset'] + jnp.arange(m['held'])
    chosen = top_i[:, :, None] == held[None, None, :]
    return jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), 1)


def sparse_moe(y, p, m, ein):
    b, t, d = y.shape
    flat = y.reshape(b * t, d)
    probs = jax.nn.softmax(jnp.einsum(
        'nd,de->ne', flat, p['moe/router'], precision=common.HIGHEST), -1)
    weight = combine_weights(probs, m)

    @jax.checkpoint
    def routed(args):
        """Every held expert on a block of tokens, weighted."""
        x, w = args
        hidden = jax.nn.silu(ein('nd,edf->nef', x, p['moe/wi_gate'])) \
            * ein('nd,edf->nef', x, p['moe/wi_up'])
        return ein('nef,efd->nd', hidden * w[:, :, None], p['moe/wo'])

    n = b * t
    block = TOKEN_BLOCK * 8 if n % (TOKEN_BLOCK * 8) == 0 else n
    out = jax.lax.map(routed, (flat.reshape(n // block, block, d),
                               weight.reshape(n // block, block, -1)))
    shared = ein('nf,fd->nd',
                 jax.nn.silu(ein('nd,df->nf', flat,
                                 p['moe/shared/wi_gate/kernel']))
                 * ein('nd,df->nf', flat, p['moe/shared/wi_up/kernel']),
                 p['moe/shared/wo/kernel'])
    share = jax.nn.sigmoid(ein('nd,do->no', flat,
                               p['moe/shared_gate/kernel']))
    return (out.reshape(n, d) + share * shared).reshape(b, t, d)


def layer(x, p, m, full, rnd):
    """One decoder layer; ``p`` holds this layer's leaves, named from
    the layer's own root."""
    ein = lambda eq, a, b: jnp.einsum(   # noqa: E731
        eq, rnd(a), rnd(b), precision=common.HIGHEST)
    y = rms_norm(x, p['norm_mixer/scale'])
    if full:
        x = x + gated_attention(y, p, m, ein)
    else:
        x = x + gated_delta_net(y, p, m, ein, rnd)
    return x + sparse_moe(rms_norm(x, p['norm_moe/scale']), p, m, ein)


def loss_fn(params: dict, tokens, model: dict, rnd):
    """Mean next-token cross-entropy of tokens [B,T]: the mean over the
    sequences of each one's own, one sequence at a time so that a
    sequence's activations are live and not the batch's."""
    one = jax.checkpoint(
        lambda row: sequence_loss(params, row[None], model, rnd))
    return jnp.mean(jax.lax.map(one, tokens))


def sequence_loss(params: dict, tokens, model: dict, rnd):
    m = _sizes(model)
    x = jnp.take(params['embed'], tokens, axis=0)
    for prefix, _, index, full in layer_names(model):
        p = {k[len(prefix):]: (v if index is None else v[index])
             for k, v in params.items() if k.startswith(prefix)}
        x = jax.checkpoint(
            lambda x, p, full=full: layer(x, p, m, full, rnd))(x, p)

    @jax.checkpoint
    def head(x, scale, kernel):
        x = rms_norm(x, scale)
        logits = jnp.einsum('btd,dv->btv', rnd(x), rnd(kernel),
                            precision=common.HIGHEST)
        logp = jax.nn.log_softmax(logits[:, :-1])
        picked = jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(jnp.mean(picked, -1))

    return head(x, params['norm_final/scale'], params['lm_head/kernel'])


def train_flops_per_sample(model: dict, data: dict) -> float:
    """FLOPs the forward and backward passes of one SEQUENCE require of
    THE SHARE this configuration holds: every projection, the head, the
    router and the shared expert whole; the held experts at the EXPECTED
    ``top_k * experts_held / n_experts`` assignments a token (what even
    routing sends here, not what a run's router sent); causal attention
    over ``n_heads`` heads; the delta rule at its recurrent count.
    Backward twice the forward. Norms, activations, the convolution's
    gate, the embedding gather and every recomputation are not
    counted."""
    from benchmark import flops, flops_qwen3_next as more
    m = _sizes(model)
    seq, d = int(data['seq_len']), m['d']
    mm = lambda k, n: flops.matmul(seq, k, n)  # noqa: E731
    moe = (mm(d, m['n_experts']) + 3 * mm(d, m['d_shared']) + mm(d, 1)
           + more.expert_matmul(
               seq * m['top_k'] * m['held'] / m['n_experts'], d,
               m['d_expert']))
    linear = (mm(d, 2 * m['key_w'] + 2 * m['val_w'])
              + mm(d, 2 * m['linear_value_heads']) + mm(m['val_w'], d)
              + 2.0 * seq * m['linear_conv_kernel']
              * (2 * m['key_w'] + m['val_w']))
    hq, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
    full = mm(d, 2 * hq * hd) + 2 * mm(d, hkv * hd) + mm(hq * hd, d)
    dense = mm(d, m['vocab'])
    mixers = 0.0
    for _, _, _, is_full in layer_names(model):
        dense += moe + (full if is_full else linear)
        if is_full:
            mixers += flops.causal_attention(seq, hq, hd) \
                + flops.causal_attention(seq, hq, hd, backward=True)
        else:
            args = (seq, m['linear_value_heads'], m['linear_key_dim'],
                    m['linear_value_dim'])
            mixers += more.gated_delta(*args) \
                + more.gated_delta(*args, backward=True)
    return 3.0 * dense + mixers


def train(job: dict, params: dict, feeds, operands='float32',
          fault=None, steps=3) -> dict:
    """Follow the first ``steps`` steps of the job; ``feeds[i]['feed']``
    is step i's rows of tokens [B,T]. ``fault='half_batch'`` leaves the
    second half of every batch out and takes the mean over the rest."""
    rnd = common.rounder(operands)
    model = job['model']

    @jax.jit
    def loss_and_grads(params, feed, step):
        tokens = jnp.asarray(feed['feed'])
        if fault == 'half_batch':
            tokens = tokens[:tokens.shape[0] // 2]
        return jax.value_and_grad(loss_fn)(params, tokens, model, rnd)

    return common.follow(loss_and_grads, job['optimizer'], params, feeds,
                         steps, offload=True)
