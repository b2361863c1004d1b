"""Plain reference for the ``lfm2_moe`` family: the decoder of
``LiquidAI/LFM2-8B-A1B`` (``config.json``, ``model_type: lfm2_moe``),
its next-token loss, backward pass and AdamW update, in float32 with
matmul precision ``highest``. Imports nothing of the program.

``RMS(x; g) = x / sqrt(mean(x^2) + 1e-5) * g``; no projection has a
bias. ``h = E[tokens]``; every layer ``u = RMS(h; norm_mixer); h = h +
mixer(u); f = RMS(h; norm_ffn); h = h + ffn(f)``; layer ``i`` is of the
kind ``layer_types[i]``, its ffn dense where ``i < n_dense_layers`` and
sparse otherwise.

1. **Conv mixer.** ``[B | C | X] = u W_in`` (d -> 3d, thirds in that
   order); ``z_t = sum_{j<K} w_j * (B * X)_{t-(K-1)+j}``, ``w`` [K, d] one
   tap a channel, zeros before the sequence's first token — written out
   as a sum over K shifted copies; ``mixer = (C * z) W_out``. No
   activation.
2. **Attention mixer.** ``q = u W_q`` as ``n_heads`` heads of
   ``head_dim``, ``k = u W_k``, ``v = u W_v`` as ``n_kv_heads``; ``q =
   RMS(q; q_norm)``, ``k = RMS(k; k_norm)`` over the head size; rotary
   positions on the whole head (halves rotated against each other,
   ``rope_theta``, positions from 0); causal ``softmax(q k^T head_dim **
   -0.5) v``, each key-value head serving ``n_heads / n_kv_heads`` query
   heads; ``mixer = concat W_o``. No gate. Computed a block of queries at
   a time against all keys, so that the scores fit.
3. **Dense ffn.** ``(silu(f W_1) * f W_3) W_2``.
4. **Sparse ffn.** ``s = sigmoid(f W_r)`` over ALL ``n_experts``; ``I`` =
   the indices of the ``top_k`` largest of ``s + b`` (``expert_bias``,
   which gets no gradient); ``w_i = s_i`` for ``i`` in ``I`` — the scores
   WITHOUT the bias; ``w = w / (sum_I w + 1e-6)``; ``w = w *
   routed_scaling_factor``; ``ffn = sum over i in I THAT ARE HELD of w_i
   (silu(f W_1i) * f W_3i) W_2i``. The held experts are ``[expert_offset,
   expert_offset + experts_held)``: the chip's share of a layer under
   expert parallelism; what the absent experts would add is left out,
   here as in the program. The held experts are a loop, each evaluated
   on every token of a block and weighted (weight 0 where it was not
   chosen): no capacity, no sorting, nothing dropped.
5. ``logits = RMS(h; norm_final) E^T`` — the head is the embedding, one
   leaf — over the slice of the vocabulary; mean next-token
   cross-entropy.
6. **The update.** AdamW as the configuration states it on every leaf
   (``expert_bias``, whose gradient is zero, moves by the decoupled
   decay alone), and then the load rule: with ``c_i`` the (token,
   expert) pairs of the step's batch that chose expert ``i`` — ALL
   ``n_experts`` of a layer, held or not — ``b_i = b_i +
   expert_bias_update_rate * sign(mean(c) - c_i)``.

Departures from the published model (the configuration's ``assumed``):
the tied head, the load rule's rate, no router auxiliary loss.
"""

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-5
NORM_TOPK_EPS = 1e-6
TOKEN_BLOCK = 1024      # tokens the experts see at once
QUERY_BLOCK = 512       # queries of attention scored at once


def _sizes(model: dict) -> dict:
    m = {k: model.get(k, d) for k, d in (
        ('n_dense_layers', 2), ('d_ff', 7168), ('n_heads', 32),
        ('n_kv_heads', 8), ('head_dim', 64), ('rope_theta', 1e6),
        ('conv_kernel', 3), ('n_experts', 32), ('top_k', 4),
        ('d_expert', 1792), ('routed_scaling_factor', 1.0),
        ('expert_offset', 0))}
    m.update(vocab=int(model['vocab_size']), d=int(model['d_model']),
             layer_types=tuple(model['layer_types']))
    m['held'] = int(model.get('experts_held') or m['n_experts'])
    return m


def layer_spec(m: dict, kind: str, sparse: bool) -> dict:
    d = m['d']
    spec = {'norm_mixer/scale': (d,), 'norm_ffn/scale': (d,)}
    if kind == 'conv':
        spec.update({'conv/in_proj/kernel': (d, 3 * d),
                     'conv/taps': (m['conv_kernel'], d),
                     'conv/out_proj/kernel': (d, d)})
    else:
        h, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
        spec.update({'attn/q_proj/kernel': (d, h, hd),
                     'attn/k_proj/kernel': (d, hkv, hd),
                     'attn/v_proj/kernel': (d, hkv, hd),
                     'attn/q_norm/scale': (hd,),
                     'attn/k_norm/scale': (hd,),
                     'attn/o_proj/kernel': (h, hd, d)})
    if sparse:
        held, f = m['held'], m['d_expert']
        spec.update({'moe/router': (d, m['n_experts']),
                     'moe/expert_bias': (m['n_experts'],),
                     'moe/wi_gate': (held, d, f),
                     'moe/wi_up': (held, d, f), 'moe/wo': (held, f, d)})
    else:
        spec.update({'mlp/wi_gate/kernel': (d, m['d_ff']),
                     'mlp/wi_up/kernel': (d, m['d_ff']),
                     'mlp/wo/kernel': (m['d_ff'], d)})
    return spec


def layers(m: dict):
    """[(prefix of the layer's leaves, kind, sparse)] as they run."""
    return [(f'layer_{i}/', kind, i >= m['n_dense_layers'])
            for i, kind in enumerate(m['layer_types'])]


def param_spec(model: dict) -> dict:
    """The tied leaf once: ``embed`` is the table and the head."""
    m = _sizes(model)
    f32 = jnp.float32
    spec = {'embed': ((m['vocab'], m['d']), f32),
            'norm_final/scale': ((m['d'],), f32)}
    for prefix, kind, sparse in layers(m):
        for name, shape in layer_spec(m, kind, sparse).items():
            spec[prefix + name] = (shape, f32)
    return spec


# ------------------------------------------------------------------ blocks
def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def rotary(x, theta: float):
    """x [B,T,H,D]: dimension i < D / 2 and its partner i + D / 2 turn
    by the angle t * theta ** (-2 i / D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                     / x.shape[-1])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def short_conv(u, p, m, ein):
    b, t, d = u.shape
    bcx = ein('btd,df->btf', u, p['conv/in_proj/kernel'])
    gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    taps = p['conv/taps']
    width = taps.shape[0]
    bx = gate_b * x
    z = 0.0
    for j in range(width):
        back = width - 1 - j        # tap j reads the token `back` before
        moved = jnp.concatenate(
            [jnp.zeros_like(bx[:, :back]), bx[:, :t - back]], 1)
        z = z + taps[j] * moved
    return ein('btd,de->bte', gate_c * z, p['conv/out_proj/kernel'])


def attention(u, p, m, ein):
    b, t, _ = u.shape
    h, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
    q = ein('btd,dhk->bthk', u, p['attn/q_proj/kernel'])
    k = ein('btd,dhk->bthk', u, p['attn/k_proj/kernel'])
    v = ein('btd,dhk->bthk', u, p['attn/v_proj/kernel'])
    q = rotary(rms_norm(q, p['attn/q_norm/scale']), m['rope_theta'])
    k = rotary(rms_norm(k, p['attn/k_norm/scale']), m['rope_theta'])
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
    return ein('bthk,hkd->btd', out, p['attn/o_proj/kernel'])


def dense_ffn(f, p, ein):
    hidden = jax.nn.silu(ein('btd,df->btf', f, p['mlp/wi_gate/kernel'])) \
        * ein('btd,df->btf', f, p['mlp/wi_up/kernel'])
    return ein('btf,fd->btd', hidden, p['mlp/wo/kernel'])


def combine_weights(scores, bias, m):
    """[N, held]: for each token the weight of each held expert — its
    unbiased score, renormalised over the token's chosen ``top_k`` —, 0
    where the expert was not among them. Chosen by ``score + bias``.
    And [n_experts]: how many tokens chose each expert."""
    _, top_i = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                             m['top_k'])
    chosen = jnp.any(
        top_i[:, :, None] == jnp.arange(m['n_experts'])[None, None, :], 1)
    weight = jnp.where(chosen, scores, 0.0)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + NORM_TOPK_EPS)
    weight = weight * m['routed_scaling_factor']
    return (weight[:, m['expert_offset']:m['expert_offset'] + m['held']],
            jnp.sum(chosen, 0).astype(jnp.float32))


def sparse_ffn(f, p, m, ein):
    b, t, d = f.shape
    flat = f.reshape(b * t, d)
    scores = jax.nn.sigmoid(jnp.einsum(
        'nd,de->ne', flat, p['moe/router'], precision=common.HIGHEST))
    weight, load = combine_weights(scores, p['moe/expert_bias'], m)

    @jax.checkpoint
    def routed(args):
        """The held experts, one after the other, on a block of tokens:
        each on every token, weighted (0 where it was not chosen)."""
        x, w = args
        out = jnp.zeros_like(x)
        for e in range(m['held']):
            hidden = jax.nn.silu(ein('nd,df->nf', x, p['moe/wi_gate'][e])) \
                * ein('nd,df->nf', x, p['moe/wi_up'][e])
            out = out + w[:, e:e + 1] * ein('nf,fd->nd', hidden,
                                            p['moe/wo'][e])
        return out

    n = b * t
    block = TOKEN_BLOCK if n % TOKEN_BLOCK == 0 else n
    out = jax.lax.map(routed, (flat.reshape(n // block, block, d),
                               weight.reshape(n // block, block, -1)))
    return out.reshape(b, t, d), load


def layer(x, p, m, kind, sparse, rnd):
    """One decoder layer; ``p`` holds this layer's leaves, named from
    the layer's own root. Returns the layer's output and, of a sparse
    layer, how many tokens chose each expert (else None)."""
    ein = lambda eq, a, b: jnp.einsum(   # noqa: E731
        eq, rnd(a), rnd(b), precision=common.HIGHEST)
    u = rms_norm(x, p['norm_mixer/scale'])
    mixer = short_conv if kind == 'conv' else attention
    x = x + mixer(u, p, m, ein)
    f = rms_norm(x, p['norm_ffn/scale'])
    if not sparse:
        return x + dense_ffn(f, p, ein), None
    out, load = sparse_ffn(f, p, m, ein)
    return x + out, load


def loss_fn(params: dict, tokens, model: dict, rnd):
    """Mean next-token cross-entropy of tokens [B,T]: the mean over the
    sequences of each one's own, one sequence at a time so that a
    sequence's activations are live and not the batch's. And {the
    ``expert_bias`` leaf of each sparse layer: how many (token, expert)
    pairs of the batch chose each of its experts}."""
    one = jax.checkpoint(
        lambda row: sequence_loss(params, row[None], model, rnd))
    losses, loads = jax.lax.map(one, tokens)
    return jnp.mean(losses), {k: jnp.sum(v, 0) for k, v in loads.items()}


def sequence_loss(params: dict, tokens, model: dict, rnd):
    m = _sizes(model)
    x = jnp.take(params['embed'], tokens, axis=0)
    loads = {}
    for prefix, kind, sparse in layers(m):
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        x, load = jax.checkpoint(
            lambda x, p, kind=kind, sparse=sparse: layer(
                x, p, m, kind, sparse, rnd))(x, p)
        if sparse:
            loads[prefix + 'moe/expert_bias'] = load

    @jax.checkpoint
    def head(x, scale, table):
        x = rms_norm(x, scale)
        logits = jnp.einsum('btd,vd->btv', rnd(x), rnd(table),
                            precision=common.HIGHEST)
        logp = jax.nn.log_softmax(logits[:, :-1])
        picked = jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(jnp.mean(picked, -1))

    return head(x, params['norm_final/scale'], params['embed']), loads


def train_flops_per_sample(model: dict, data: dict) -> float:
    """FLOPs the forward and backward passes of one SEQUENCE require of
    THE SHARE this configuration holds: every projection, the dense
    ffn, the router and the tied head whole; the held experts at the
    EXPECTED ``top_k * experts_held / n_experts`` assignments a token
    (what even routing sends here, not what a run's router sent);
    causal attention over ``n_heads`` heads; the convolution's taps.
    Backward twice the forward. Norms, gates, activations, the
    embedding gather and every recomputation are not counted."""
    from benchmark import flops, flops_lfm2, flops_qwen3_next as more
    m = _sizes(model)
    seq, d = int(data['seq_len']), m['d']
    mm = lambda k, n: flops.matmul(seq, k, n)  # noqa: E731
    hq, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
    dense = mm(d, m['vocab'])
    mixers = 0.0
    for _, kind, sparse in layers(m):
        if kind == 'conv':
            dense += mm(d, 3 * d) + mm(d, d) \
                + flops_lfm2.short_conv(seq, d, m['conv_kernel'])
        else:
            dense += mm(d, hq * hd) + 2 * mm(d, hkv * hd) + mm(hq * hd, d)
            mixers += flops.causal_attention(seq, hq, hd) \
                + flops.causal_attention(seq, hq, hd, backward=True)
        if sparse:
            dense += mm(d, m['n_experts']) + more.expert_matmul(
                seq * m['top_k'] * m['held'] / m['n_experts'], d,
                m['d_expert'])
        else:
            dense += 3 * mm(d, m['d_ff'])
    return 3.0 * dense + mixers


def load_rule(bias, load, rate: float):
    """``expert_bias`` after a step in which ``load[i]`` pairs chose
    expert ``i``: up by ``rate`` where that is under the mean, down
    where it is over."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def train(job: dict, params: dict, feeds, operands='float32',
          fault=None, steps=3) -> dict:
    """Follow the first ``steps`` steps of the job; ``feeds[i]['feed']``
    is step i's rows of tokens [B,T]. ``fault='half_batch'`` leaves the
    second half of every batch out and takes the mean over the rest.

    ``common.follow`` with this family's second update: the parameters,
    their gradients and two moments do not fit the chip together, so
    AdamW goes leaf by leaf with the moments and the starting weights
    on the host; the load rule follows it. Returns what it returns."""
    import numpy as np
    rnd = common.rounder(operands)
    model, opt = job['model'], job['optimizer']
    rate = float(model.get('expert_bias_update_rate', 0.0))

    @jax.jit
    def loss_and_grads(params, feed):
        tokens = jnp.asarray(feed['feed'])
        if fault == 'half_batch':
            tokens = tokens[:tokens.shape[0] // 2]
        return jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, model, rnd)

    @jax.jit
    def update(leaf, grad, state, step):
        return common.opt_update(opt, leaf, grad, state, step)

    start = {k: np.asarray(v) for k, v in params.items()}
    params, moments = dict(params), {}
    losses, grad_norms = [], None
    for step in range(steps):
        with jax.default_matmul_precision('highest'):
            (loss, loads), grads = loss_and_grads(params, feeds[step])
        if step == 0:
            grad_norms = jax.jit(common.leaf_norms)(grads)
        for k in sorted(params):
            one = {m: {k: jnp.asarray(v)} for m, v in moments[k].items()} \
                if k in moments else common.opt_init(opt, {k: params[k]})
            leaf, one = update({k: params.pop(k)}, {k: grads.pop(k)}, one,
                               step)
            params[k] = leaf[k]
            moments[k] = {m: np.asarray(v[k]) for m, v in one.items()}
        if rate:
            for k, load in loads.items():
                params[k] = load_rule(params[k], load, rate)
        losses.append(float(loss))
    norm = lambda a: float(np.sqrt(np.sum(np.square(  # noqa: E731
        np.asarray(a, np.float64)))))
    return {'loss': losses,
            'grad_norm': {k: float(v) for k, v in grad_norms.items()},
            'moment_norm': {k: norm(v['m']) for k, v in moments.items()},
            'delta_norm': {k: norm(np.asarray(params[k]) - start[k])
                           for k in params}}
