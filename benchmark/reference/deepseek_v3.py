"""Plain reference for the ``deepseek_v3`` family: the decoder of
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` (``config.json``,
``model_type: deepseek_v3``), its next-token loss, backward pass and
AdamW update, in float32 with matmul precision ``highest``. Imports
nothing of the program.

``RMS(x; g) = x / sqrt(mean(x^2) + 1e-6) * g``; no projection has a
bias. ``h = E[tokens]``; every layer ``u = RMS(h; norm_mixer); h = h +
mla(u); f = RMS(h; norm_ffn); h = h + ffn(f)``; the ffn of layer ``i``
is dense where ``i < n_dense_layers`` and sparse otherwise.

1. **Latent attention (MLA), expanded.** ``q = u W_q`` as ``n_heads``
   heads of ``nope + rope`` (no query low-rank path: ``q_lora_rank`` is
   null); ``[c | k_r] = u W_kva`` (``kv_lora_rank`` | ``rope``); ``c =
   RMS(c; kv_a_norm)``; ``[k_nope | v] = c W_kvb`` a head (``nope`` |
   ``v_head_dim``). Rotary positions on the LAST ``rope`` dimensions of
   every query head and on ``k_r``, which is one vector a token: the
   pair ``(x[2i], x[2i+1])`` turns by ``t * theta ** (-2 i / rope)``,
   positions from 0 (``rope_interleave``, written out as pairs; the
   published code de-interleaves and then turns halves, which permutes
   the rotary dimensions of q and k alike and leaves every score as it
   is). Head ``j``'s key is ``[k_nope_j | k_r]``, concatenated; causal
   ``softmax(q_j k_j^T (nope + rope) ** -0.5) v_j``; ``mla = concat_j
   W_o``. Computed a block of query rows at a time against all keys,
   so that the 32 heads' scores fit.
2. **Dense ffn.** ``(silu(f W_1) * f W_3) W_2``.
3. **Sparse ffn.** ``s = sigmoid(f W_r)`` over ALL ``n_experts``; ``I``
   = the indices of the ``top_k`` largest of ``s + b`` (``expert_bias``,
   which gets no gradient; one group, so the group limit of
   ``noaux_tc`` is no limit); ``w_i = s_i`` for ``i`` in ``I`` — the
   scores WITHOUT the bias; ``w = w / (sum_I w + 1e-20)``; ``w = w *
   routed_scaling_factor``; ``ffn = sum over i in I THAT ARE HELD of w_i
   E_i(f) + Shared(f)``, ``E(x) = (silu(x W_1) * x W_3) W_2`` of width
   ``d_expert`` and ``Shared`` ONE such expert of ``n_shared_experts *
   d_expert``, added WITHOUT a gate. The held experts are
   ``[expert_offset, expert_offset + experts_held)``: the chip's share
   of a layer under expert parallelism; what the absent experts would
   add is left out, here as in the program. The held experts are a
   loop, each evaluated on every token of a block and weighted (weight
   0 where it was not chosen): no capacity, no sorting, nothing dropped.
4. ``logits = RMS(h; norm_final) W_head`` — an untied head — over the
   slice of the vocabulary; mean next-token cross-entropy.
5. **The update.** AdamW as the configuration states it on every leaf
   (``expert_bias``, whose gradient is zero, moves by the decoupled
   decay alone), and then the load rule: with ``c_i`` the (token,
   expert) pairs of the step's batch that chose expert ``i`` — ALL
   ``n_experts`` of a layer, held or not — ``b_i = b_i +
   expert_bias_update_rate * sign(mean(c) - c_i)``.

Departures from the published model (the configuration's ``assumed``):
no router auxiliary loss, no multi-token-prediction module, the
renormalising 1e-20 and the load rule's rate 0.001.
"""

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-6
NORM_TOPK_EPS = 1e-20
TOKEN_BLOCK = 1024      # tokens the experts see at once
QUERY_BLOCK = 512       # query rows of attention scored at once


def _sizes(model: dict) -> dict:
    m = {k: model.get(k, d) for k, d in (
        ('n_dense_layers', 1), ('d_ff', 6144), ('n_heads', 32),
        ('kv_lora_rank', 512), ('qk_nope_head_dim', 128),
        ('qk_rope_head_dim', 64), ('v_head_dim', 128),
        ('rope_theta', 1e6), ('n_experts', 128), ('top_k', 6),
        ('d_expert', 768), ('n_shared_experts', 2),
        ('routed_scaling_factor', 2.448), ('expert_offset', 0))}
    m.update(vocab=int(model['vocab_size']), d=int(model['d_model']),
             n_layers=int(model['n_layers']))
    m['held'] = int(model.get('experts_held') or m['n_experts'])
    m['d_shared'] = m['n_shared_experts'] * m['d_expert']
    return m


def layer_spec(m: dict, sparse: bool) -> dict:
    d, h, rank = m['d'], m['n_heads'], m['kv_lora_rank']
    nope, rope, dv = (m['qk_nope_head_dim'], m['qk_rope_head_dim'],
                      m['v_head_dim'])
    spec = {'norm_mixer/scale': (d,), 'norm_ffn/scale': (d,),
            'attn/q_proj/kernel': (d, h, nope + rope),
            'attn/kv_a_proj/kernel': (d, rank + rope),
            'attn/kv_a_norm/scale': (rank,),
            'attn/kv_b_proj/kernel': (rank, h, nope + dv),
            'attn/o_proj/kernel': (h, dv, d)}
    if sparse:
        held, f, fs = m['held'], m['d_expert'], m['d_shared']
        spec.update({'moe/router': (d, m['n_experts']),
                     'moe/expert_bias': (m['n_experts'],),
                     'moe/wi_gate': (held, d, f),
                     'moe/wi_up': (held, d, f), 'moe/wo': (held, f, d),
                     'moe/shared/wi_gate/kernel': (d, fs),
                     'moe/shared/wi_up/kernel': (d, fs),
                     'moe/shared/wo/kernel': (fs, d)})
    else:
        spec.update({'mlp/wi_gate/kernel': (d, m['d_ff']),
                     'mlp/wi_up/kernel': (d, m['d_ff']),
                     'mlp/wo/kernel': (m['d_ff'], d)})
    return spec


def layers(m: dict):
    """[(prefix of the layer's leaves, sparse)] as they run."""
    return [(f'layer_{i}/', i >= m['n_dense_layers'])
            for i in range(m['n_layers'])]


def param_spec(model: dict) -> dict:
    m = _sizes(model)
    f32 = jnp.float32
    spec = {'embed': ((m['vocab'], m['d']), f32),
            'norm_final/scale': ((m['d'],), f32),
            'lm_head/kernel': ((m['d'], m['vocab']), f32)}
    for prefix, sparse in layers(m):
        for name, shape in layer_spec(m, sparse).items():
            spec[prefix + name] = (shape, f32)
    return spec


# ------------------------------------------------------------------ blocks
def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def rotary_pairs(x, theta: float):
    """x [B,T,H,D]: the neighbours ``2 i`` and ``2 i + 1`` turn by the
    angle ``t * theta ** (-2 i / D)``."""
    dim = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def attention(u, p, m, ein):
    b, t, _ = u.shape
    h, rank = m['n_heads'], m['kv_lora_rank']
    nope, rope, dv = (m['qk_nope_head_dim'], m['qk_rope_head_dim'],
                      m['v_head_dim'])
    q = ein('btd,dhk->bthk', u, p['attn/q_proj/kernel'])
    kva = ein('btd,dr->btr', u, p['attn/kv_a_proj/kernel'])
    latent = rms_norm(kva[..., :rank], p['attn/kv_a_norm/scale'])
    kv = ein('btr,rhk->bthk', latent, p['attn/kv_b_proj/kernel'])
    q = jnp.concatenate(
        [q[..., :nope], rotary_pairs(q[..., nope:], m['rope_theta'])], -1)
    # ONE rotary key a token, the same for every head
    k_rope = rotary_pairs(kva[:, :, None, rank:], m['rope_theta'])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, h, rope))], -1)
    v = kv[..., nope:]
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def queries(start, q_blk):
        s = ein('bqhd,bkhd->bhqk', q_blk, k) * (nope + rope) ** -0.5
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
        return ein('bhqk,bkhd->bqhd', w, v)

    starts = jnp.arange(0, t, block)
    blocks = jnp.moveaxis(
        q.reshape(b, t // block, block, h, nope + rope), 1, 0)
    out = jax.lax.map(lambda a: queries(*a), (starts, blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, h, dv)
    return ein('bthk,hkd->btd', out, p['attn/o_proj/kernel'])


def swiglu(x, gate, up, down, ein):
    hidden = jax.nn.silu(ein('...d,df->...f', x, gate)) \
        * ein('...d,df->...f', x, up)
    return ein('...f,fd->...d', hidden, down)


def combine_weights(scores, bias, m):
    """[N, held]: for each token the weight of each held expert — its
    unbiased score, renormalised over the token's chosen ``top_k`` and
    times ``routed_scaling_factor`` —, 0 where the expert was not among
    them. Chosen by ``score + bias``. And [n_experts]: how many tokens
    chose each expert."""
    _, top_i = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                             m['top_k'])
    chosen = jnp.any(
        top_i[:, :, None] == jnp.arange(m['n_experts'])[None, None, :], 1)
    weight = jnp.where(chosen, scores, 0.0)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + NORM_TOPK_EPS)
    weight = weight * m['routed_scaling_factor']
    return (weight[:, m['expert_offset']:m['expert_offset'] + m['held']],
            jnp.sum(chosen, 0).astype(jnp.float32))


def routed_ffn(f, p, m, ein):
    """The held experts' part of the layer, and the load."""
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
            out = out + w[:, e:e + 1] * swiglu(
                x, p['moe/wi_gate'][e], p['moe/wi_up'][e],
                p['moe/wo'][e], ein)
        return out

    n = b * t
    block = TOKEN_BLOCK if n % TOKEN_BLOCK == 0 else n
    out = jax.lax.map(routed, (flat.reshape(n // block, block, d),
                               weight.reshape(n // block, block, -1)))
    return out.reshape(b, t, d), load


def shared_ffn(f, p, ein):
    """The shared expert: every token, no gate."""
    return swiglu(f, p['moe/shared/wi_gate/kernel'],
                  p['moe/shared/wi_up/kernel'],
                  p['moe/shared/wo/kernel'], ein)


def layer(x, p, m, sparse, rnd):
    """One decoder layer; ``p`` holds this layer's leaves, named from
    the layer's own root. Returns the layer's output and, of a sparse
    layer, how many tokens chose each expert (else None)."""
    ein = lambda eq, a, b: jnp.einsum(   # noqa: E731
        eq, rnd(a), rnd(b), precision=common.HIGHEST)
    u = rms_norm(x, p['norm_mixer/scale'])
    x = x + attention(u, p, m, ein)
    f = rms_norm(x, p['norm_ffn/scale'])
    if not sparse:
        return x + swiglu(f, p['mlp/wi_gate/kernel'],
                          p['mlp/wi_up/kernel'], p['mlp/wo/kernel'],
                          ein), None
    out, load = routed_ffn(f, p, m, ein)
    return x + out + shared_ffn(f, p, ein), load


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
    for prefix, sparse in layers(m):
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        x, load = jax.checkpoint(
            lambda x, p, sparse=sparse: layer(x, p, m, sparse, rnd))(x, p)
        if sparse:
            loads[prefix + 'moe/expert_bias'] = load

    @jax.checkpoint
    def head(x, scale, kernel):
        x = rms_norm(x, scale)
        logits = jnp.einsum('btd,dv->btv', rnd(x), rnd(kernel),
                            precision=common.HIGHEST)
        logp = jax.nn.log_softmax(logits[:, :-1])
        picked = jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(jnp.mean(picked, -1))

    return head(x, params['norm_final/scale'],
                params['lm_head/kernel']), loads


def train_flops_per_sample(model: dict, data: dict) -> float:
    """FLOPs the forward and backward passes of one SEQUENCE require of
    THE SHARE this configuration holds: every projection, the dense
    ffn, the router, the shared expert and the head whole; the held
    experts at the EXPECTED ``top_k * experts_held / n_experts``
    assignments a token (what even routing sends here, not what a run's
    router sent); causal latent attention over ``n_heads`` heads, the
    scores ``nope + rope`` deep and the values ``v_head_dim`` wide.
    Backward twice the forward. Norms, the rotary turn, activations,
    the embedding gather and every recomputation are not counted."""
    from benchmark import flops, flops_deepseek_v3, flops_qwen3_next as more
    m = _sizes(model)
    seq, d, h = int(data['seq_len']), m['d'], m['n_heads']
    mm = lambda k, n: flops.matmul(seq, k, n)  # noqa: E731
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    nope, dv, rank = m['qk_nope_head_dim'], m['v_head_dim'], m['kv_lora_rank']
    dense = mm(d, m['vocab'])
    mixers = 0.0
    for _, sparse in layers(m):
        dense += mm(d, h * qk) + mm(d, rank + m['qk_rope_head_dim']) \
            + mm(rank, h * (nope + dv)) + mm(h * dv, d)
        mixers += flops_deepseek_v3.mla_attention(seq, h, qk, dv) \
            + flops_deepseek_v3.mla_attention(seq, h, qk, dv, backward=True)
        if sparse:
            dense += mm(d, m['n_experts']) + 3 * mm(d, m['d_shared']) \
                + more.expert_matmul(
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
