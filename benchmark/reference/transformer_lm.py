"""Plain reference for the ``transformer_lm`` family: the decoder the
repo states (``models/transformer.py``) at OLMo-1B's published sizes
(Groeneveld et al., arXiv:2402.00838, Table 1; ``allenai/OLMo-1B-hf``
``config.json``), its next-token loss, backward pass and AdamW update,
in float32. Imports nothing of the program.

The block, as the configuration states it: pre-norm decoder layers,
``x += attn(norm(x)); x += mlp(norm(x))``; causal softmax attention
over ``n_heads`` heads of ``d_model / n_heads``, scores scaled by
``head_dim ** -0.5``, one fused q/k/v projection and an output
projection, no biases; SwiGLU ``wo(silu(wi_gate y) * wi_up y)``; a final
norm and an untied output head over the whole vocabulary.

Departures from the published OLMo-1B block, all of them the repo's
decoder's (this PR may not touch ``models/``): learned absolute
positions where OLMo has rotary; RMSNorm with a learned gain where OLMo
has a non-parametric LayerNorm; an untied ``lm_head`` where OLMo-1B
ties it to the embedding.

Parameters are stacked over layers (a leading ``n_layers`` axis under
``layers/...``), as the configuration's ``scan_layers: auto`` lays them
out. To fit beside the optimizer's state on one chip the layers run
under ``jax.checkpoint`` (one layer's activations live at a time) and
the update goes leaf by leaf with the moments kept on the host.
"""

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-6


def param_spec(model: dict) -> dict:
    f32 = jnp.float32
    d, layers = int(model['d_model']), int(model['n_layers'])
    heads, ff = int(model['n_heads']), int(model['d_ff'])
    vocab, seq = int(model['vocab_size']), int(model['max_seq_len'])
    hd = d // heads
    return {
        'embed': ((vocab, d), f32),
        'pos_embed': ((seq, d), f32),
        'layers/norm_attn/scale': ((layers, d), f32),
        'layers/attn/qkv/kernel': ((layers, d, 3, heads, hd), f32),
        'layers/attn/out/kernel': ((layers, heads, hd, d), f32),
        'layers/norm_mlp/scale': ((layers, d), f32),
        'layers/mlp/wi_gate/kernel': ((layers, d, ff), f32),
        'layers/mlp/wi_up/kernel': ((layers, d, ff), f32),
        'layers/mlp/wo/kernel': ((layers, ff, d), f32),
        'norm_final/scale': ((d,), f32),
        'lm_head/kernel': ((d, vocab), f32),
    }


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def layer(x, p, rnd):
    """One decoder layer on x [B,T,D]; ``p`` holds this layer's slice of
    every ``layers/...`` leaf."""
    ein = lambda eq, a, b: jnp.einsum(   # noqa: E731
        eq, rnd(a), rnd(b), precision=common.HIGHEST)
    t = x.shape[1]
    y = rms_norm(x, p['layers/norm_attn/scale'])
    qkv = ein('btd,dqhk->btqhk', y, p['layers/attn/qkv/kernel'])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = ein('bqhd,bkhd->bhqk', q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    a = ein('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), v)
    x = x + ein('bthk,hkd->btd', a, p['layers/attn/out/kernel'])
    y = rms_norm(x, p['layers/norm_mlp/scale'])
    gate = ein('btd,df->btf', y, p['layers/mlp/wi_gate/kernel'])
    up = ein('btd,df->btf', y, p['layers/mlp/wi_up/kernel'])
    return x + ein('btf,fd->btd', jax.nn.silu(gate) * up,
                   p['layers/mlp/wo/kernel'])


def loss_fn(params: dict, tokens, rnd):
    """Mean next-token cross-entropy of tokens [B,T]."""
    x = jnp.take(params['embed'], tokens, axis=0)
    x = x + params['pos_embed'][None, :tokens.shape[1]]
    stacked = {k: v for k, v in params.items() if k.startswith('layers/')}

    @jax.checkpoint
    def body(x, p):
        return layer(x, p, rnd), None

    x, _ = jax.lax.scan(body, x, stacked)

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
    """FLOPs the forward and backward passes of one SEQUENCE require
    (``flops.py``): every projection, the head and causal attention,
    backward twice the forward; the embedding gather, norms and
    activations are not counted, nor is any recomputation."""
    from benchmark import flops
    d, layers = int(model['d_model']), int(model['n_layers'])
    heads, ff = int(model['n_heads']), int(model['d_ff'])
    seq, vocab = int(data['seq_len']), int(model['vocab_size'])
    per_layer = (flops.matmul(seq, d, 3 * d) + flops.matmul(seq, d, d)
                 + 3 * flops.matmul(seq, d, ff))
    dense = layers * per_layer + flops.matmul(seq, d, vocab)
    attention = layers * (
        flops.causal_attention(seq, heads, d // heads)
        + flops.causal_attention(seq, heads, d // heads, backward=True))
    return 3.0 * dense + attention


def train(job: dict, params: dict, feeds, operands='float32',
          fault=None, steps=3) -> dict:
    """Follow the first ``steps`` steps of the job; ``feeds[i]['feed']``
    is step i's rows of tokens [B,T]. ``fault='half_batch'`` leaves the
    second half of every batch out and takes the mean over the rest."""
    rnd = common.rounder(operands)

    @jax.jit
    def loss_and_grads(params, feed, step):
        tokens = jnp.asarray(feed['feed'])
        if fault == 'half_batch':
            tokens = tokens[:tokens.shape[0] // 2]
        return jax.value_and_grad(loss_fn)(params, tokens, rnd)

    return common.follow(loss_and_grads, job['optimizer'], params, feeds,
                         steps, offload=True)
