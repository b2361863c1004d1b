"""Plain reference for the ``ouro`` family: the looped decoder of
``ByteDance/Ouro-2.6B`` (``config.json``, ``model_type: ouro``; Zhu et
al., arXiv:2510.25741), its training loss, backward pass and AdamW
update, in float32 with matmul precision ``highest``. Imports nothing of
the program.

``RMS(x; g) = x / sqrt(mean(x^2) + 1e-6) * g``; no projection has a
bias. A layer, sandwich-normed:

    h = x + RMS(attn(RMS(x; N1)); N2)
    y = h + RMS(ffn(RMS(h; N3)); N4)

``attn``: ``q = u W_q`` as ``n_heads`` heads of ``head_dim``, ``k = u
W_k``, ``v = u W_v`` as ``n_kv_heads``; rotary positions on the whole
head (halves rotated against each other, ``rope_theta``, positions from
0) on q and k; causal ``softmax(q k^T head_dim ** -0.5) v``; ``attn =
concat W_o``. Computed a block of queries at a time against all keys.
``ffn = (silu(f W_1) * f W_3) W_2``.

The model: ``s_0 = E[tokens]``; for ``t = 1..N`` (``ut_steps``) the SAME
``n_layers`` layers run on ``s_{t-1}`` and ``s_t = RMS(stack(s_{t-1});
norm_final)``; exit ``t`` has the logits ``z_t = s_t W_head`` (untied)
and the gate ``lambda_t = sigmoid(s_t w_g + b_g)``. The exit
distribution: ``p(t) = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t <
N``, ``p(N) = prod_{j<N} (1 - lambda_j)``. The loss of a token:
``sum_t p(t) CE(z_t, next token) - beta H(p)``, ``H(p) = -sum_t p(t) log
p(t)``; the mean over a sequence's tokens (every one but the last), then
over the sequences, ``beta`` = 0.1 (``BETA``).

Computed a sequence at a time (the gradients summed on the host), each
layer application under ``jax.checkpoint``, each exit's head and
cross-entropy a block of ``TOKEN_BLOCK`` tokens at a time, so that it
follows the timed steps on the chip at the timed sizes.

Departures from the published model (the configuration's ``assumed``):
the gate's bias, the final norm at the end of every recurrent step and
the sandwich norms are read from the published modeling code and the
paper, not from a key of ``config.json``; ``beta`` = 0.1.
"""

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-6
QUERY_BLOCK = 512       # queries of attention scored at once
TOKEN_BLOCK = 1024      # tokens an exit's head sees at once
STACK = 'loop/layers/'  # the stacked leaves' prefix: [n_layers, ...]
BETA = 0.1              # the configuration's assumed entropy weight


def _sizes(model: dict) -> dict:
    m = {k: model.get(k, d) for k, d in (
        ('n_heads', 16), ('n_kv_heads', 16), ('head_dim', 128),
        ('d_ff', 5632), ('rope_theta', 1e6), ('ut_steps', 4))}
    m.update(vocab=int(model['vocab_size']), d=int(model['d_model']),
             layers=int(model['n_layers']))
    return m


def layer_spec(m: dict) -> dict:
    """One layer's leaves and their shapes (the stacked leaves add a
    leading ``n_layers``)."""
    d, f = m['d'], m['d_ff']
    h, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
    return {'attn/norm_in/scale': (d,),
            'attn/q_proj/kernel': (d, h, hd),
            'attn/k_proj/kernel': (d, hkv, hd),
            'attn/v_proj/kernel': (d, hkv, hd),
            'attn/o_proj/kernel': (h, hd, d),
            'attn/norm_out/scale': (d,),
            'mlp/norm_in/scale': (d,),
            'mlp/ffn/wi_gate/kernel': (d, f),
            'mlp/ffn/wi_up/kernel': (d, f),
            'mlp/ffn/wo/kernel': (f, d),
            'mlp/norm_out/scale': (d,)}


def param_spec(model: dict) -> dict:
    m = _sizes(model)
    f32 = jnp.float32
    spec = {'embed': ((m['vocab'], m['d']), f32),
            'loop/exit/norm_final/scale': ((m['d'],), f32),
            'loop/exit/gate/kernel': ((m['d'], 1), f32),
            'loop/exit/gate/bias': ((1,), f32),
            'lm_head': ((m['d'], m['vocab']), f32)}
    for name, shape in layer_spec(m).items():
        spec['loop/layers/' + name] = ((m['layers'],) + shape, f32)
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


def attention(u, p, m, ein):
    b, t, _ = u.shape
    h, hkv, hd = m['n_heads'], m['n_kv_heads'], m['head_dim']
    q = rotary(ein('btd,dhk->bthk', u, p['attn/q_proj/kernel']),
               m['rope_theta'])
    k = rotary(ein('btd,dhk->bthk', u, p['attn/k_proj/kernel']),
               m['rope_theta'])
    v = ein('btd,dhk->bthk', u, p['attn/v_proj/kernel'])
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


def ffn(f, p, ein):
    hidden = jax.nn.silu(ein('btd,df->btf', f, p['mlp/ffn/wi_gate/kernel'])) \
        * ein('btd,df->btf', f, p['mlp/ffn/wi_up/kernel'])
    return ein('btf,fd->btd', hidden, p['mlp/ffn/wo/kernel'])


def layer(x, p, m, rnd):
    """One sandwich-normed layer; ``p`` holds this layer's slice of every
    ``layers/...`` leaf, named from the layer's own root."""
    ein = lambda eq, a, b: jnp.einsum(   # noqa: E731
        eq, rnd(a), rnd(b), precision=common.HIGHEST)
    h = x + rms_norm(attention(rms_norm(x, p['attn/norm_in/scale']), p, m,
                               ein), p['attn/norm_out/scale'])
    return h + rms_norm(ffn(rms_norm(h, p['mlp/norm_in/scale']), p, ein),
                        p['mlp/norm_out/scale'])


def exit_ce(s, head, targets, rnd):
    """CE(s W_head, targets) of every token of s [T, D], a block of
    ``TOKEN_BLOCK`` tokens at a time."""
    t = s.shape[0]
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    @jax.checkpoint
    def one(args):
        x, y = args
        logits = jnp.einsum('td,dv->tv', rnd(x), rnd(head),
                            precision=common.HIGHEST)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    ce = jax.lax.map(one, (s.reshape(t // block, block, -1),
                           targets.reshape(t // block, block)))
    return ce.reshape(t)


def exit_distribution(gates):
    """p(t) [N, T] from the gates' logits [N, T], written out."""
    lam = jax.nn.sigmoid(gates)
    n = gates.shape[0]
    stay = jnp.ones_like(lam[0])
    out = []
    for i in range(n - 1):
        out.append(lam[i] * stay)
        stay = stay * (1.0 - lam[i])
    return jnp.stack(out + [stay])


def sequence_loss(params: dict, tokens, model: dict, beta: float, rnd):
    """The loss of one sequence tokens [T]: the recurrent steps are a
    scan over the same stacked leaves, each a scan over the layers."""
    m = _sizes(model)
    stacked = {k[len(STACK):]: v for k, v in params.items()
               if k.startswith(STACK)}

    @jax.checkpoint
    def layer_body(x, p):
        return layer(x, p, m, rnd), None

    # the token at position i is the target of position i - 1; the last
    # position's target (the first token) is left out of the mean
    targets = jnp.roll(tokens, -1)

    def step_body(x, _):
        x, _ = jax.lax.scan(layer_body, x, stacked)
        x = rms_norm(x, params['loop/exit/norm_final/scale'])
        gate = jnp.einsum(
            'td,do->t', rnd(x[0]), rnd(params['loop/exit/gate/kernel']),
            precision=common.HIGHEST) + params['loop/exit/gate/bias'][0]
        return x, (gate, exit_ce(x[0], params['lm_head'], targets, rnd))

    x = jnp.take(params['embed'], tokens, axis=0)[None]
    _, (gates, ces) = jax.lax.scan(step_body, x, None,
                                   length=m['ut_steps'])
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(p), 0)
    per_token = jnp.sum(p * ces, 0) - beta * entropy
    return jnp.mean(per_token[:-1])


def loss_fn(params: dict, tokens, model: dict, beta: float, rnd):
    """The mean over the sequences of tokens [B,T] of each one's loss."""
    return jnp.mean(jnp.stack([sequence_loss(params, row, model, beta, rnd)
                               for row in tokens]))


def train_flops_per_sample(model: dict, data: dict) -> float:
    """FLOPs the forward and backward passes of one SEQUENCE require:
    every parameter of a layer (the norms' gains counted as weights) at
    6 a token in each of the ``ut_steps`` applications, the head at 6 a
    token and weight at each of the ``ut_steps`` exits, the gate's
    kernel likewise, and causal attention over ``n_heads`` heads in
    every layer application (backward twice the forward). The embedding
    gather, norms' arithmetic, activations and every recomputation are
    not counted."""
    import numpy as np
    from benchmark import flops
    m = _sizes(model)
    seq, steps = int(data['seq_len']), m['ut_steps']
    per_layer = sum(int(np.prod(s)) for s in layer_spec(m).values())
    weights = steps * (m['layers'] * per_layer + m['d'] * m['vocab']
                       + m['d'])
    attention = steps * m['layers'] * (
        flops.causal_attention(seq, m['n_heads'], m['head_dim'])
        + flops.causal_attention(seq, m['n_heads'], m['head_dim'],
                                 backward=True))
    return 6.0 * seq * weights + attention


def train(job: dict, params: dict, feeds, operands='float32',
          fault=None, steps=3) -> dict:
    """Follow the first ``steps`` steps of the job; ``feeds[i]['feed']``
    is step i's rows of tokens [B,T]. ``fault='half_batch'`` leaves the
    second half of every batch out and takes the mean over the rest.

    A step's loss and gradient are the means over its sequences of each
    one's, taken a sequence at a time with the sum kept on the host, so
    that the chip holds the parameters and one sequence's program; then
    ``common.follow``'s update leaf by leaf."""
    import numpy as np
    rnd = common.rounder(operands)

    @jax.jit
    def one(params, row):
        return jax.value_and_grad(sequence_loss)(params, row, job['model'],
                                                 BETA, rnd)

    def loss_and_grads(params, feed, step):
        rows = np.asarray(feed['feed'])
        if fault == 'half_batch':
            rows = rows[:rows.shape[0] // 2]
        loss, total = 0.0, {}
        for row in rows:
            value, grads = one(params, row)
            loss += float(value)
            for k in list(grads):
                total[k] = total.get(k, 0.0) + np.asarray(grads.pop(k))
        return loss / len(rows), {k: v / len(rows)
                                  for k, v in total.items()}

    return common.follow(loss_and_grads, job['optimizer'], params, feeds,
                         steps, offload=True)
