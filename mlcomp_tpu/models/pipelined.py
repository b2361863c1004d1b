"""Pipeline-parallel transformer LM.

The decoder layers' parameters live in STACKED arrays with a leading
``stage`` logical axis (→ ``pp`` mesh axis), and the layer math is
expressed as pure functions over one layer's slice — so the same
parameters run either as a plain ``lax.scan`` over layers (no pp axis)
or through the GPipe microbatch schedule (``parallel/pipeline.py``)
with each pp rank holding only its stage's weights. Numerics are
identical by construction (tests assert it).

This is a deliberately self-contained sibling of ``TransformerLM``:
pipelining requires raw stacked parameter pytrees and shard_map-local
math (no logical-constraint annotations inside the scheduled region),
which doesn't mix with the per-layer flax module structure.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mlcomp_tpu.models.base import register_model
from mlcomp_tpu.models.transformer import TransformerConfig
from mlcomp_tpu.parallel.pipeline import (
    merge_microbatches, pipeline_apply, split_microbatches, stage_apply,
)


def _rms_norm(h, scale, eps=1e-6):
    h32 = h.astype(jnp.float32)
    norm = h32 * jax.lax.rsqrt(
        jnp.mean(h32 * h32, axis=-1, keepdims=True) + eps)
    return (norm * scale).astype(h.dtype)


def _causal_attention(q, k, v):
    """Dense causal attention over [B, T, H, Dh] — pure jnp so it runs
    inside shard_map on any backend."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decoder_layer_fn(dtype):
    """(layer_params, h) -> h for ONE layer's parameter slice."""

    def apply(lp, h):
        y = _rms_norm(h, lp['attn_norm'])
        qkv = jnp.einsum('btd,dchk->btchk', y.astype(dtype),
                         lp['qkv'].astype(dtype))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = _causal_attention(q, k, v)
        h = h + jnp.einsum('bthk,hkd->btd', attn,
                           lp['attn_out'].astype(dtype))
        y = _rms_norm(h, lp['mlp_norm'])
        gate = jnp.einsum('btd,df->btf', y.astype(dtype),
                          lp['wi_gate'].astype(dtype))
        up = jnp.einsum('btd,df->btf', y.astype(dtype),
                        lp['wi_up'].astype(dtype))
        h = h + jnp.einsum('btf,fd->btd', nn.silu(gate) * up,
                           lp['wo'].astype(dtype))
        return h

    return apply


class PipelinedTransformerLM(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    n_microbatches: int = 4

    def _stacked_layer_params(self):
        cfg = self.cfg
        d, h_heads, dh, f = (cfg.d_model, cfg.n_heads, cfg.head_dim,
                             cfg.d_ff)
        n = cfg.n_layers
        init = nn.initializers.lecun_normal()

        def stacked(name, shape, axes, initializer=init):
            return self.param(
                name, nn.with_logical_partitioning(initializer, axes),
                (n, *shape))

        return {
            'attn_norm': stacked('attn_norm', (d,), ('stage', 'norm'),
                                 nn.initializers.ones),
            'qkv': stacked('qkv', (d, 3, h_heads, dh),
                           ('stage', 'embed', 'qkv', 'heads', 'kv')),
            'attn_out': stacked('attn_out', (h_heads, dh, d),
                                ('stage', 'heads', 'kv', 'embed')),
            'mlp_norm': stacked('mlp_norm', (d,), ('stage', 'norm'),
                                nn.initializers.ones),
            'wi_gate': stacked('wi_gate', (d, f),
                               ('stage', 'embed', 'mlp')),
            'wi_up': stacked('wi_up', (d, f), ('stage', 'embed', 'mlp')),
            'wo': stacked('wo', (f, d), ('stage', 'mlp', 'embed')),
        }

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'embed')),
            name='embed')
        h = embed(tokens)
        pos = self.param(
            'pos_embed',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('seq', 'embed')),
            (cfg.max_seq_len, cfg.d_model))
        h = h + pos[None, :tokens.shape[1], :].astype(dtype)

        stacked = self._stacked_layer_params()
        layer_fn = decoder_layer_fn(dtype)
        pp = (self.mesh.shape['pp']
              if self.mesh is not None and 'pp' in self.mesh.axis_names
              else 1)
        if cfg.n_layers % max(pp, 1):
            raise ValueError(
                f'n_layers={cfg.n_layers} must be a multiple of the pp '
                f'mesh axis ({pp}) — every stage holds an equal slice '
                f'of the layer stack')
        # unbox for raw-pytree math (plain scan or shard_map pipeline)
        raw = jax.tree.map(
            lambda x: x.value if isinstance(x, nn.Partitioned)
            else x, stacked,
            is_leaf=lambda x: isinstance(x, nn.Partitioned))
        if pp > 1:
            data = tuple(a for a in ('dp', 'fsdp')
                         if a in self.mesh.axis_names)
            batch_part = data if len(data) > 1 else (
                data[0] if data else None)
            param_spec = jax.tree.map(
                lambda x: P('pp'), raw,
                is_leaf=lambda x: hasattr(x, 'ndim'))
            act_spec = P(batch_part)
            n_micro = self.n_microbatches

            def pipelined(params, x):
                # microbatch the LOCAL (per-dp-shard) batch — each dp
                # replica runs its own pipeline over the pp axis. Small
                # traces (init forwards, tail evals) get as many
                # microbatches as the local batch divides into; the
                # schedule's numerics are invariant to the count.
                import math
                m = math.gcd(n_micro, x.shape[0])
                x_mb = split_microbatches(x, max(m, 1))
                y = pipeline_apply(layer_fn, params, x_mb,
                                   axis_name='pp')
                return merge_microbatches(y)

            run = shard_map(
                pipelined, mesh=self.mesh,
                in_specs=(param_spec, act_spec), out_specs=act_spec,
                check_vma=False)
            h = run(raw, h)
        else:
            h = stage_apply(layer_fn, raw, h)

        scale = self.param(
            'final_norm',
            nn.with_logical_partitioning(nn.initializers.ones, ('norm',)),
            (cfg.d_model,))
        h = _rms_norm(h, scale)
        head = self.param(
            'lm_head',
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ('embed', 'vocab')),
            (cfg.d_model, cfg.vocab_size))
        return jnp.einsum('btd,dv->btv', h.astype(jnp.float32),
                          head.astype(jnp.float32))


@register_model('pipelined_lm')
def _pipelined(mesh=None, n_microbatches=4, **kwargs):
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    cfg = TransformerConfig(
        **{k: v for k, v in kwargs.items() if k in fields})
    # loud-failure contract (cf. train/optim.py): this model's raw
    # einsum math implements none of these TransformerConfig knobs —
    # accepting them silently would train a different model than the
    # config says
    if cfg.matmul_precision != 'bf16':
        raise ValueError(
            f"pipelined_lm does not implement matmul_precision="
            f"{cfg.matmul_precision!r} (its layer math is raw einsums"
            f" — use transformer_lm for int8 training)")
    if cfg.param_dtype != 'float32':
        raise ValueError(
            f"pipelined_lm does not implement param_dtype="
            f"{cfg.param_dtype!r}; its params are created in f32")
    if cfg.scan_layers is True:
        raise ValueError(
            'pipelined_lm stages already scan their layer slices '
            '(stage_apply) — scan_layers does not apply; leave it '
            "'auto'")
    return PipelinedTransformerLM(cfg, mesh=mesh,
                                  n_microbatches=int(n_microbatches))


__all__ = ['PipelinedTransformerLM', 'decoder_layer_fn']
