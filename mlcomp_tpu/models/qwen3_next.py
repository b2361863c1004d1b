"""Qwen3-Next decoder (``model_type: qwen3_next``): gated-DeltaNet
linear attention and gated grouped-query attention in one stack, every
layer followed by a sparse top-k mixture of experts with a shared
expert.

The stack is made from a period: layer ``i`` holds full attention where
``(i + 1) % full_attention_interval == 0`` and a gated DeltaNet mixer
otherwise, so depth is a number (whole periods are scanned where there
is more than one; layers past the last whole period are linear). Every
block is a flax module named for what it is — ``linear_attn``,
``full_attn``, ``moe`` — which is also its ``jax.named_scope`` on the
device trace; the kernels inside carry names of their own
(``gated_delta_prepare`` / ``gated_delta_fwd`` / ``gated_delta_bwd_scan``
/ ``gated_delta_prepare_bwd``, ``gqa_attn``, ``expert_matmul``).

The expert layer is ``models/decoder_parts.py``'s ``SparseMoe`` — the
share of the experts a program holds (``experts_held`` /
``expert_offset``), the sorted buffer and its counters are described
there — with this model's router: a softmax whose top-k values,
renormalised, are the weights, and a gated shared expert.

Counters (sown under ``intermediates``, carried out of the step by
``train/loop.py`` and emitted per epoch by ``JaxTrain``):
``moe.local_assign_share``, ``moe.load_max_over_mean``, ``moe.dropped``,
``gated_delta.chunks``.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.core import meta as flax_meta
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from mlcomp_tpu.models.base import register_model
from mlcomp_tpu.models.decoder_parts import (
    MoeConfig, SparseMoe, dense, per_device, remat_saving, rms_norm,
    rotary,
)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    # the keys of the published config.json, under the repo's names
    vocab_size: int = 151936
    d_model: int = 2048                 # hidden_size
    n_layers: int = 48                  # num_hidden_layers
    full_attention_interval: int = 4
    n_heads: int = 16                   # num_attention_heads
    n_kv_heads: int = 2                 # num_key_value_heads
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_key_heads: int = 16          # linear_num_key_heads
    linear_value_heads: int = 32        # linear_num_value_heads
    linear_key_dim: int = 128           # linear_key_head_dim
    linear_value_dim: int = 128         # linear_value_head_dim
    linear_conv_kernel: int = 4         # linear_conv_kernel_dim
    n_experts: int = 512                # num_experts (the router's width)
    top_k: int = 10                     # num_experts_per_tok
    d_expert: int = 512                 # moe_intermediate_size
    d_shared: int = 512                 # shared_expert_intermediate_size
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6               # rms_norm_eps
    # the share of the experts this program holds (module docstring);
    # None = all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_buffer_factor: Optional[float] = None
    # how it runs
    dtype: str = 'bfloat16'
    remat: bool = False
    scan_layers: Any = 'auto'           # scan over whole periods
    attn_impl: str = 'auto'             # ops/flash_attention.py
    delta_impl: str = 'auto'            # ops/gated_delta.py
    delta_chunk: int = 64
    moe_impl: str = 'auto'              # 'gmm' | 'interpret' | 'ragged'

    @property
    def held(self):
        return self.n_experts if self.experts_held is None \
            else int(self.experts_held)


# ------------------------------------------------------------ the mixers
class GatedAttention(nn.Module):
    """Grouped-query causal attention with per-head q/k RMSNorm, partial
    rotary positions and a sigmoid output gate."""
    cfg: Qwen3NextConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        qg = dense((h, 2 * d), ('embed', 'heads', 'kv'), dtype,
                   'q_proj')(x)
        q, gate = qg[..., :d], qg[..., d:]
        k = dense((hkv, d), ('embed', 'heads', 'kv'), dtype,
                  'k_proj')(x)
        v = dense((hkv, d), ('embed', 'heads', 'kv'), dtype,
                  'v_proj')(x)
        q = rms_norm(cfg, 'q_norm', ('kv',))(q)
        k = rms_norm(cfg, 'k_norm', ('kv',))(k)
        rot = int(d * cfg.partial_rotary_factor)
        q = rotary(q, cfg.rope_theta, rot)
        k = rotary(k, cfg.rope_theta, rot)
        q = nn.with_logical_constraint(q, ('batch', 'seq', 'heads', 'kv'))

        from mlcomp_tpu.ops.flash_attention import fused_attention

        def attend(q, k, v):
            with jax.named_scope('gqa_attn'):
                return fused_attention(q, k, v, causal=True,
                                       impl=cfg.attn_impl)

        out = per_device(self.mesh, attend, 3, q, k, v)
        out = out * jax.nn.sigmoid(gate)
        out = dense(cfg.d_model, ('heads', 'kv', 'embed'), dtype,
                    'o_proj', axis=(-2, -1))(out)
        return nn.with_logical_constraint(out, ('batch', 'seq', 'embed'))


def causal_depthwise_conv(x, kernel):
    """y[t] = sum_j kernel[j] * x[t - (K-1) + j] over x [B,T,C] with
    kernel [K,C]: left padding K-1, no bias."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * kernel[j] for j in range(k))


class GatedDeltaNet(nn.Module):
    """The gated-DeltaNet mixer (``ops/gated_delta.py``)."""
    cfg: Qwen3NextConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        f32 = jnp.float32
        hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
        b, t, _ = x.shape
        key_w, val_w = hk * dk, hv * dv
        qkvz = checkpoint_name(
            dense(2 * key_w + 2 * val_w, ('embed', 'mlp'), dtype,
                  'in_proj_qkvz')(x), 'linear_attn.qkvz')
        ba = dense(2 * hv, ('embed', 'heads'), dtype, 'in_proj_ba')(x)
        conv = self.param(
            'conv', nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (None, 'mlp')),
            (cfg.linear_conv_kernel, 2 * key_w + val_w), jnp.float32)
        a_log = self.param(
            'A_log', nn.with_logical_partitioning(
                nn.initializers.zeros, ('heads',)), (hv,), f32)
        dt_bias = self.param(
            'dt_bias', nn.with_logical_partitioning(
                nn.initializers.zeros, ('heads',)), (hv,), f32)

        mixed, z = qkvz[..., :2 * key_w + val_w], qkvz[..., -val_w:]
        mixed = nn.silu(causal_depthwise_conv(mixed, conv.astype(dtype)))
        q = mixed[..., :key_w].reshape(b, t, hk, dk)
        k = mixed[..., key_w:2 * key_w].reshape(b, t, hk, dk)
        v = mixed[..., 2 * key_w:].reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., hv:].astype(f32) + dt_bias)

        def unit(y):            # L2 norm over the head dimension
            y = y.astype(f32)
            return y * jax.lax.rsqrt(
                jnp.sum(y * y, -1, keepdims=True) + 1e-6)

        # each key head serves hv / hk value heads
        q = jnp.repeat((unit(q) * dk ** -0.5).astype(dtype), hv // hk, 2)
        k = jnp.repeat(unit(k).astype(dtype), hv // hk, 2)

        from mlcomp_tpu.ops.gated_delta import chunk_count, \
            gated_delta_rule
        o = per_device(
            self.mesh,
            lambda *a: gated_delta_rule(*a, chunk=cfg.delta_chunk,
                                        impl=cfg.delta_impl),
            5, q, k, v, g, beta)
        self.sow('intermediates', 'gated_delta.chunks',
                 jnp.float32(chunk_count(b, t, hv, cfg.delta_chunk)))
        o = rms_norm(cfg, 'norm', ('kv',))(o)
        o = o * nn.silu(z.reshape(b, t, hv, dv))
        out = dense(cfg.d_model, ('mlp', 'embed'), dtype, 'out_proj')(o.reshape(b, t, val_w))
        return nn.with_logical_constraint(out, ('batch', 'seq', 'embed'))


# -------------------------------------------------------------- the stack
class Qwen3NextLayer(nn.Module):
    cfg: Qwen3NextConfig
    full: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = rms_norm(cfg, 'norm_mixer')(x)
        if self.full:
            x = x + GatedAttention(cfg, self.mesh, name='full_attn')(y)
        else:
            x = x + GatedDeltaNet(cfg, self.mesh, name='linear_attn')(y)
        y = rms_norm(cfg, 'norm_moe')(x)
        x = x + SparseMoe(MoeConfig.of(cfg), self.mesh, name='moe')(y)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))


# What a `remat`ted layer holds for its backward pass besides its
# input: the values that are dear to make again and cheap to hold
# (docs/qwen3_next.md, "What remat holds"). The names are given where
# the values are made: in the forward rules of ``ops/gated_delta.py``
# and ``ops/flash_attention.py``, in ``SparseMoe.routed`` and in
# ``GatedDeltaNet``. Everything else of a layer is computed again.
REMAT_SAVED = (
    # the delta op: without these the backward runs `gated_delta_prepare`
    # and `gated_delta_fwd` once more
    'gated_delta.out', 'gated_delta.states', 'gated_delta.inputs',
    # the flash forward kernel
    'flash_attn.out', 'flash_attn.lse', 'flash_attn.qkv',
    # the router's float32 product at Precision.HIGHEST and softmax;
    # top-k (weights, indices), argsort (order), bincount (sizes)
    'moe.probs', 'moe.routing',
    # the widest projection of the stack, [tokens, 12288]
    'linear_attn.qkvz',
)


def _layer_class(cfg, **remat_kwargs):
    """``Qwen3NextLayer``, under ``cfg.remat`` holding ``REMAT_SAVED``."""
    return remat_saving(Qwen3NextLayer, cfg.remat, REMAT_SAVED,
                        **remat_kwargs)


class Qwen3NextPeriod(nn.Module):
    """One period of the layer pattern: ``interval - 1`` linear layers
    and a full one; the body of the scan over periods."""
    cfg: Qwen3NextConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        layer = _layer_class(cfg, prevent_cse=False)    # inside a scan
        for i in range(cfg.full_attention_interval):
            full = i == cfg.full_attention_interval - 1
            x = layer(cfg, full, self.mesh, name=f'layer_{i}')(x)
        return x, None


class Qwen3NextLM(nn.Module):
    cfg: Qwen3NextConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        table = self.param(
            'embed', nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'embed')),
            (cfg.vocab_size, cfg.d_model), jnp.float32)
        x = jnp.take(table, tokens, axis=0).astype(dtype)
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))

        interval = cfg.full_attention_interval
        periods = cfg.n_layers // interval
        use_scan = periods > 1 if cfg.scan_layers == 'auto' \
            else bool(cfg.scan_layers) and periods > 0
        first = 0
        if use_scan:
            scanned = nn.scan(
                Qwen3NextPeriod,
                variable_axes={'params': 0, 'intermediates': 0},
                split_rngs={'params': True}, in_axes=nn.broadcast,
                length=periods,
                metadata_params={flax_meta.PARTITION_NAME: 'layers'})
            x, _ = scanned(cfg, self.mesh, name='periods')(x, None)
            first = periods * interval
        layer = _layer_class(cfg)
        for i in range(first, cfg.n_layers):
            # preflight: disable=jax-layer-loop
            full = (i + 1) % interval == 0
            x = layer(cfg, full, self.mesh, name=f'layer_{i}')(x)

        x = rms_norm(cfg, 'norm_final')(x)
        logits = dense(cfg.vocab_size, ('embed', 'vocab'), dtype,
                       'lm_head')(x)
        return nn.with_logical_constraint(
            logits, ('batch', 'seq', 'vocab'))


@register_model('qwen3_next')
def _qwen3_next(mesh=None, **kwargs):
    fields = {f.name for f in dataclasses.fields(Qwen3NextConfig)}
    cfg = Qwen3NextConfig(
        **{k: v for k, v in kwargs.items() if k in fields})
    return Qwen3NextLM(cfg, mesh=mesh)


__all__ = ['Qwen3NextConfig', 'Qwen3NextLM', 'causal_depthwise_conv']
