"""LFM2-MoE decoder (``model_type: lfm2_moe``): gated short-convolution
mixers and grouped-query attention mixers in one stack, by a LIST of
layer kinds; the first ``n_dense_layers`` layers carry a dense SwiGLU,
the others sparse top-k experts routed by sigmoid scores with a
selection bias, no shared expert; the head is the embedding, transposed.

Every layer: ``u = norm_mixer(h); h += mixer(u); f = norm_ffn(h); h +=
ffn(f)``, RMSNorm with a gain, no bias anywhere.

- ``conv`` (``ShortConv``): ``[B | C | X] = u W_in`` (d -> 3d, thirds
  in that order), ``z = causal depthwise conv(B * X)`` with
  ``conv_kernel`` taps a channel, ``mixer = (C * z) W_out``; the middle
  is one op, ``ops/short_conv.py`` (``short_conv_fwd`` /
  ``short_conv_bwd`` on the device trace).
- ``full_attention`` (``GroupedQueryAttention``): per-head RMSNorm of q
  and k over the head size, rotary positions on the whole head, causal
  softmax attention with ``n_heads / n_kv_heads`` query heads a
  key-value head (``ops/flash_attention.py`` inside the named scope
  ``gqa_attn``), no gate.
- the sparse ffn is ``models/decoder_parts.py``'s ``SparseMoe`` — the
  class ``models/qwen3_next.py`` runs, told by this config that the
  scores are sigmoids, that the top-k is taken of ``score +
  expert_bias`` while the weights are the scores, ``+ norm_topk_eps``
  in the renormalising sum, ``routed_scaling_factor``, and no shared
  expert. ``expert_bias`` gets no gradient; with
  ``expert_bias_update_rate`` the load rule moves it after every step,
  towards an even load over all ``n_experts``. ``experts_held`` /
  ``expert_offset`` say which experts this program holds (the share,
  that module's docstring); the dense ffn is ``transformer.py``'s
  ``MlpBlock``.

The layers differ, so the stack is a loop and not a scan. Under
``remat`` each layer holds ``REMAT_SAVED`` by name and makes the rest
again (``docs/lfm2_moe.md``).

Counters (sown under ``intermediates``): ``moe.local_assign_share``,
``moe.load_max_over_mean``, ``moe.dropped`` from ``SparseMoe`` and
``short_conv.rows``, the rows through the conv op a step.
"""

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from mlcomp_tpu.models.base import register_model
from mlcomp_tpu.models.decoder_parts import (
    MoeConfig, SparseMoe, dense, per_device, remat_saving, rms_norm,
    rotary,
)
from mlcomp_tpu.models.transformer import MlpBlock, TransformerConfig

#: the published order of LFM2-8B-A1B's 24 layers
LAYER_TYPES = tuple(
    'full_attention' if i in (2, 6, 10, 14, 18, 21) else 'conv'
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    # the keys of the published config.json, under the repo's names
    vocab_size: int = 65536
    d_model: int = 2048                 # hidden_size
    layer_types: Tuple[str, ...] = LAYER_TYPES
    n_dense_layers: int = 2             # num_dense_layers
    d_ff: int = 7168                    # intermediate_size
    n_heads: int = 32                   # num_attention_heads
    n_kv_heads: int = 8                 # num_key_value_heads
    head_dim: int = 64                  # hidden_size / heads
    rope_theta: float = 1e6
    conv_kernel: int = 3                # conv_L_cache
    n_experts: int = 32                 # num_experts (the router's width)
    top_k: int = 4                      # num_experts_per_tok
    d_expert: int = 1792                # moe_intermediate_size
    norm_topk_prob: bool = True
    expert_bias: bool = True            # use_expert_bias
    routed_scaling_factor: float = 1.0
    rms_eps: float = 1e-5               # norm_eps
    # what no key of the file gives (the configuration's `assumed`);
    # the head is the embedding, which no key says either
    router_score: str = 'sigmoid'
    norm_topk_eps: float = 1e-6
    expert_bias_update_rate: float = 0.0    # the load rule's; 0: none
    # the share of the experts this program holds; None = all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_buffer_factor: Optional[float] = None
    # how it runs
    dtype: str = 'bfloat16'
    remat: bool = False
    attn_impl: str = 'auto'             # ops/flash_attention.py
    conv_impl: str = 'auto'             # ops/short_conv.py
    moe_impl: str = 'auto'              # 'gmm' | 'interpret' | 'ragged'

    @property
    def held(self):
        return self.n_experts if self.experts_held is None \
            else int(self.experts_held)


# ------------------------------------------------------------ the mixers
class ShortConv(nn.Module):
    """The gated short-convolution mixer."""
    cfg: Lfm2MoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        d = cfg.d_model
        bcx = checkpoint_name(
            dense(3 * d, ('embed', 'mlp'), dtype, 'in_proj')(x),
            'short_conv.bcx')
        taps = self.param(
            'taps', nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (None, 'mlp')),
            (cfg.conv_kernel, d), jnp.float32)

        from mlcomp_tpu.ops.short_conv import gated_short_conv
        y = per_device(
            self.mesh,
            lambda bcx, taps: gated_short_conv(bcx, taps,
                                               impl=cfg.conv_impl),
            1, bcx, taps)
        self.sow('intermediates', 'short_conv.rows',
                 jnp.float32(x.shape[0] * x.shape[1]))
        out = dense(d, ('mlp', 'embed'), dtype, 'out_proj')(y)
        return nn.with_logical_constraint(out, ('batch', 'seq', 'embed'))


class GroupedQueryAttention(nn.Module):
    """Grouped-query causal attention with per-head q/k RMSNorm and
    rotary positions on the whole head; no gate."""
    cfg: Lfm2MoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        axes = ('embed', 'heads', 'kv')
        q = dense((h, d), axes, dtype, 'q_proj')(x)
        k = dense((hkv, d), axes, dtype, 'k_proj')(x)
        v = dense((hkv, d), axes, dtype, 'v_proj')(x)
        q = rotary(rms_norm(cfg, 'q_norm', ('kv',))(q), cfg.rope_theta, d)
        k = rotary(rms_norm(cfg, 'k_norm', ('kv',))(k), cfg.rope_theta, d)
        q = nn.with_logical_constraint(q, ('batch', 'seq', 'heads', 'kv'))

        from mlcomp_tpu.ops.flash_attention import fused_attention

        def attend(q, k, v):
            with jax.named_scope('gqa_attn'):
                return fused_attention(q, k, v, causal=True,
                                       impl=cfg.attn_impl)

        out = per_device(self.mesh, attend, 3, q, k, v)
        out = dense(cfg.d_model, ('heads', 'kv', 'embed'), dtype,
                    'o_proj', axis=(-2, -1))(out)
        return nn.with_logical_constraint(out, ('batch', 'seq', 'embed'))


# -------------------------------------------------------------- the stack
class Lfm2MoeLayer(nn.Module):
    cfg: Lfm2MoeConfig
    kind: str                   # 'conv' | 'full_attention'
    sparse: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = rms_norm(cfg, 'norm_mixer')(x)
        if self.kind == 'full_attention':
            x = x + GroupedQueryAttention(cfg, self.mesh, name='attn')(y)
        elif self.kind == 'conv':
            x = x + ShortConv(cfg, self.mesh, name='conv')(y)
        else:
            raise ValueError(f'no layer of the kind {self.kind!r}')
        y = rms_norm(cfg, 'norm_ffn')(x)
        if self.sparse:
            x = x + SparseMoe(MoeConfig.of(cfg), self.mesh, name='moe')(y)
        else:
            x = x + MlpBlock(TransformerConfig(
                d_model=cfg.d_model, d_ff=cfg.d_ff, dtype=cfg.dtype),
                name='mlp')(y)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))


# What a `remat`ted layer holds for its backward pass besides its input
# (docs/lfm2_moe.md, "What remat holds"; the measurements are in PERF.md
# section 6, PR 33): at 6.1 GB of arguments the chip has room for what is
# dear to make again. The names are given where the values are made: in
# ``ops/flash_attention.py``'s and ``ops/short_conv.py``'s forward, in
# ``SparseMoe.routed`` and in ``ShortConv``. Everything else of a layer
# (norms, the attention and output projections, the dense ffn, the
# gather of the routed rows) is computed again.
REMAT_SAVED = (
    # the flash forward kernel
    'flash_attn.out', 'flash_attn.lse', 'flash_attn.qkv',
    # the router's float32 product at Precision.HIGHEST and its scores;
    # top-k (values, indices), argsort (order), bincount (sizes)
    'moe.probs', 'moe.routing',
    # the conv mixer's input projection [tokens, 3 d_model] (the op's
    # own residual) and the op's result
    'short_conv.bcx', 'short_conv.out',
    # the grouped products' results: gate and up [rows, d_expert], down
    # [rows, d_model] over the sorted buffer
    'moe.hidden', 'moe.out',
)


class Lfm2MoeLM(nn.Module):
    cfg: Lfm2MoeConfig
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

        layer = remat_saving(Lfm2MoeLayer, cfg.remat, REMAT_SAVED)
        for i, kind in enumerate(cfg.layer_types):
            # preflight: disable=jax-layer-loop
            x = layer(cfg, kind, i >= cfg.n_dense_layers, self.mesh,
                      name=f'layer_{i}')(x)

        x = rms_norm(cfg, 'norm_final')(x)
        # the head is the embedding: one leaf, whose gradient is the sum
        # of the gather's and this product's
        logits = jnp.einsum('btd,vd->btv', x, table.astype(dtype))
        return nn.with_logical_constraint(
            logits, ('batch', 'seq', 'vocab'))


@register_model('lfm2_moe')
def _lfm2_moe(mesh=None, **kwargs):
    fields = {f.name for f in dataclasses.fields(Lfm2MoeConfig)}
    kwargs = {k: v for k, v in kwargs.items() if k in fields}
    if 'layer_types' in kwargs:         # a YAML list -> hashable
        kwargs['layer_types'] = tuple(kwargs['layer_types'])
    return Lfm2MoeLM(Lfm2MoeConfig(**kwargs), mesh=mesh)


__all__ = ['Lfm2MoeConfig', 'Lfm2MoeLM', 'ShortConv',
           'GroupedQueryAttention']
