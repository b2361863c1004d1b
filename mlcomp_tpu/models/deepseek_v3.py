"""DeepSeek-V3 decoder (``model_type: deepseek_v3``): multi-head latent
attention in its expanded (training) form, the first
``n_dense_layers`` layers with a dense SwiGLU and the others with
sparse top-k experts routed by sigmoid scores with a selection bias
beside an UNGATED shared expert; an untied head.

Every layer: ``u = norm_mixer(h); h += mla(u); f = norm_ffn(h); h +=
ffn(f)``, RMSNorm with a gain, no bias anywhere.

- ``LatentAttention``: ``q = u W_q`` as ``n_heads`` heads of
  ``qk_nope_head_dim + qk_rope_head_dim`` (no query low-rank path);
  ``[c | k_r] = u W_kva`` (``kv_lora_rank`` | ``qk_rope_head_dim``);
  ``c = RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` a head
  (``qk_nope_head_dim`` | ``v_head_dim``). Rotary positions, neighbours
  turned against each other (``rope_interleave``), on the LAST
  ``qk_rope_head_dim`` of every query head and on ``k_r``, which is ONE
  vector a token: head ``j``'s key is ``[k_nope_j | k_r]``. Causal
  softmax attention at the scale of the score head, ``(nope +
  rope) ** -0.5``, with values of ``v_head_dim``
  (``ops/flash_attention.py`` inside the named scope ``mla_attn``;
  the shared rotary key is laid beside every head's own part by XLA,
  so the kernels see plain keys as wide as the queries). The absorbed
  form (one latent key for all heads) is a decode form and is not here.
- the sparse ffn is ``models/decoder_parts.py``'s ``SparseMoe`` — the
  class ``qwen3_next`` and ``lfm2_moe`` run — told by this config that
  the scores are sigmoids, that the top-k is taken of ``score +
  expert_bias`` (``topk_method: noaux_tc`` with one group) while the
  weights are the scores renormalised and times
  ``routed_scaling_factor``, and that the shared expert
  (``n_shared_experts * d_expert`` wide) is added without a gate.
  ``experts_held`` / ``expert_offset`` say which experts this program
  holds (the share, that module's docstring); the dense ffn is
  ``transformer.py``'s ``MlpBlock``.

The stack is a loop over layers (a scan over the identical sparse layers
needs ``leaf_updates`` among ``nn.scan``'s ``variable_axes``). Under
``remat`` each layer holds ``REMAT_SAVED`` by name and makes the rest
again (``docs/deepseek_v3.md``).

Counters (sown under ``intermediates``): ``moe.local_assign_share``,
``moe.load_max_over_mean``, ``moe.dropped`` from ``SparseMoe`` and
``mla_attn.rows``, the rows through the attention op a step.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from mlcomp_tpu.models.base import register_model
from mlcomp_tpu.models.decoder_parts import (
    MoeConfig, SparseMoe, dense, per_device, remat_saving, rms_norm,
    rotary,
)
from mlcomp_tpu.models.transformer import MlpBlock, TransformerConfig


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    # the keys of the published config.json, under the repo's names
    # (the defaults are kanana-2-30b-a3b's)
    vocab_size: int = 128256
    d_model: int = 2048                 # hidden_size
    n_layers: int = 48                  # num_hidden_layers
    n_dense_layers: int = 1             # first_k_dense_replace
    d_ff: int = 6144                    # intermediate_size
    n_heads: int = 32                   # num_attention_heads
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    n_experts: int = 128                # n_routed_experts (router's width)
    top_k: int = 6                      # num_experts_per_tok
    d_expert: int = 768                 # moe_intermediate_size
    n_shared_experts: int = 2           # one SwiGLU of this many d_expert
    routed_scaling_factor: float = 2.448
    rms_eps: float = 1e-6               # rms_norm_eps
    # what no key of the file gives (the configuration's `assumed`)
    norm_topk_eps: float = 1e-20
    expert_bias_update_rate: float = 0.0    # the load rule's; 0: none
    # the share of the experts this program holds; None = all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_buffer_factor: Optional[float] = None
    # how it runs
    dtype: str = 'bfloat16'
    remat: bool = False
    attn_impl: str = 'auto'             # ops/flash_attention.py
    moe_impl: str = 'auto'              # 'gmm' | 'interpret' | 'ragged'

    # what the family has ONE form of (no field, so no option): read by
    # ``LatentAttention`` and, beside the fields above, ``MoeConfig.of``
    rope_interleave = True              # rotary turns neighbouring pairs
    norm_topk_prob = True
    router_score = 'sigmoid'            # scoring_func
    expert_bias = True                  # topk_method: noaux_tc
    shared_gate = False                 # the shared expert added as it is

    @property
    def d_shared(self):
        return self.n_shared_experts * self.d_expert


class LatentAttention(nn.Module):
    """Multi-head latent attention, expanded: keys and values made a
    head from the normalised latent, one rotary key shared by all
    heads."""
    cfg: DeepseekV3Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, rank = cfg.n_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        q = dense((h, nope + rope), ('embed', 'heads', 'kv'), dtype,
                  'q_proj')(x)
        kva = dense(rank + rope, ('embed', None), dtype, 'kv_a_proj')(x)
        latent = rms_norm(cfg, 'kv_a_norm', (None,))(kva[..., :rank])
        kv = dense((h, nope + dv), (None, 'heads', 'kv'), dtype,
                   'kv_b_proj')(latent)
        turn = lambda a: rotary(  # noqa: E731
            a, cfg.rope_theta, rope, interleaved=cfg.rope_interleave)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
        # the one rotary key a token, beside every head's own part
        k_rope = turn(kva[..., None, rank:])
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h, rope))], -1)
        v = kv[..., nope:]
        q = nn.with_logical_constraint(q, ('batch', 'seq', 'heads', 'kv'))

        from mlcomp_tpu.ops.flash_attention import fused_attention

        def attend(q, k, v):
            with jax.named_scope('mla_attn'):
                return fused_attention(q, k, v, causal=True,
                                       impl=cfg.attn_impl)

        out = per_device(self.mesh, attend, 3, q, k, v)
        self.sow('intermediates', 'mla_attn.rows',
                 jnp.float32(x.shape[0] * x.shape[1]))
        out = dense(cfg.d_model, ('heads', 'kv', 'embed'), dtype,
                    'o_proj', axis=(-2, -1))(out)
        return nn.with_logical_constraint(out, ('batch', 'seq', 'embed'))


class DeepseekV3Layer(nn.Module):
    cfg: DeepseekV3Config
    sparse: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = rms_norm(cfg, 'norm_mixer')(x)
        x = x + LatentAttention(cfg, self.mesh, name='attn')(y)
        y = rms_norm(cfg, 'norm_ffn')(x)
        if self.sparse:
            x = x + SparseMoe(MoeConfig.of(cfg), self.mesh, name='moe')(y)
        else:
            x = x + MlpBlock(TransformerConfig(
                d_model=cfg.d_model, d_ff=cfg.d_ff, dtype=cfg.dtype),
                name='mlp')(y)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))


# What a `remat`ted layer holds for its backward pass besides its input
# (docs/deepseek_v3.md, "What remat holds"; the sizes and what each is
# worth are in PERF.md section 6, PR 35). The names are given where the
# values are made: in ``ops/flash_attention.py``'s forward and in
# ``SparseMoe.routed``. Computed again: the norms, ``kv_a_proj`` (the
# latent) and ``o_proj`` (the residual stream), the shared expert, the
# dense ffn, the gather of the routed rows. Not ``q_proj``, ``kv_b_proj``
# or the rotary turn: ``flash_attn.qkv`` holds what they make.
REMAT_SAVED = (
    # the flash forward kernel: its result, the row statistic, and its
    # operands q, k (192 a head) and v (128)
    'flash_attn.out', 'flash_attn.lse', 'flash_attn.qkv',
    # the router's float32 product at Precision.HIGHEST and its scores;
    # top-k (values, indices), argsort (order), bincount (sizes)
    'moe.probs', 'moe.routing',
    # the grouped products' results: gate and up [rows, d_expert], down
    # [rows, d_model] over the sorted buffer
    'moe.hidden', 'moe.out',
)


class DeepseekV3LM(nn.Module):
    cfg: DeepseekV3Config
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

        layer = remat_saving(DeepseekV3Layer, cfg.remat, REMAT_SAVED)
        for i in range(cfg.n_layers):
            # preflight: disable=jax-layer-loop
            x = layer(cfg, i >= cfg.n_dense_layers, self.mesh,
                      name=f'layer_{i}')(x)

        x = rms_norm(cfg, 'norm_final')(x)
        logits = dense(cfg.vocab_size, ('embed', 'vocab'), dtype,
                       'lm_head')(x)
        return nn.with_logical_constraint(
            logits, ('batch', 'seq', 'vocab'))


@register_model('deepseek_v3')
def _deepseek_v3(mesh=None, **kwargs):
    fields = {f.name for f in dataclasses.fields(DeepseekV3Config)}
    kwargs = {k: v for k, v in kwargs.items() if k in fields}
    return DeepseekV3LM(DeepseekV3Config(**kwargs), mesh=mesh)


__all__ = ['DeepseekV3Config', 'DeepseekV3LM', 'LatentAttention']
