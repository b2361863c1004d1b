"""Ouro decoder (``model_type: ouro``; Zhu et al., "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): a looped
language model. One stack of ``n_layers`` layers runs ``ut_steps``
times a token on the SAME weights; after every run of the stack comes
an exit: the final norm, a gate and the head over the whole vocabulary.

Every layer is sandwich-normed, RMSNorm with a gain, no bias anywhere:
``h = x + N2(attn(N1(x)))``, ``y = h + N4(swiglu(N3(h)))``.

- ``attn`` (``SandwichAttention``): q, k, v projections to ``n_heads``
  / ``n_kv_heads`` heads of ``head_dim``, rotary positions on the whole
  head (halves turned against each other), causal softmax attention
  (``ops/flash_attention.py`` inside the named scope ``gqa_attn``), the
  output projection; ``N1`` and ``N2`` are its own, so that every op of
  the branch, its residual add too, is named under ``attn``.
- ``mlp`` (``SandwichMlp``): ``transformer.py``'s ``MlpBlock`` between
  ``N3`` and ``N4``, named under ``mlp`` the same way.

The model: ``s_0 = embed(tokens)``; for ``t = 1..ut_steps``: ``s_t =
norm_final(stack(s_{t-1}))`` — the final norm closes every run of the
stack and its result feeds the next —, the exit gate ``lambda_t =
sigmoid(s_t w + b)``. The stack is a scan over the layers inside a scan
over the recurrent steps (``RecurrentStep``) whose parameters are
broadcast: they exist once, and the backward pass adds each
application's gradient into one accumulator. The exits' ops (final
norm, gate) are inside the module ``exit`` (``Exit``, `remat`ted).

The model returns the exits, not logits (``exits``); the loss
``looped_lm_ce`` (``train/loop.py``) applies the head exit by exit, so
that one exit's ``[tokens, vocab]`` logits are live at a time. Under
``remat`` a layer holds ``REMAT_SAVED`` by name and makes the rest
again; the scan holds each application's input.

Counters (sown under ``intermediates``): ``loop.expected_exit``, the
mean over tokens of ``sum_t t p(t)`` (between 1 and ``ut_steps``), and
``loop.layer_rows``, tokens times layer applications a step.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.core import meta as flax_meta
from jax.sharding import Mesh

from mlcomp_tpu.models.base import register_model
from mlcomp_tpu.models.decoder_parts import (
    dense, per_device, remat_saving, rms_norm, rotary,
)
from mlcomp_tpu.models.transformer import MlpBlock, TransformerConfig


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    # the keys of the published config.json, under the repo's names
    vocab_size: int = 49152
    d_model: int = 2048                 # hidden_size
    n_layers: int = 48                  # num_hidden_layers (held here)
    n_heads: int = 16                   # num_attention_heads
    n_kv_heads: int = 16                # num_key_value_heads
    head_dim: int = 128
    d_ff: int = 5632                    # intermediate_size
    rope_theta: float = 1e6
    rms_eps: float = 1e-6               # rms_norm_eps
    ut_steps: int = 4                   # total_ut_steps
    # how it runs
    dtype: str = 'bfloat16'
    remat: bool = False
    attn_impl: str = 'auto'             # ops/flash_attention.py


class SandwichAttention(nn.Module):
    """``x + norm_out(attention(norm_in(x)))``."""
    cfg: OuroConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        y = rms_norm(cfg, 'norm_in')(x)
        axes = ('embed', 'heads', 'kv')
        q = rotary(dense((h, d), axes, dtype, 'q_proj')(y),
                   cfg.rope_theta, d)
        k = rotary(dense((hkv, d), axes, dtype, 'k_proj')(y),
                   cfg.rope_theta, d)
        v = dense((hkv, d), axes, dtype, 'v_proj')(y)
        q = nn.with_logical_constraint(q, ('batch', 'seq', 'heads', 'kv'))

        from mlcomp_tpu.ops.flash_attention import fused_attention

        def attend(q, k, v):
            with jax.named_scope('gqa_attn'):
                return fused_attention(q, k, v, causal=True,
                                       impl=cfg.attn_impl)

        out = per_device(self.mesh, attend, 3, q, k, v)
        out = dense(cfg.d_model, ('heads', 'kv', 'embed'), dtype,
                    'o_proj', axis=(-2, -1))(out)
        return x + rms_norm(cfg, 'norm_out')(out)


class SandwichMlp(nn.Module):
    """``x + norm_out(swiglu(norm_in(x)))``."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = MlpBlock(TransformerConfig(
            d_model=cfg.d_model, d_ff=cfg.d_ff, dtype=cfg.dtype),
            name='ffn')(rms_norm(cfg, 'norm_in')(x))
        return x + rms_norm(cfg, 'norm_out')(y)


class OuroLayer(nn.Module):
    """One layer, as the body of the scan over the stack."""
    cfg: OuroConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, _):
        x = SandwichAttention(self.cfg, self.mesh, name='attn')(x)
        x = SandwichMlp(self.cfg, name='mlp')(x)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'embed')), \
            None


#: what a ``remat``ted layer holds for its backward pass besides its
#: input: nothing. The chip's 15.75 GB hold 9.8 GB of weights, gradients
#: and Adam's moments, the inputs of all ``ut_steps * n_layers`` layer
#: applications (1.07 GB at 8,192 tokens) and one exit's logits with
#: their gradient; the flash kernels' results held as well would add
#: another 1.07 GB (``PERF.md``, the ouro cell)
REMAT_SAVED = ()


def exit_log_probs(gate_logits):
    """log p(t) [N, ...] of the exit distribution from the gates' logits
    [N, ...] (``lambda_t = sigmoid(logit_t)``): ``p(t) = lambda_t
    prod_{j<t} (1 - lambda_j)`` for ``t < N`` and ``p(N) = prod_{j<N}
    (1 - lambda_j)`` — the last gate is not read. Sums to 1 over t."""
    stay = jax.nn.log_sigmoid(-gate_logits[:-1])        # log(1 - lambda)
    before = jnp.cumsum(stay, 0) - stay
    leave = jax.nn.log_sigmoid(gate_logits[:-1]) + before
    return jnp.concatenate([leave, jnp.sum(stay, 0, keepdims=True)], 0)


class ExitGate(nn.Module):
    """The gate's logit ``s w + b`` of every token of s [B, T, D]: s and
    w in s's dtype, the sum and the bias in float32 (so that the
    backward pass holds no float32 copy of s)."""

    @nn.compact
    def __call__(self, s):
        f32 = jnp.float32
        kernel = self.param('kernel', nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ('embed', None)),
            (s.shape[-1], 1), f32)
        bias = self.param('bias', nn.with_logical_partitioning(
            nn.initializers.zeros, (None,)), (1,), f32)
        return jnp.einsum('btd,do->bto', s, kernel.astype(s.dtype),
                          preferred_element_type=f32)[..., 0] + bias[0]


class Exit(nn.Module):
    """``s = norm_final(x)`` and the gate's logit of s. ``remat``ted by
    its caller, so that its backward pass holds x alone (the final
    norm's float32 values are made again)."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x):
        s = rms_norm(self.cfg, 'norm_final')(x)
        return s, ExitGate(name='gate')(s)


class RecurrentStep(nn.Module):
    """One run of the stack and its exit, as the body of the scan over
    the recurrent steps: ``s_t = norm_final(stack(s_{t-1}))`` and the
    gate's logit ``s_t w + b`` (float32). The stack is a scan over the
    ``n_layers`` layers, each ``remat``ted under ``REMAT_SAVED``."""
    cfg: OuroConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, _):
        cfg = self.cfg
        x, _ = nn.scan(
            remat_saving(OuroLayer, cfg.remat, REMAT_SAVED,
                         prevent_cse=False),
            variable_axes={'params': 0}, split_rngs={'params': True},
            length=cfg.n_layers,
            metadata_params={flax_meta.PARTITION_NAME: 'layers'},
        )(cfg, self.mesh, name='layers')(x, None)
        x, gate = nn.remat(Exit)(cfg, name='exit')(x)
        return x, (x, gate)


class OuroLM(nn.Module):
    cfg: OuroConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        """tokens [B, T] -> the exits: ``{'states': [N, B, T, D] (the
        normed state after each run of the stack), 'exit_logp': [N, B,
        T] float32 (log p(t)), 'head': [D, V] (the head's kernel)}``."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        f32 = jnp.float32
        table = self.param(
            'embed', nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'embed')),
            (cfg.vocab_size, cfg.d_model), f32)
        x = jnp.take(table, tokens, axis=0).astype(dtype)
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))

        # the recurrent steps: a scan whose parameters are broadcast, so
        # that the backward pass adds each application's gradient into
        # one accumulator as it goes
        _, (states, gates) = nn.scan(
            RecurrentStep, variable_broadcast='params',
            split_rngs={'params': False}, length=cfg.ut_steps,
        )(cfg, self.mesh, name='loop')(x, None)

        with jax.named_scope('exit'):
            logp = exit_log_probs(gates)
            steps = jnp.arange(1, cfg.ut_steps + 1, dtype=f32)
            self.sow('intermediates', 'loop.expected_exit', jnp.mean(
                jnp.einsum('n,nbt->bt', steps, jnp.exp(logp))))
        b, t = tokens.shape
        self.sow('intermediates', 'loop.layer_rows',
                 jnp.float32(b * t * cfg.n_layers * cfg.ut_steps))
        head = self.param(
            'lm_head', nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ('embed', 'vocab')),
            (cfg.d_model, cfg.vocab_size), f32)
        return {'states': states, 'exit_logp': logp, 'head': head}


@register_model('ouro')
def _ouro(mesh=None, **kwargs):
    fields = {f.name for f in dataclasses.fields(OuroConfig)}
    return OuroLM(OuroConfig(**{k: v for k, v in kwargs.items()
                                if k in fields}), mesh=mesh)


__all__ = ['OuroConfig', 'OuroLM', 'OuroLayer', 'RecurrentStep', 'Exit',
           'ExitGate', 'SandwichAttention', 'SandwichMlp', 'exit_log_probs']
