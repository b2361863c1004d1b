"""Ring attention: exact blockwise attention over a sequence-parallel mesh
axis.

Long-context capability absent from the reference (SURVEY.md §2.3 — no
SP/CP anywhere in mlcomp; its workloads are CNNs). Here it is first-class:
the sequence dimension is sharded over the ``sp`` mesh axis, each device
computes attention of its local query block against K/V blocks that rotate
around the ring via ``lax.ppermute`` (one ICI hop per step), with online
(flash-style) softmax renormalisation so the result is exact.

Memory per device is O(T/n_sp) for activations — sequence length scales
linearly with the number of devices on the ``sp`` axis. Communication is
n_sp-1 neighbour exchanges of the local K/V block, fully overlappable with
compute by XLA since the ppermute of step i+1 has no data dependency on
step i's FLOPs.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attention(q, k, v, m, l, o, q_offset, k_offset, causal, scale):
    """One flash-attention accumulation step.

    q: [b, h, tq, d]; k, v: [b, h, tk, d]
    m, l: [b, h, tq] running max / normaliser; o: [b, h, tq, d] accum.
    q_offset / k_offset: global position of element 0 of each block.
    """
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        q_pos = q_offset + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = k_offset + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(k_pos > q_pos, NEG_INF, s)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        'bhqk,bhkd->bhqd', p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def ring_attention(q, k, v, *, axis_name: str, axis_size: int,
                   causal: bool = False, scale: Optional[float] = None):
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    Call inside ``shard_map``. Shapes (local shards): [batch, seq_local,
    heads, head_dim]. Returns the same shape/dtype as ``q``.
    """
    in_dtype = q.dtype
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # [b, t, h, d] -> [b, h, t, d] for contiguous attention math
    q_ = jnp.transpose(q, (0, 2, 1, 3))
    k_ = jnp.transpose(k, (0, 2, 1, 3))
    v_ = jnp.transpose(v, (0, 2, 1, 3))
    b, h, t, d = q_.shape

    my_idx = lax.axis_index(axis_name) if axis_size > 1 else 0
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    o0 = jnp.zeros((b, h, t, d), jnp.float32)

    # own (diagonal) block first — no communication
    m, l, o = _block_attention(
        q_, k_, v_, m0, l0, o0, q_offset=my_idx * t,
        k_offset=my_idx * t, causal=causal, scale=scale)

    if axis_size > 1:
        # then n_sp-1 rotate-and-accumulate steps (rotate FIRST so the
        # final iteration does no wasted ppermute)
        def step(carry, i):
            m, l, o, k_blk, v_blk = carry
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            kv_idx = (my_idx - i) % axis_size
            m, l, o = _block_attention(
                q_, k_blk, v_blk, m, l, o,
                q_offset=my_idx * t, k_offset=kv_idx * t,
                causal=causal, scale=scale)
            return (m, l, o, k_blk, v_blk), None

        (m, l, o, _, _), _ = lax.scan(
            step, (m, l, o, k_, v_), jnp.arange(1, axis_size))

    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(in_dtype)


def make_ring_attention(mesh: Mesh, causal: bool = False,
                        attn_impl: str = 'auto'):
    """Build an attention fn over GLOBAL [B, T, H, D] arrays: sequence
    sharded on ``sp``, batch on dp/fsdp, heads on ``tp``; exact ring
    attention between the sp shards. Without an sp axis, the Pallas
    flash kernel (or dense fallback) runs on each device's local
    batch/head shard.
    """
    sp = mesh.shape['sp'] if 'sp' in mesh.axis_names else 1
    data = tuple(a for a in ('dp', 'fsdp') if a in mesh.axis_names)
    batch_part = data if len(data) > 1 else (data[0] if data else None)
    head_part = 'tp' if 'tp' in mesh.axis_names else None
    spec = P(batch_part, 'sp' if sp > 1 else None, head_part, None)

    if sp <= 1:
        if attn_impl == 'dense':
            return functools.partial(_plain_attention, causal=causal)
        from mlcomp_tpu.ops.flash_attention import fused_attention

        # shard_map so the pallas_call sees per-device local shards
        # (batch over dp/fsdp, heads over tp); impl-auto still picks
        # dense off-TPU, inside the same spec
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)
        def sharded_local(q, k, v):
            return fused_attention(q, k, v, causal=causal,
                                   impl=attn_impl)

        dp_size = 1
        for a in ('dp', 'fsdp'):
            if a in mesh.axis_names:
                dp_size *= mesh.shape[a]
        tp_size = mesh.shape.get('tp', 1)

        def attend(q, k, v):
            # shard_map needs exact divisibility; uneven shapes (tail
            # eval batches, odd head counts) take the global dense path
            # where GSPMD handles padding
            if q.shape[0] % dp_size or q.shape[2] % tp_size:
                return _plain_attention(q, k, v, causal=causal)
            return sharded_local(q, k, v)

        return attend

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def sharded(q, k, v):
        return ring_attention(q, k, v, axis_name='sp', axis_size=sp,
                              causal=causal)

    return sharded


def _plain_attention(q, k, v, causal: bool):
    """Reference (non-ring) attention on global arrays [B, T, H, D] —
    one implementation of the dense math for the whole tree (the
    previous local copy drifted from ops/ in bf16 numerics)."""
    from mlcomp_tpu.ops.flash_attention import reference_attention
    return reference_attention(q, k, v, causal=causal)


__all__ = ['ring_attention', 'make_ring_attention']
