"""What the decoders of ``models/qwen3_next.py``, ``models/lfm2_moe.py``
and ``models/deepseek_v3.py`` share: the RMSNorm with a gain, rotary
positions, the per-device call round a Pallas kernel, the save-by-name
``remat`` class and the sparse expert layer, ``SparseMoe``, with its own
settings (``MoeConfig``).

**The share.** ``experts_held`` / ``expert_offset`` tell an expert layer
which of the ``n_experts`` it holds: ``[offset, offset + held)``. The
router keeps all ``n_experts`` outputs, the top-k and its
renormalisation run over all of them, the layer computes the part of
the sum that its own experts give for the tokens routed to them, and
the shared expert (where there is one; behind a sigmoid gate or,
``shared_gate: False``, added as it is) whole. What the absent experts
would add is left out: under expert parallelism their ranks add it. No
expert has a capacity: the (token, expert) pairs that land here are
sorted by expert and go through grouped matrix products
(``jax.lax.ragged_dot``, or the megablox Pallas kernel on a TPU)
whatever the split between experts. ``moe_buffer_factor`` bounds the
rows of that sorted buffer at a multiple of the even share (``tokens *
top_k * held / n_experts``); ``None`` sizes it for the worst case, so
that nothing can ever be left out. The ``moe.dropped`` counter says how
many pairs did not fit.

**The sorted buffer and its dead rows.** Row p of the buffer is the
pair ``order[p]`` of the stable sort by expert; the rows past the pairs
that landed and fit (``ends[-1]``) belong to no group, and the grouped
products leave their results there undefined (``grouped_matmul``). Rows
move between the tokens and the buffer in one of two forms, picked from
the shapes alone (``gathers_rows``: the pairs against
``SCATTER_ROW_COST`` times the buffer's rows):

- ``through_gathers`` — both ways row gathers, by the sort and its
  inverse (``buffer_slots``: each pair's row, or a sentinel). Into the
  buffer every row is some token's row, a dead one too; out of it only
  the rows a slot names are read, forward (the weighted sum over each
  token's pairs) and backward (the weights' gradient, and the gradient
  into the tokens, a gather of the buffer's gradient summed over each
  token's pairs in float32). A dead row's gradient is a number no path
  reads: a grouped product takes a row to the same row, so it stays in
  the dead rows, and the weight gradients (``tgmm``) read no row outside
  a group.
- ``through_scatters`` — scatter-adds of the buffer's rows into the
  tokens, which read every buffer row, so the dead ones are masked to
  zero before anything multiplies them, in both passes.

Counters (sown under ``intermediates``, carried out of the step by
``train/loop.py`` and emitted per epoch by ``JaxTrain``):
``moe.local_assign_share``, ``moe.load_max_over_mean``, ``moe.dropped``.
"""

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from mlcomp_tpu.models.transformer import (
    MlpBlock, TransformerConfig, _dense as dense,
)


def rms_norm(cfg, name, axes=('norm',)):
    """RMSNorm with a float32 gain, by the config's ``rms_eps`` and
    ``dtype``."""
    return nn.RMSNorm(
        epsilon=cfg.rms_eps, dtype=jnp.dtype(cfg.dtype), name=name,
        param_dtype=jnp.float32,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones, axes))


def per_device(mesh, fn, n_batched, *args, n_out=1):
    """``fn`` on each device's rows of the first ``n_batched`` arguments
    (the others whole; ``n_out`` batch-major results): the Pallas
    kernels see local shards. One device or data-parallel only."""
    if mesh is None or mesh.size == 1:
        return fn(*args)
    for axis in ('sp', 'tp', 'ep', 'pp'):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f'these decoders run on one device or data-parallel; the '
                f'mesh has {axis}={mesh.shape[axis]}')
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:                                 # older jax
        from jax.experimental.shard_map import shard_map
    data = tuple(a for a in ('dp', 'fsdp') if a in mesh.axis_names)
    specs = tuple(P(data) if i < n_batched else P()
                  for i in range(len(args)))
    out = P(data) if n_out == 1 else (P(data),) * n_out
    return shard_map(fn, mesh=mesh, in_specs=specs, out_specs=out,
                     check_vma=False)(*args)


# ---------------------------------------------------------------- rotary
def rotary(x, theta: float, rotary_dim: int, interleaved: bool = False):
    """Rotary positions on the first ``rotary_dim`` of the head
    dimension of x [B,T,H,D] (the rest passes): halves rotated against
    each other, dimension ``i`` with ``i + rotary_dim / 2``, or,
    ``interleaved``, neighbours, ``2 i`` with ``2 i + 1`` — each pair
    by ``t * theta ** (-2 i / rotary_dim)``, as the published model
    does."""
    t = x.shape[1]
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if interleaved:
        pairs = x[..., :rotary_dim].astype(jnp.float32).reshape(
            x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        turned = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(
            x.shape[:-1] + (rotary_dim,)).astype(x.dtype)
    else:
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:rotary_dim].astype(jnp.float32)
        turned = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rotary_dim:]], -1)


# ----------------------------------------------------------------- remat
def remat_saving(layer, remat: bool, saved, **remat_kwargs):
    """The module class ``layer``; under ``remat`` with the policy that
    holds the values named in ``saved`` and makes the rest again."""
    if not remat:
        return layer
    return nn.remat(
        layer, policy=jax.checkpoint_policies
        .save_only_these_names(*saved), **remat_kwargs)


# ------------------------------------------------------------ the experts
#: rows of the grouped product's tile. 128 wastes least at the groups'
#: edges where an expert sees a few hundred rows (qwen: 320, bound by the
#: weights' bytes whatever the tile). Where an expert's even share fills
#: whole tiles of 512 (lfm2: 2,048), a tile of 128 re-reads a [512, 512]
#: piece of the weights for every 128 rows and is bound by those bytes
#: (105 FLOP a byte against the chip's 240); 512 rows are not (the step
#: alone at even routing, PERF.md section 6, PR 33: 368.1 -> 350.3 ms)
ROW_TILE, WIDE_ROW_TILE = 128, 512


def row_tile(even_share: int, rows: int) -> int:
    """The tile for a buffer of ``rows`` whose experts see
    ``even_share`` rows each when routing is even."""
    wide = even_share >= WIDE_ROW_TILE and rows % WIDE_ROW_TILE == 0
    return WIDE_ROW_TILE if wide else ROW_TILE


#: a scatter-add row's device time over a gather row's, moving rows of
#: d_model between the tokens and the sorted buffer, forward and backward.
#: The scatter form adds ``rows`` buffer rows into the tokens; the gather
#: form reads every one of the ``tokens * top_k`` (token, expert) pairs'
#: rows. On a v5e (``scripts/moe_rows_probe.py``; PERF.md section 6) a
#: scatter-form row takes 0.29-0.31 us and a gather-form row 0.21-0.22
#: at the three sparse cells' shapes: 1.33, 1.36, 1.51. So the
#: gathers where the buffer holds every pair (lfm2: the layer 36.4 ->
#: 33.5 ms), the scatters at two pairs a row (kanana: 30.1 against 37.8)
#: and at four (qwen: 28.0 against 49.0)
SCATTER_ROW_COST = 1.36


def gathers_rows(pairs: int, rows: int) -> bool:
    """Whether ``SparseMoe`` moves rows between the tokens and a sorted
    buffer of ``rows`` by gathers over all ``pairs`` (token, expert)
    pairs (``to_buffer``, ``from_buffer``) rather than by scatter-adds
    over the buffer's rows: whichever costs less, from the shapes."""
    return pairs <= SCATTER_ROW_COST * rows


def grouped_matmul(lhs, rhs, group_sizes, impl: str,
                   tile_rows: int = ROW_TILE):
    """[M,K] x [G,K,N] -> [M,N], rows grouped by ``group_sizes``; rows
    past their sum come back undefined (kept off every live result:
    module docstring).
    ``tile_rows``: rows of the megablox kernel's tile."""
    if impl == 'auto':
        impl = 'gmm' if jax.default_backend() == 'tpu' else 'ragged'
    with jax.named_scope('expert_matmul'):
        if impl == 'ragged':
            return jax.lax.ragged_dot(lhs, rhs, group_sizes)
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        tile = lambda n: min(512, n)  # noqa: E731
        return gmm(lhs, rhs, group_sizes, lhs.dtype,
                   (min(tile_rows, lhs.shape[0]), tile(lhs.shape[1]),
                    tile(rhs.shape[2])), interpret=impl == 'interpret')


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def routing_top_k(probs, k: int):
    """``lax.top_k`` over the experts of probs [N,E], its results named
    ``moe.routing`` and its gradient a scatter to the NAMED indices.
    ``lax.top_k``'s own gradient rule reads its un-named index output,
    for which a save-by-name `remat` sorts a second time; the numbers
    and the gradient are ``lax.top_k``'s."""
    return _routing_top_k_fwd(probs, k)[0]


def _routing_top_k_fwd(probs, k):
    top_w, top_i = (checkpoint_name(x, 'moe.routing')
                    for x in jax.lax.top_k(probs, k))
    # `probs` (held under its own name) gives the gradient its shape
    return (top_w, top_i), (top_i, probs)


def _routing_top_k_bwd(k, residuals, cotangents):
    top_i, probs = residuals
    rows = jnp.arange(top_i.shape[0])[:, None]
    return (jnp.zeros_like(probs).at[rows, top_i].add(cotangents[0]),)


routing_top_k.defvjp(_routing_top_k_fwd, _routing_top_k_bwd)


def buffer_slots(local, sizes, rows: int):
    """The sorted buffer's row of every (token, expert) pair: local
    [pairs] (the held expert, ``held`` where none), sizes [held] -> [pairs],
    ``rows`` where the pair is not held here or did not fit. The stable
    sort puts a pair at its expert's first row plus its rank among that
    expert's pairs: a running count over the one-hot [pairs, held], with
    no scatter and no gather."""
    hit = local[:, None] == jnp.arange(sizes.shape[0])
    rank = jnp.cumsum(hit, 0, dtype=jnp.int32) - 1
    first = jnp.cumsum(sizes) - sizes
    slot = jnp.sum(jnp.where(hit, rank + first, 0), -1)
    return jnp.where(jnp.any(hit, -1) & (slot < rows), slot, rows)


def _slot_rows(a, slots):
    """Rows ``slots`` of a, zeros for a slot past its rows."""
    return jnp.take(a, slots, axis=0, mode='fill', fill_value=0)


@jax.custom_vjp
def to_buffer(flat, token, slots):
    """The sorted buffer: flat [n, m] -> [len(token), m], its row p the
    token ``token[p]``'s (a buffer row past the pairs that landed holds
    some token's row: no path reads it). ``slots`` [n, k]: each (token,
    expert) pair's row of the buffer (``buffer_slots``), for the
    gradient, a gather too: d flat[t] = the sum over j of d buffer at
    ``slots[t, j]``, in float32."""
    return flat[token]


def _to_buffer_fwd(flat, token, slots):
    return flat[token], slots


def _to_buffer_bwd(slots, d_rows):
    d_flat = jnp.sum(_slot_rows(d_rows, slots).astype(jnp.float32), 1)
    return d_flat.astype(d_rows.dtype), None, None


to_buffer.defvjp(_to_buffer_fwd, _to_buffer_bwd)


@jax.custom_vjp
def from_buffer(ys, w, slots, order):
    """Each token's weighted sum of its pairs' rows of the sorted buffer:
    ys [rows, m], w [n, k] -> [n, m] in ys's dtype, out[t] = the sum over
    j of w[t, j] ys[slots[t, j]] in float32 (a slot past ``rows`` adds
    nothing), so ys is read at the rows a slot names and nowhere else.
    The gradient gathers too: d ys[p] = w at the pair ``order[p]`` times
    d out at its token (a row past the pairs that landed gets a number
    no path reads), d w[t, j] = <d out[t], ys[slots[t, j]]>."""
    return _from_buffer_fwd(ys, w, slots, order)[0]


def _from_buffer_fwd(ys, w, slots, order):
    picked = _slot_rows(ys, slots).astype(jnp.float32)
    out = jnp.sum(picked * w[..., None], 1).astype(ys.dtype)
    return out, (ys, w, slots, order)


def _from_buffer_bwd(residuals, d_out):
    ys, w, slots, order = residuals
    weight = w.reshape(-1)[order][:, None]
    d_ys = d_out[order // w.shape[1]].astype(jnp.float32) * weight
    d_w = jnp.einsum('nkm,nm->nk', _slot_rows(ys, slots), d_out,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return d_ys.astype(ys.dtype), d_w, None, None


from_buffer.defvjp(_from_buffer_fwd, _from_buffer_bwd)


def through_gathers(flat, top_w, local, order, sizes, experts, dtype):
    """flat [n, m] through ``experts`` ([rows, m] -> [rows, m], the
    sorted buffer ``order`` lays out) and back, each token's rows
    weighted by top_w [n, k], in ``dtype``: both ways row gathers
    (``to_buffer``, ``from_buffer``), which read every pair's row."""
    k = top_w.shape[1]
    slots = buffer_slots(local, sizes, order.shape[0]).reshape(-1, k)
    ys = experts(to_buffer(flat.astype(dtype), order // k, slots))
    return from_buffer(ys, top_w, slots, order)


def through_scatters(flat, top_w, order, ends, experts, dtype):
    """``through_gathers``' result by scatter-adds of the buffer's rows
    into the tokens, in float32, which read the buffer's rows alone:
    rows past the pairs that landed (``ends[-1]``) are undefined, in
    both passes, so they are masked before anything multiplies them."""
    token = order // top_w.shape[1]
    valid = (jnp.arange(order.shape[0]) < ends[-1])[:, None]
    xs = jnp.where(valid, flat[token], 0).astype(dtype)
    ys = jnp.where(valid, experts(xs).astype(jnp.float32), 0) \
        * top_w.reshape(-1)[order][:, None]
    return jnp.zeros(flat.shape, jnp.float32).at[token].add(ys)


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    """What ``SparseMoe`` is told. The defaults are the plainest router:
    a softmax whose top-k values, renormalised, are the weights."""
    d_model: int
    d_expert: int
    n_experts: int
    top_k: int
    d_shared: int = 0                   # the shared expert; 0: none
    shared_gate: bool = True            # times a sigmoid gate of its own
    norm_topk_prob: bool = True
    router_score: str = 'softmax'       # | 'sigmoid', each on its own
    norm_topk_eps: float = 0.0          # added to the renormalising sum
    routed_scaling_factor: float = 1.0
    # the top-k is taken of ``score + expert_bias`` while the weights
    # stay the unbiased scores. The bias is a leaf of ``params`` that
    # gets no gradient; a rate makes the load rule move it: after every
    # step ``+ rate`` where an expert got fewer of the step's (token,
    # expert) pairs than the mean over all ``n_experts``, ``- rate``
    # where it got more (sown under ``leaf_updates``, train/loop.py)
    expert_bias: bool = False
    expert_bias_update_rate: float = 0.0
    # the share of the experts this program holds; None = all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_buffer_factor: Optional[float] = None
    dtype: str = 'bfloat16'
    moe_impl: str = 'auto'              # 'gmm' | 'interpret' | 'ragged'

    @classmethod
    def of(cls, cfg):
        """From a model's config: the fields it has under these names."""
        return cls(**{f.name: getattr(cfg, f.name)
                      for f in dataclasses.fields(cls)
                      if hasattr(cfg, f.name)})

    @property
    def held(self):
        return self.n_experts if self.experts_held is None \
            else int(self.experts_held)


def buffer_rows(cfg: MoeConfig, tokens: int) -> int:
    """Rows of the sorted (token, expert) buffer: the worst case, or
    ``moe_buffer_factor`` times the even share; whole row tiles of the
    grouped product."""
    rows = tokens * min(cfg.top_k, cfg.held)
    if cfg.moe_buffer_factor is not None:
        even = tokens * cfg.top_k * cfg.held / cfg.n_experts
        rows = min(rows, int(cfg.moe_buffer_factor * even))
    tile = 128 if tokens >= 128 else 8
    return max(1, -(-rows // tile)) * tile


class SparseMoe(nn.Module):
    """Top-k routed experts on a share of the experts, with a shared
    expert where ``cfg.d_shared`` says so, gated or not (module
    docstring; the router's forms are ``MoeConfig``'s fields)."""
    cfg: MoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        f32 = jnp.float32
        held, m, f = cfg.held, cfg.d_model, cfg.d_expert
        if not 0 <= cfg.expert_offset <= cfg.n_experts - held:
            raise ValueError(
                f'experts [{cfg.expert_offset}, {cfg.expert_offset + held})'
                f' are not among {cfg.n_experts}')
        router = self.param(
            'router', nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', None)),
            (m, cfg.n_experts), f32)

        def experts(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes), shape, jnp.float32)

        wi_gate = experts('wi_gate', (held, m, f),
                          ('expert', 'embed', 'mlp'))
        wi_up = experts('wi_up', (held, m, f), ('expert', 'embed', 'mlp'))
        wo = experts('wo', (held, f, m), ('expert', 'mlp', 'embed'))

        score = {'softmax': lambda z: jax.nn.softmax(z, -1),
                 'sigmoid': jax.nn.sigmoid}[cfg.router_score]
        extra = ()
        if cfg.expert_bias:
            extra = (self.param(
                'expert_bias', nn.with_logical_partitioning(
                    nn.initializers.zeros, (None,)),
                (cfg.n_experts,), f32),)
        moves = bool(extra) and cfg.expert_bias_update_rate > 0

        def routed(x, router, wi_gate, wi_up, wo, *bias):
            b, t, _ = x.shape
            n = b * t
            flat = x.reshape(n, m)
            # the router over ALL experts, in float32
            probs = checkpoint_name(score(jnp.dot(
                flat.astype(f32), router,
                precision=jax.lax.Precision.HIGHEST)), 'moe.probs')
            if bias:
                # chosen by score + bias, weighted by the score: the
                # top values less the bias at their indices, read from
                # its `n_experts` numbers by a comparison (no gather
                # over the [tokens, experts] scores, PERF.md PR 31)
                held_bias = jax.lax.stop_gradient(bias[0])
                top_w, top_i = routing_top_k(probs + held_bias, cfg.top_k)
                hit = top_i[..., None] == jnp.arange(cfg.n_experts)
                top_w = top_w - jnp.sum(jnp.where(hit, held_bias, 0.0), -1)
            else:
                top_w, top_i = routing_top_k(probs, cfg.top_k)
            if cfg.norm_topk_prob:
                total = jnp.sum(top_w, -1, keepdims=True)
                if cfg.norm_topk_eps:
                    total = total + cfg.norm_topk_eps
                top_w = top_w / total
            if cfg.routed_scaling_factor != 1.0:
                top_w = top_w * cfg.routed_scaling_factor
            # pairs that land on a held expert, sorted by expert; the
            # others sort behind them under the id `held`
            local = top_i - cfg.expert_offset
            local = jnp.where((local >= 0) & (local < held), local,
                              held).reshape(-1)
            rows = buffer_rows(cfg, n)
            tile = row_tile(n * cfg.top_k // cfg.n_experts, rows)
            order = checkpoint_name(
                jnp.argsort(local, stable=True)[:rows], 'moe.routing')
            sizes = checkpoint_name(
                jnp.bincount(local, length=held + 1)[:held], 'moe.routing')
            landed = jnp.sum(sizes)
            # groups cut to the buffer (nothing is cut at the default)
            ends = jnp.minimum(jnp.cumsum(sizes), rows)
            fitted = jnp.diff(ends, prepend=0).astype(jnp.int32)
            gm = lambda a, w: grouped_matmul(  # noqa: E731
                a, w.astype(dtype), fitted, cfg.moe_impl, tile)
            # named for a model whose `remat` policy has the room to
            # hold them (models/lfm2_moe.py); a name nobody holds is an
            # identity that lowers to nothing
            named = lambda a, w, name: checkpoint_name(  # noqa: E731
                gm(a, w), name)

            def experts(xs):
                hidden = nn.silu(named(xs, wi_gate, 'moe.hidden')) \
                    * named(xs, wi_up, 'moe.hidden')
                return named(hidden, wo, 'moe.out')

            if gathers_rows(n * cfg.top_k, rows):
                out = through_gathers(flat, top_w, local, order, sizes,
                                      experts, dtype)
            else:
                out = through_scatters(flat, top_w, order, ends, experts,
                                       dtype)
            mean = jnp.maximum(landed / held, 1e-9)
            counters = jnp.stack([
                landed / (n * cfg.top_k), jnp.max(sizes) / mean,
                (landed - ends[-1]).astype(f32)]).astype(f32)
            out = out.astype(dtype).reshape(b, t, m)
            if moves:
                # the pairs each of ALL the experts got, held or not
                return out, counters[None], jnp.sum(hit, (0, 1))[None]
            return out, counters[None]

        y, counters, *load = per_device(
            self.mesh, routed, 1, x, router, wi_gate, wi_up, wo, *extra,
            n_out=2 + moves)
        if moves:
            load = jnp.sum(load[0], 0).astype(f32)      # over the devices
            self.sow('leaf_updates', 'expert_bias',
                     cfg.expert_bias_update_rate
                     * jnp.sign(jnp.mean(load) - load))
        for i, name in enumerate(('moe.local_assign_share',
                                  'moe.load_max_over_mean',
                                  'moe.dropped')):
            self.sow('intermediates', name, jnp.mean(counters[:, i]))

        if cfg.d_shared:
            shared_cfg = TransformerConfig(
                d_model=m, d_ff=cfg.d_shared, dtype=cfg.dtype)
            shared = MlpBlock(shared_cfg, name='shared')(x)
            if cfg.shared_gate:
                gate = dense(1, ('embed', None), dtype, 'shared_gate')(x)
                shared = jax.nn.sigmoid(gate) * shared
            y = y + shared
        return nn.with_logical_constraint(y, ('batch', 'seq', 'embed'))


__all__ = ['MoeConfig', 'SparseMoe', 'grouped_matmul', 'routing_top_k',
           'gathers_rows', 'buffer_slots', 'to_buffer', 'from_buffer',
           'through_gathers', 'through_scatters',
           'buffer_rows', 'rms_norm', 'per_device', 'rotary', 'dense',
           'remat_saving']
