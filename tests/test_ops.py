"""Pallas ops (VERDICT round-1 item 9): flash attention kernel
correctness vs the dense reference, gradients, fallback selection, and
transformer integration. Runs under the Pallas interpreter on the CPU
test mesh; the real-chip speed comparison lives in the kernel module's
docstring + bench history."""

import numpy as np
import pytest

from mlcomp_tpu.ops import (
    fused_attention, reference_attention,
)


def _qkv(b=2, t=256, h=4, d=64, seed=0, dtype='float32'):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(b, t, h, d).astype(np.float32), jnp.dtype(dtype))
    return mk(), mk(), mk()


class TestKernelNumerics:
    @pytest.mark.parametrize('causal', [True, False])
    def test_forward_matches_reference(self, causal):
        import jax.numpy as jnp
        q, k, v = _qkv()
        ref = reference_attention(q, k, v, causal=causal)
        out = fused_attention(q, k, v, causal=causal, impl='interpret')
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_multi_block_seq(self):
        import jax.numpy as jnp
        # t=1024 > block 512 -> real multi-block accumulation
        q, k, v = _qkv(b=1, t=1024, h=2, d=64)
        ref = reference_attention(q, k, v, causal=True)
        out = fused_attention(q, k, v, causal=True, impl='interpret')
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_gradients_match_reference(self):
        import jax
        import jax.numpy as jnp
        q, k, v = _qkv(t=128)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        g_fa = jax.grad(loss(lambda q, k, v: fused_attention(
            q, k, v, impl='interpret')), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(reference_attention),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4

    def test_scale_override(self):
        import jax.numpy as jnp
        q, k, v = _qkv(t=128)
        ref = reference_attention(q, k, v, causal=True, scale=0.25)
        out = fused_attention(q, k, v, causal=True, scale=0.25,
                              impl='interpret')
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


class TestSelection:
    def test_auto_on_cpu_is_dense(self):
        import jax.numpy as jnp
        q, k, v = _qkv(t=128)
        out = fused_attention(q, k, v, impl='auto')  # cpu backend
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_untileable_seq_falls_back(self):
        q, k, v = _qkv(t=100)
        out = fused_attention(q, k, v, impl='auto')
        assert out.shape == q.shape
        with pytest.raises(ValueError, match='divisible'):
            fused_attention(q, k, v, impl='interpret')


class TestTransformerIntegration:
    def test_attn_impl_interpret_runs_kernel_in_model(self):
        import jax
        from mlcomp_tpu.models import create_model
        model_d = create_model(
            'transformer_lm', vocab_size=128, d_model=64, n_layers=1,
            n_heads=2, d_ff=128, max_seq_len=128, dtype='float32',
            attn_impl='dense')
        model_p = create_model(
            'transformer_lm', vocab_size=128, d_model=64, n_layers=1,
            n_heads=2, d_ff=128, max_seq_len=128, dtype='float32',
            attn_impl='interpret')
        tokens = np.random.RandomState(0).randint(
            0, 128, (2, 128)).astype(np.int32)
        var = model_d.init(jax.random.PRNGKey(0), tokens)
        out_d = np.asarray(model_d.apply(var, tokens))
        out_p = np.asarray(model_p.apply(var, tokens))
        np.testing.assert_allclose(out_p, out_d, atol=2e-4)

    def test_sharded_kernel_on_mesh(self):
        """dp-sharded batch through the shard_mapped kernel path."""
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.parallel import mesh_from_spec
        from mlcomp_tpu.parallel.ring import make_ring_attention
        mesh = mesh_from_spec({'dp': 4, 'tp': 2})
        q, k, v = _qkv(b=4, t=128, h=4, d=64)
        attend = make_ring_attention(mesh, causal=True,
                                     attn_impl='interpret')
        with mesh:
            out = jax.jit(attend)(q, k, v)
        ref = reference_attention(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


class TestBlockwiseBackward:
    def test_blockwise_matches_reference(self):
        import jax.numpy as jnp
        from mlcomp_tpu.ops.flash_attention import blockwise_attention
        q, k, v = _qkv(t=256)
        for causal in (True, False):
            ref = reference_attention(q, k, v, causal=causal)
            blk = blockwise_attention(q, k, v, causal=causal,
                                      block_k=128)
            assert float(jnp.max(jnp.abs(blk - ref))) < 2e-5, causal

    def test_gradients_through_custom_vjp(self):
        """The custom vjp (fused Pallas backward) produces the dense
        gradients exactly."""
        import jax
        import jax.numpy as jnp
        q, k, v = _qkv(t=256)
        g_fa = jax.grad(
            lambda q, k, v: (fused_attention(
                q, k, v, impl='interpret') ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: (reference_attention(q, k, v) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4


class TestFusedBackward:
    def test_lse_matches_dense_logsumexp(self):
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.flash_attention import (
            flash_attention_forward,
        )
        q, k, v = _qkv(t=256)
        out, lse = flash_attention_forward(q, k, v, causal=True,
                                           interpret=True,
                                           with_lse=True)
        d = q.shape[-1]
        s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * (d ** -0.5)
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        ref = jax.scipy.special.logsumexp(s, axis=-1)
        assert float(jnp.max(jnp.abs(lse - ref))) < 1e-4

    def test_backward_kernel_matches_reference(self):
        """flash_attention_backward's dq/dk/dv == autodiff of dense."""
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.flash_attention import (
            flash_attention_backward, flash_attention_forward,
        )
        q, k, v = _qkv(t=256)
        out, lse = flash_attention_forward(q, k, v, causal=True,
                                           interpret=True,
                                           with_lse=True)
        do = jnp.ones_like(out) * 0.1
        dq, dk, dv = flash_attention_backward(
            q, k, v, out, lse, do, causal=True, interpret=True)
        _, vjp = jax.vjp(lambda q_, k_, v_: reference_attention(
            q_, k_, v_, causal=True), q, k, v)
        rq, rk, rv = vjp(do)
        for a, b in ((dq, rq), (dk, rk), (dv, rv)):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4


# -- PR 34: one backward kernel, diagonal tiles walked by strips
def _heads(h, h_kv, t, d, seed=3):
    """(q, k, v, do) with ``h`` query heads over ``h_kv`` key-value
    heads of ``d``."""
    import jax
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, t, h, d)),
            jax.random.normal(ks[1], (1, t, h_kv, d)),
            jax.random.normal(ks[2], (1, t, h_kv, d)),
            jax.random.normal(ks[3], (1, t, h, d)))


def _rel(a, b):
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


HEADS = [(2, 2, 128), (4, 1, 64), (8, 1, 256)]
HEAD_IDS = ['equal_d128', '4to1_d64', '8to1_d256']
# PR 36: q, k, v enter as rows [B*H, T, D] or as panels [B*H, D, T];
# the head sizes pick one (``_panels_for``), and the flash tests below
# run the other too at every size, by turning the pick round
LAYOUTS = ['by_size', 'flipped']


def _force(monkeypatch, layout):
    """Make the flash op take, ``flipped``, the orientation its head
    sizes do not pick."""
    from mlcomp_tpu.ops import flash_attention as fa
    if layout == 'flipped':
        picks = fa._panels_for
        monkeypatch.setattr(fa, '_panels_for',
                            lambda d, dv: not picks(d, dv))


def _logsumexp(q, k, causal):
    """The dense logsumexp a query row [B, H, T] (scale 1/sqrt(D),
    grouped heads repeated) that the forward's lse has to be."""
    import jax
    import jax.numpy as jnp
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * q.shape[-1] ** -0.5
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jax.scipy.special.logsumexp(s, axis=-1)
# (tokens, tile): one tile of four strips; two tiles of two strips (a
# whole tile under the diagonal, then the diagonal's own); four tiles of
# one strip each; T = tile with a single strip
WALKS = [(512, 512), (512, 256), (512, 128), (128, 128)]


@pytest.mark.parametrize('layout', LAYOUTS)
@pytest.mark.parametrize('t,block', WALKS,
                         ids=[f't{t}_tile{b}' for t, b in WALKS])
@pytest.mark.parametrize('causal', [True, False],
                         ids=['causal', 'full'])
@pytest.mark.parametrize('h,h_kv,d', HEADS, ids=HEAD_IDS)
def test_fused_flash_backward_against_dense(monkeypatch, h, h_kv, d,
                                            causal, t, block, layout):
    """dq, dk, dv of the ONE backward kernel against autodiff of the
    dense reference, the forward with and without ``with_lse``, and
    the lse against the dense logsumexp, with q, k, v as rows and as
    panels."""
    import functools

    import jax
    from mlcomp_tpu.ops import flash_attention as fa
    _force(monkeypatch, layout)
    if causal:      # the backward's walk, from its own rule
        strips = fa._diagonal_strips(block, fa._strips(5, d, d))
        assert len(strips) == {512: 4, 256: 2, 128: 1}[block]
    q, k, v, do = _heads(h, h_kv, t, d)
    want, pull = jax.vjp(functools.partial(
        reference_attention, causal=causal), q, k, v)
    kw = dict(causal=causal, block_q=block, block_k=block, interpret=True)
    plain = fa.flash_attention_forward(q, k, v, **kw)
    out, lse = fa.flash_attention_forward(q, k, v, with_lse=True, **kw)
    assert _rel(plain, want) < 1e-5 and _rel(out, want) < 1e-5
    assert _rel(lse, _logsumexp(q, k, causal)) < 1e-6
    grads = fa.flash_attention_backward(q, k, v, out, lse, do, **kw)
    for name, a, b in zip('dq dk dv'.split(), grads, pull(do)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize('layout', LAYOUTS)
@pytest.mark.parametrize('causal', [True, False], ids=['causal', 'full'])
@pytest.mark.parametrize('spans', [2, 4])
def test_fused_flash_backward_in_spans(monkeypatch, causal, spans, layout):
    """A key-value head too long to stay in VMEM whole goes through the
    same kernel in spans, dq summed from one float32 partial a span:
    the budget is made small, the COMPUTED bytes choose."""
    import functools

    import jax
    from mlcomp_tpu.ops import flash_attention as fa
    _force(monkeypatch, layout)
    t, d, block = 512, 64, 128
    monkeypatch.setattr(fa, 'RESIDENT_BYTES',
                        fa._resident_bytes(t // spans, d, d, 4))
    assert fa._span(t, block, d, d, 4) == t // spans
    q, k, v, do = _heads(4, 2, t, d)
    _, pull = jax.vjp(functools.partial(
        reference_attention, causal=causal), q, k, v)
    kw = dict(causal=causal, block_q=block, block_k=block, interpret=True)
    out, lse = fa.flash_attention_forward(q, k, v, with_lse=True, **kw)
    grads = fa.flash_attention_backward(q, k, v, out, lse, do, **kw)
    for name, a, b in zip('dq dk dv'.split(), grads, pull(do)):
        assert a.dtype == b.dtype and _rel(a, b) < 1e-5, name


@pytest.mark.parametrize('t,d,most', [
    (2048, 128, 1.13),      # olmo-1b: two of three tiles diagonal
    (8192, 64, 1.04),       # lfm2-8b-a1b
    (8192, 256, 1.04),      # qwen3-next-80b-a3b
])
def test_pairs_the_walk_executes(t, d, most):
    """What the backward kernel multiplies (5 of a trained sequence's 7
    products) against what attention requires, from the helpers the
    kernels and the index map themselves walk by; the forward walks
    its diagonal tiles whole where the chip said strips lose (PR 34)."""
    from mlcomp_tpu.ops import flash_attention as fa
    block, same = fa._blocks(t, 1024, 1024, True)
    assert block == same == 1024
    need = t * (t + 1) // 2
    n = t // block
    whole = n * (n + 1) // 2 * block * block    # every live tile whole
    assert fa._strips(5, d, d) == 4
    pairs = fa.executed_pairs(t, block, fa._strips(5, d, d), True)
    assert need <= pairs <= most * need and pairs < whole
    forward = fa.executed_pairs(t, block, fa._strips(2, d, d), True)
    assert fa._strips(2, d, d) == (2 if d > 128 else 1)
    assert pairs <= forward <= whole and (forward < whole) == (d > 128)
    # the strips cover a diagonal tile's lower triangle exactly once
    for strips in (1, 2, 4, 8):
        seen = np.zeros((block, block), int)
        for r0, rows, cols in fa._diagonal_strips(block, strips):
            seen[r0:r0 + rows, :cols] += 1
        assert (seen[np.tril_indices(block)] == 1).all()
        assert seen.max() == 1
    assert fa.executed_pairs(t, block, 4, False) == t * t
    # the index map names a dead step's tile after the diagonal's own
    ix = fa._causal_kv_ix(True)
    assert [int(ix(0, 1, ik)[1]) for ik in range(4)] == [0, 1, 1, 1]


def test_causal_tiles_are_square():
    from mlcomp_tpu.ops import flash_attention as fa
    assert fa._blocks(8192, 1024, 512, True) == (512, 512)
    assert fa._blocks(8192, 1024, 512, False) == (1024, 512)
    assert fa._blocks(384, 1024, 1024, True) == (384, 384)
    # strips are whole 128s that divide the tile
    assert [len(fa._diagonal_strips(b, 4))
            for b in (128, 256, 384, 512, 640, 1024)] == [1, 2, 3, 4, 5, 4]
    assert fa._diagonal_strips(1024, 2) == [(0, 512, 512), (512, 512, 1024)]
    assert fa._diagonal_strips(1024, 1) == [(0, 1024, 1024)]


# -- PR 35: a score head that is not the value head (latent attention)
def _unequal_heads(h, h_kv, t, d, dv, seed=5):
    import jax
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, t, h, d)),
            jax.random.normal(ks[1], (1, t, h_kv, d)),
            jax.random.normal(ks[2], (1, t, h_kv, dv)),
            jax.random.normal(ks[3], (1, t, h, dv)))


UNEQUAL = [(2, 2, 192, 128), (2, 1, 24, 8), (2, 2, 64, 128)]
UNEQUAL_IDS = ['mla_192_128', 'tiny_24_8_grouped', 'value_wider_64_128']


@pytest.mark.parametrize('layout', LAYOUTS)
@pytest.mark.parametrize('t,block', [(512, 256), (256, 128), (128, 128)],
                         ids=['t512_tile256', 't256_tile128', 't128'])
@pytest.mark.parametrize('causal', [True, False], ids=['causal', 'full'])
@pytest.mark.parametrize('h,h_kv,d,dv', UNEQUAL, ids=UNEQUAL_IDS)
def test_flash_with_unequal_head_sizes_against_dense(
        monkeypatch, h, h_kv, d, dv, causal, t, block, layout):
    """q, k [.., d] and v, o, do [.., dv]: the forward, its lse, dq and
    dk at the score head's width and dv at the value head's, against
    autodiff of the dense form; the scale is the score head's."""
    import functools

    import jax
    from mlcomp_tpu.ops import flash_attention as fa
    _force(monkeypatch, layout)
    q, k, v, do = _unequal_heads(h, h_kv, t, d, dv)
    want, pull = jax.vjp(functools.partial(
        reference_attention, causal=causal), q, k, v)
    assert want.shape == (1, t, h, dv)
    kw = dict(causal=causal, block_q=block, block_k=block, interpret=True)
    out, lse = fa.flash_attention_forward(q, k, v, with_lse=True, **kw)
    assert out.shape == want.shape and _rel(out, want) < 1e-5
    assert _rel(lse, _logsumexp(q, k, causal)) < 1e-6
    grads = fa.flash_attention_backward(q, k, v, out, lse, do, **kw)
    for name, a, b, like in zip('dq dk dv'.split(), grads, pull(do),
                                (q, k, v)):
        assert a.shape == b.shape == like.shape and a.dtype == b.dtype
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize('layout', LAYOUTS)
@pytest.mark.parametrize('spans', [1, 2])
def test_fused_attention_with_unequal_heads_in_forced_spans(monkeypatch,
                                                            spans, layout):
    """``fused_attention(impl='interpret')`` at 192 / 128 under
    ``jax.grad``, the backward's keys in one span and forced into two:
    all three gradients against the dense form's, and the pure-jnp
    twin (``blockwise_attention``) beside them."""
    import jax
    import jax.numpy as jnp
    from mlcomp_tpu.ops import flash_attention as fa
    _force(monkeypatch, layout)
    t, d, dv = 2048, 192, 128
    monkeypatch.setattr(fa, 'RESIDENT_BYTES',
                        fa._resident_bytes(t // spans, d, dv, 4))
    assert fa._span(t, 1024, d, dv, 4) == t // spans
    q, k, v, do = _unequal_heads(1, 1, t, d, dv)

    def loss(impl):
        return lambda q, k, v: jnp.sum(do * fused_attention(
            q, k, v, causal=True, impl=impl))

    got = jax.grad(loss('interpret'), (0, 1, 2))(q, k, v)
    want = jax.grad(loss('dense'), (0, 1, 2))(q, k, v)
    for name, a, b in zip('dq dk dv'.split(), got, want):
        assert a.shape == b.shape and _rel(a, b) < 1e-5, name
    assert _rel(fa.blockwise_attention(q, k, v),
                reference_attention(q, k, v)) < 1e-5


def test_what_the_kernels_do_beyond_the_required_pairs_at_192_128():
    """At 2 x 8,192 tokens with heads of 192 / 128 the walk is the equal
    heads' but for the forward's diagonal tile, which runs in two
    strips from 3 MXU passes a pair on (equal heads take 2 or 4; the
    backward's runs in four), and the lanes of the score head pad to
    256 in VMEM; V is 128 wide everywhere."""
    from mlcomp_tpu.ops import flash_attention as fa
    t, d, dv = 8192, 192, 128
    assert (fa._lanes(d), fa._lanes(dv)) == (256, 128)
    assert fa._strips(2, d, dv) == 2 and fa._strips(5, d, dv) == 4
    # the equal heads' rule is what it was
    assert [fa._strips(2, e, e) for e in (64, 128, 256)] == [1, 1, 2]
    assert [fa._strips(5, e, e) for e in (64, 128, 256)] == [4, 4, 4]
    need = t * (t + 1) // 2
    assert fa.executed_pairs(t, 1024, 2, True) / need \
        == pytest.approx(1.0625, abs=1e-3)
    assert fa.executed_pairs(t, 1024, 4, True) / need < 1.04
    # a whole head's K, dK (256 lanes) and V, dV (128) stay resident
    assert fa._resident_bytes(t, d, dv, 2) == t * 384 * 12
    assert fa._span(t, 1024, d, dv, 2) == t
    assert fa._resident_bytes(t, 128, 128, 2) == t * 128 * 24


# the gauge ``step.flash_layout_copies`` (``JaxTrain._introspect``)
_COMPILED = """\
HloModule jit_step, entry_computation_layout={()->()}

%fused_computation.3 (param_0.1: bf16[2,8,4]) -> bf16[2,4,8] {
  %param_0.1 = bf16[2,8,4]{2,1,0} parameter(0)
  ROOT %copy.9 = bf16[2,4,8]{2,1,0} copy(%param_0.1), metadata={op_name="jit(step)/attn/mla_attn/flash_layout/transpose"}
}

%bitcast_fusion.1 (bitcast_input.1: bf16[2,8,4]) -> bf16[2,4,8] {
  %bitcast_input.1 = bf16[2,8,4]{2,1,0} parameter(0)
  ROOT %copy.8 = bf16[2,4,8]{1,2,0} copy(%bitcast_input.1), metadata={op_name="jit(step)/attn/mla_attn/flash_layout/reshape"}
}

ENTRY %main.5 (p: bf16[2,8,4]) -> bf16[2,4,8] {
  %p = bf16[2,8,4]{2,1,0} parameter(0)
  %copy.1 = bf16[2,8,4]{1,2,0} copy(%p), metadata={op_name="jit(step)/attn/mla_attn/flash_layout/transpose"}
  %copy.2 = bf16[2,8,4]{0,2,1} copy(%copy.1), metadata={op_name="jit(step)/transpose(jvp())/attn/mla_attn/flash_layout/reshape;jit(step)/attn/squeeze"}
  %copy.3 = bf16[2,8,4]{1,2,0} copy(%p), metadata={op_name="jit(step)/attn/kv_b_proj/convert_element_type"}
  %fusion.4 = bf16[2,4,8]{2,1,0} fusion(%copy.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/attn/mla_attn/flash_layout/transpose"}
  %copy_bitcast_fusion = bf16[2,4,8]{2,1,0} fusion(%copy.2), kind=kLoop, calls=%bitcast_fusion.1, metadata={op_name="jit(step)/attn/mla_attn/flash_layout/copy"}
  ROOT %copy.4 = bf16[2,4,8]{1,2,0} copy(%fusion.4), metadata={op_name="jit(step)/flash_layout_other/transpose"}
}
"""


@pytest.mark.parametrize('text,copies', [
    (_COMPILED, 2),
    ('', 0),
    # a step with no flash op (ResNet's) or one whose layout is free
    (_COMPILED.replace('/flash_layout/', '/mixer/'), 0),
], ids=['two_of_six', 'empty', 'no_flash_op'])
def test_layout_copies_counts_the_flash_ops_top_level_copies(text, copies):
    """Only copy instructions at a computation's top level whose op_name
    runs through the op's layout scope: not a fused computation's copy
    (it moves no bytes of its own), not a fusion that merely carries
    the name, not another scope's copy, not a scope named alike."""
    from mlcomp_tpu.ops.flash_attention import LAYOUT_SCOPE, layout_copies
    assert LAYOUT_SCOPE == 'flash_layout'
    assert layout_copies(text) == copies


class TestFusedCE:
    """Blocked CE kernel (ops/fused_ce.py) vs the exact reference —
    run in interpret mode (auto resolves to dense on TPU; see the
    module docstring's measured numbers)."""

    def _case(self, n=64, v=512, dtype='float32'):
        import jax.numpy as jnp
        import numpy as np
        rng = np.random.RandomState(7)
        logits = jnp.asarray(rng.randn(n, v) * 3, jnp.dtype(dtype))
        labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        return logits, labels

    def test_forward_matches_reference(self):
        import jax.numpy as jnp
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import (
            reference_ce, softmax_ce_per_example,
        )
        logits, labels = self._case()
        got = softmax_ce_per_example(logits, labels, block_n=16,
                                     block_v=128, impl='pallas',
                                     interpret=True)
        want = reference_ce(logits, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert got.dtype == jnp.float32

    def test_gradients_match_reference(self):
        import jax
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import (
            reference_ce, softmax_ce_per_example,
        )
        logits, labels = self._case()
        gw = jax.grad(lambda l: reference_ce(l, labels).mean())(logits)
        gg = jax.grad(lambda l: softmax_ce_per_example(
            l, labels, block_n=16, block_v=128, impl='pallas',
            interpret=True).mean())(logits)
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                                   atol=1e-5, rtol=1e-4)

    def test_bf16_grads_stay_bf16(self):
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.fused_ce import softmax_ce_per_example
        logits, labels = self._case(dtype='bfloat16')
        g = jax.grad(lambda l: softmax_ce_per_example(
            l, labels, block_n=16, block_v=128, impl='pallas',
            interpret=True).mean())(logits)
        assert g.dtype == jnp.bfloat16

    def test_auto_is_dense_and_untileable_falls_back(self):
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import (
            reference_ce, softmax_ce_per_example,
        )
        import pytest as _pytest
        logits, labels = self._case(n=10, v=100)  # tiles neither dim
        got = softmax_ce_per_example(logits, labels)
        want = reference_ce(logits, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        with _pytest.raises(ValueError):
            softmax_ce_per_example(logits, labels, impl='pallas')

    def test_out_of_range_labels_clamp_on_both_paths(self):
        """Labels outside [0, V) are clamped identically on the dense
        and pallas paths (unclamped, take_along_axis wraps negatives
        and NaN-fills >= V while the kernel contributes 0)."""
        import jax.numpy as jnp
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import softmax_ce_per_example
        logits, _ = self._case(n=16, v=128)
        labels = jnp.asarray([-100, -1, 128, 500] * 4, jnp.int32)
        dense = softmax_ce_per_example(logits, labels, impl='dense')
        pallas = softmax_ce_per_example(logits, labels, block_n=8,
                                        block_v=128, impl='pallas',
                                        interpret=True)
        assert np.isfinite(np.asarray(dense)).all()
        np.testing.assert_allclose(np.asarray(pallas),
                                   np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)


class TestInt8Matmul:
    """Weight-only int8 serving matmul (ops/int8_matmul.py) — kernel in
    interpret mode vs the dequantize-then-dot oracle."""

    def _case(self, m=32, k=256, n=384):
        import jax.numpy as jnp
        import numpy as np
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        w = jnp.asarray(rng.randn(k, n) * 0.05, jnp.float32)
        return x, w

    def test_quantization_roundtrip_error_bounded(self):
        import jax.numpy as jnp
        import numpy as np
        from mlcomp_tpu.ops.int8_matmul import quantize_int8
        _, w = self._case()
        w_qt, scale = quantize_int8(w)
        assert w_qt.dtype == jnp.int8 and scale.shape == (384,)
        assert w_qt.shape == (384, 256)          # transposed layout
        deq = (np.asarray(w_qt, np.float32)
               * np.asarray(scale)[:, None]).T
        err = np.abs(deq - np.asarray(w))
        # symmetric absmax/127: error bounded by scale/2 per channel
        assert (err <= np.asarray(scale)[None, :] / 2 + 1e-7).all()

    def test_kernel_matches_dequant_reference(self):
        import numpy as np
        from mlcomp_tpu.ops.int8_matmul import (
            int8_matmul, quantize_int8, reference_int8_matmul,
        )
        x, w = self._case()
        w_qt, scale = quantize_int8(w)
        got = int8_matmul(x, w_qt, scale, impl='pallas',
                          block_n=128, block_k=128, interpret=True)
        want = reference_int8_matmul(x, w_qt, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_matmul_close_to_exact(self):
        import jax.numpy as jnp
        import numpy as np
        from mlcomp_tpu.ops.int8_matmul import (
            int8_matmul, quantize_int8,
        )
        x, w = self._case()
        w_q, scale = quantize_int8(w)
        got = int8_matmul(x, w_q, scale, impl='dense')
        exact = np.asarray(jnp.dot(x, w))
        rel = np.abs(np.asarray(got) - exact).max() / np.abs(exact).max()
        assert rel < 0.02, rel

    def test_auto_dispatch_and_untileable(self):
        import pytest as _pytest
        from mlcomp_tpu.ops.int8_matmul import (
            int8_matmul, quantize_int8,
        )
        x, w = self._case(m=10, k=100, n=99)    # tiles nothing
        w_q, scale = quantize_int8(w)
        int8_matmul(x, w_q, scale)    # auto -> dense (measured faster)
        with _pytest.raises(ValueError, match='tile'):
            int8_matmul(x, w_q, scale, impl='pallas')
        with _pytest.raises(ValueError, match='shape mismatch'):
            int8_matmul(x, w_q, scale[:-1])


class TestBf16KernelPath:
    """The MXU dots take bf16 operands when inputs are bf16 (for f32
    inputs every cast in the kernel is a no-op, so the f32 suites above
    cannot catch bf16-path regressions like dropping the f32
    accumulation)."""

    def test_bf16_forward_close_to_f32_reference(self):
        import jax.numpy as jnp
        q, k, v = _qkv(t=256, dtype='bfloat16')
        out = fused_attention(q, k, v, causal=True, impl='interpret')
        assert out.dtype == jnp.bfloat16
        ref = reference_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True)
        # bf16 rounding of p + output cast: ~8-bit mantissa tolerance
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        assert err < 2e-2, err

    def test_bf16_gradients_close_to_f32_reference(self):
        import jax
        import jax.numpy as jnp
        q, k, v = _qkv(t=256, dtype='bfloat16')
        g16 = jax.grad(
            lambda q, k, v: (fused_attention(
                q, k, v, impl='interpret').astype(jnp.float32) ** 2)
            .sum(), argnums=(0, 1, 2))(q, k, v)
        g32 = jax.grad(
            lambda q, k, v: (reference_attention(q, k, v) ** 2).sum(),
            argnums=(0, 1, 2))(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32))
        for a, b in zip(g16, g32):
            assert a.dtype == jnp.bfloat16
            rel = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                        / (jnp.max(jnp.abs(b)) + 1e-9))
            assert rel < 5e-2, rel


class TestFusedCeZLossSmoothing:
    """z-loss + label smoothing fused into the CE kernel (round-3
    VERDICT next #5): exact vs the XLA reference in interpret mode,
    forward and gradients, separately and combined."""

    def _case(self, n=32, v=256):
        import jax.numpy as jnp
        import numpy as np
        rng = np.random.RandomState(3)
        logits = jnp.asarray(rng.randn(n, v) * 3, jnp.float32)
        labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        return logits, labels

    @pytest.mark.parametrize('z,eps', [(1e-4, 0.0), (0.0, 0.1),
                                       (1e-4, 0.1)])
    def test_forward_and_grad_match_reference(self, z, eps):
        import jax
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import (
            reference_ce, softmax_ce_per_example,
        )
        logits, labels = self._case()
        got = softmax_ce_per_example(
            logits, labels, block_n=8, block_v=128, impl='pallas',
            interpret=True, z_loss=z, label_smoothing=eps)
        want = reference_ce(logits, labels, z_loss=z,
                            label_smoothing=eps)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        gw = jax.grad(lambda l: reference_ce(
            l, labels, z_loss=z, label_smoothing=eps).mean())(logits)
        gg = jax.grad(lambda l: softmax_ce_per_example(
            l, labels, block_n=8, block_v=128, impl='pallas',
            interpret=True, z_loss=z,
            label_smoothing=eps).mean())(logits)
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                                   atol=1e-5, rtol=1e-4)

    def test_zero_coefs_reduce_to_plain_ce(self):
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import (
            reference_ce, softmax_ce_per_example,
        )
        logits, labels = self._case()
        got = softmax_ce_per_example(
            logits, labels, block_n=8, block_v=128, impl='pallas',
            interpret=True, z_loss=0.0, label_smoothing=0.0)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(reference_ce(logits, labels)),
            atol=1e-5, rtol=1e-5)

    def test_auto_on_cpu_stays_dense_with_coefs(self):
        """auto never routes to an uninterpreted pallas_call off-TPU."""
        import numpy as np
        from mlcomp_tpu.ops.fused_ce import (
            reference_ce, softmax_ce_per_example,
        )
        logits, labels = self._case()
        got = softmax_ce_per_example(logits, labels, z_loss=1e-4,
                                     label_smoothing=0.1)
        want = reference_ce(logits, labels, z_loss=1e-4,
                            label_smoothing=0.1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


class TestServingStack:
    """Fused serving megakernel (ops/serving_stack.py): one program
    runs the whole small-batch layer stack, activation resident in
    VMEM. Exactness vs the pure-jnp chain, both weight dtypes."""

    def _mats(self, layers=3, kn=256, m=16, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        ws = [jnp.asarray(rng.randn(kn, kn).astype(np.float32) * 0.05)
              for _ in range(layers)]
        x = jnp.asarray(rng.randn(m, kn), jnp.bfloat16)
        return x, ws

    def test_int8_stack_matches_reference(self):
        from mlcomp_tpu.ops.serving_stack import (
            quantize_stack, reference_stack, serving_stack,
        )
        x, ws = self._mats()
        wq, sc = quantize_stack(ws)
        want = np.asarray(reference_stack(x, wq, sc))
        got = np.asarray(serving_stack(x, wq, sc, block_n=128,
                                       block_k=128, interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_bf16_stack_matches_reference(self):
        from mlcomp_tpu.ops.serving_stack import (
            reference_stack, serving_stack,
        )
        import jax.numpy as jnp
        x, ws = self._mats(seed=3)
        wstk = jnp.stack([w.astype(jnp.bfloat16) for w in ws])
        want = np.asarray(reference_stack(x, wstk))
        got = np.asarray(serving_stack(x, wstk, block_n=128,
                                       block_k=128, interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_no_feed_variant(self):
        from mlcomp_tpu.ops.serving_stack import (
            reference_stack, serving_stack,
        )
        import jax.numpy as jnp
        x, ws = self._mats(layers=2, seed=5)
        wstk = jnp.stack([w.astype(jnp.bfloat16) for w in ws])
        want = np.asarray(reference_stack(x, wstk, feed=False))
        got = np.asarray(serving_stack(x, wstk, feed=False,
                                       block_n=128, block_k=128,
                                       interpret=True))
        # without the feed renormalization the activations grow, so the
        # kernel's per-k-block f32 accumulation order vs the reference's
        # whole-K dot shows up at the ~3e-5 level
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_shape_validation(self):
        import jax.numpy as jnp
        from mlcomp_tpu.ops.serving_stack import serving_stack
        x = jnp.zeros((8, 256), jnp.bfloat16)
        with pytest.raises(ValueError, match='square layers'):
            serving_stack(x, jnp.zeros((2, 128, 256), jnp.int8))
        with pytest.raises(ValueError, match='tile'):
            serving_stack(x, jnp.zeros((2, 256, 256), jnp.int8),
                          block_n=100)


class TestInt8TrainMatmul:
    """Dynamic int8 TRAINING matmul (ops/int8_matmul.py
    int8_train_matmul): the custom_vjp's forward AND gradients pinned
    against the straight-through jnp oracle, at f32 compute dtype so
    CPU parity is bit-tight."""

    def _case(self, m=16, k=64, n=48, seed=7):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        w = jnp.asarray(rng.randn(k, n) * 0.05, jnp.float32)
        return x, w

    def test_forward_matches_ste_oracle(self):
        import jax.numpy as jnp
        from mlcomp_tpu.ops.int8_matmul import (
            int8_train_matmul, reference_int8_train_matmul,
        )
        x, w = self._case()
        got = int8_train_matmul(x, w, jnp.float32)
        want = reference_int8_train_matmul(x, w, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_forward_close_to_exact(self):
        import jax.numpy as jnp
        from mlcomp_tpu.ops.int8_matmul import int8_train_matmul
        x, w = self._case()
        got = np.asarray(int8_train_matmul(x, w, jnp.float32))
        exact = np.asarray(jnp.dot(x, w))
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        assert rel < 0.02, rel

    def test_gradients_match_ste_oracle(self):
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.int8_matmul import (
            int8_train_matmul, reference_int8_train_matmul,
        )
        x, w = self._case()
        rng = np.random.RandomState(11)
        cot = jnp.asarray(rng.randn(x.shape[0], w.shape[1]),
                          jnp.float32)

        def loss(fn):
            return lambda x_, w_: jnp.sum(fn(x_, w_, jnp.float32) * cot)

        dx, dw = jax.grad(loss(int8_train_matmul), argnums=(0, 1))(x, w)
        rx, rw = jax.grad(loss(reference_int8_train_matmul),
                          argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(rx),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(rw),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_dtypes_follow_primals(self):
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.int8_matmul import int8_train_matmul
        x, w = self._case()
        xb = x.astype(jnp.bfloat16)
        wb = w.astype(jnp.bfloat16)
        dx, dw = jax.grad(
            lambda a, b: jnp.sum(int8_train_matmul(a, b)),
            argnums=(0, 1))(xb, wb)
        assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16

    def test_zero_rows_and_cols_are_safe(self):
        """All-zero rows/columns must not divide by zero in the
        dynamic scales."""
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.int8_matmul import int8_train_matmul
        x, w = self._case()
        x = x.at[3].set(0.0)
        w = w.at[:, 5].set(0.0)
        y = int8_train_matmul(x, w, jnp.float32)
        assert np.isfinite(np.asarray(y)).all()
        assert np.asarray(y)[3].max() == 0.0
        dx, dw = jax.grad(
            lambda a, b: jnp.sum(int8_train_matmul(a, b, jnp.float32)),
            argnums=(0, 1))(x, w)
        assert np.isfinite(np.asarray(dx)).all()
        assert np.isfinite(np.asarray(dw)).all()

    def test_int8_dense_layer_matches_matmul(self):
        """Int8DenseGeneral (models/quant.py) is a thin reshape over
        int8_train_matmul — multi-dim batch and tuple features."""
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.models.quant import Int8DenseGeneral
        from mlcomp_tpu.ops.int8_matmul import int8_train_matmul
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(2, 6, 32), jnp.float32)
        layer = Int8DenseGeneral(
            (4, 8), dtype=jnp.float32, param_dtype=jnp.float32)
        params = layer.init(jax.random.PRNGKey(0), x)
        y = layer.apply(params, x)
        assert y.shape == (2, 6, 4, 8)
        kernel = params['params']['kernel']
        want = int8_train_matmul(
            x.reshape(-1, 32), jnp.asarray(kernel).reshape(32, 32),
            jnp.float32).reshape(2, 6, 4, 8)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        with pytest.raises(ValueError, match='trailing'):
            Int8DenseGeneral(4, axis=0).init(jax.random.PRNGKey(0), x)


class TestFusedNorm:
    """Fused batch-norm(+act) kernel (ops/fused_norm.py): Pallas
    interpret mode vs the dense oracle, forward and the custom-vjp
    backward, and path selection."""

    def _case(self, r=64, c=128, seed=2):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(r, c) * 2 + 0.5, jnp.float32)
        gamma = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        beta = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
        return x, gamma, beta

    @pytest.mark.parametrize('act', [True, False])
    def test_kernel_matches_reference(self, act):
        from mlcomp_tpu.ops.fused_norm import (
            fused_norm_act, reference_norm_act,
        )
        x, gamma, beta = self._case()
        got, gm, gv = fused_norm_act(x, gamma, beta, 1e-5, act,
                                     'interpret')
        want, wm, wv = reference_norm_act(x, gamma, beta, act=act)
        np.testing.assert_allclose(np.asarray(gm), np.asarray(wm),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_narrow_channel_block(self):
        """C=64 (the CIFAR stage-1 width) rides a lane-padded block —
        the biggest byte sites must not be exempt from the kernel."""
        from mlcomp_tpu.ops.fused_norm import (
            fused_norm_act, reference_norm_act,
        )
        x, gamma, beta = self._case(r=64, c=64)
        got, _, _ = fused_norm_act(x, gamma, beta, 1e-5, True,
                                   'interpret')
        want, _, _ = reference_norm_act(x, gamma, beta)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_multi_row_block_accumulation(self):
        """R spanning several row blocks exercises the two-pass
        statistics accumulation."""
        from mlcomp_tpu.ops.fused_norm import (
            fused_norm_act, reference_norm_act,
        )
        x, gamma, beta = self._case(r=256)
        got, _, _ = fused_norm_act(x, gamma, beta, 1e-5, True,
                                   'interpret', 64)
        want, _, _ = reference_norm_act(x, gamma, beta)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize('act', [True, False])
    def test_gradients_match_dense_bn(self, act):
        """The custom-vjp backward (through the batch statistics, relu
        mask recomputed) vs jax.grad of the plain dense formulation."""
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.fused_norm import fused_norm_act

        x, gamma, beta = self._case()
        rng = np.random.RandomState(9)
        cot = jnp.asarray(rng.randn(*x.shape), jnp.float32)

        def dense(x_, g_, b_):
            mean = jnp.mean(x_, axis=0)
            var = jnp.maximum(
                jnp.mean(x_ * x_, axis=0) - mean * mean, 0.0)
            y = (x_ - mean) * jax.lax.rsqrt(var + 1e-5) * g_ + b_
            if act:
                y = jnp.maximum(y, 0.0)
            return jnp.sum(y * cot)

        def fused(x_, g_, b_):
            return jnp.sum(
                fused_norm_act(x_, g_, b_, 1e-5, act, 'dense')[0]
                * cot)

        got = jax.grad(fused, argnums=(0, 1, 2))(x, gamma, beta)
        want = jax.grad(dense, argnums=(0, 1, 2))(x, gamma, beta)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)

    def test_gradients_flow_through_interpret_kernel(self):
        """Same vjp wraps the Pallas forward — grads off the kernel
        path equal grads off the dense path (identical residuals)."""
        import jax
        import jax.numpy as jnp
        from mlcomp_tpu.ops.fused_norm import fused_norm_act
        x, gamma, beta = self._case()

        def loss(impl):
            return lambda x_: jnp.sum(
                fused_norm_act(x_, gamma, beta, 1e-5, True,
                               impl)[0] ** 2)

        gk = jax.grad(loss('interpret'))(x)
        gd = jax.grad(loss('dense'))(x)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)

    def test_path_selection(self):
        import jax.numpy as jnp
        from mlcomp_tpu.ops.fused_norm import fused_norm_act
        x = jnp.zeros((30, 100), jnp.float32)   # tiles nothing
        g = jnp.ones((100,), jnp.float32)
        b = jnp.zeros((100,), jnp.float32)
        fused_norm_act(x, g, b)                 # auto -> dense, runs
        with pytest.raises(ValueError, match='tile'):
            fused_norm_act(x, g, b, 1e-5, True, 'interpret')
        with pytest.raises(ValueError, match='unknown impl'):
            fused_norm_act(x, g, b, 1e-5, True, 'nope')

    def test_eval_path_uses_given_stats(self):
        import jax.numpy as jnp
        from mlcomp_tpu.ops.fused_norm import reference_norm_act
        x, gamma, beta = self._case()
        mean = jnp.zeros((128,), jnp.float32)
        var = jnp.ones((128,), jnp.float32)
        y, m, v = reference_norm_act(x, gamma, beta, act=False,
                                     stats=(mean, var))
        want = (np.asarray(x) - 0.0) / np.sqrt(1.0 + 1e-5) \
            * np.asarray(gamma) + np.asarray(beta)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5,
                                   atol=1e-5)
