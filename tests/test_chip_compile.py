"""The Pallas kernels of the main path, compiled at real widths for a
DESCRIBED v5e chip — no chip attached, nothing runs. Catches what
interpret mode cannot: a slice off the tiling, a kernel over its VMEM
budget, a lowering the installed jax/libtpu refuses. A compile that
passes is not a chip run and says nothing about results or times
(``chip_smoke.py`` runs the same kernels against their references on
the chip).

Rules this file keeps (only one process at a time may load the TPU
library, and xdist workers each import every test file): the topology
is described inside a module-scoped fixture — never at import, never in
``conftest.py``, never ``autouse`` — everything compiles in the test's
own process, and every chip-compile test lives in THIS file so one
worker owns the library. Kernels are called with ``impl='pallas'`` /
directly: under the fixture ``jax.default_backend()`` is still ``cpu``,
so ``auto`` would take the dense branch.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(
            platform='tpu', topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; returns the kernel-call
    count of the compiled program."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('tpu_custom_call')


def _flash(grad):
    from mlcomp_tpu.ops.flash_attention import fused_attention

    def fwd(q, k, v):
        return fused_attention(q, k, v, causal=True, impl='pallas')

    if not grad:
        return fwd
    return jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))


@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
@pytest.mark.parametrize('shape', [
    (1, 8192, 8, 128),      # the LM flagship's T, wide heads
    (1, 8192, 16, 64),      # the LM flagship itself (d=1024, 16 heads)
    (4, 1024, 8, 64),
    (4, 2048, 16, 128),     # a step of ``olmo-1b.steady``
], ids=lambda s: 'x'.join(map(str, s)))
def test_flash_attention(one_chip, shape, grad):
    qkv = [(shape, jnp.bfloat16)] * 3
    # fwd is one kernel; the backward is ONE more (PR 34: dq, dk and dv
    # from one walk, with the VMEM limit its shapes ask for)
    assert _compile(_flash(grad), one_chip, *qkv) == (2 if grad else 1)


def test_olmo_layer_keeps_the_rows_its_fused_qkv_writes(one_chip):
    """``jax.grad`` of one `remat`ted layer of ``olmo-1b.steady`` (4 x
    2,048 tokens, 16 heads of 128): its fused qkv projection writes the
    kernels' rows, so heads of whole 128s take rows (PR 36,
    ``_panels_for``; panels would add 9 copies here) and the op leaves
    the one copy the parent's step had; the forward runs twice (no
    save-by-name policy), the backward once."""
    import json

    import flax
    import flax.linen as nn
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.models.transformer import DecoderLayer
    from mlcomp_tpu.ops.flash_attention import layout_copies
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, 'benchmark/configs/olmo-1b.json')) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = create_model(**dict(kwargs, attn_impl='pallas')).cfg
    layer = nn.remat(DecoderLayer, static_argnums=(2,))(cfg)
    x = jax.ShapeDtypeStruct((4, 2048, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x, False)['params']))

    def loss(p, x):
        return (layer.apply({'params': p}, x, False).astype(
            jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert text.count('tpu_custom_call') == 3
    assert layout_copies(text) <= 1


def test_flash_attention_backward_in_spans(one_chip):
    """A key-value head of 32,768 tokens at head size 256 does not stay
    in VMEM whole (K, V, their gradients' blocks and the float32
    accumulators are 201 MB): the same kernel takes it in spans, each
    span's share of dq a float32 partial."""
    from mlcomp_tpu.ops import flash_attention as fa
    assert fa._span(32768, 1024, 256, 256, 2) == 8192
    assert fa._span(8192, 1024, 256, 256, 2) == 8192
    shapes = [((1, 32768, 8, 256), jnp.bfloat16),
              ((1, 32768, 1, 256), jnp.bfloat16),
              ((1, 32768, 1, 256), jnp.bfloat16)]
    assert _compile(_flash(True), one_chip, *shapes) == 2


@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
@pytest.mark.parametrize('r,c', [
    # the four norm sites of CIFAR ResNet-18 at batch 512
    (524288, 64), (131072, 128), (32768, 256), (8192, 512),
])
def test_fused_norm(one_chip, r, c, grad):
    from mlcomp_tpu.ops.fused_norm import fused_norm_act

    def fwd(x, gamma, beta):
        return fused_norm_act(x, gamma, beta, 1e-5, True, 'pallas')

    fn = fwd if not grad else jax.grad(
        lambda x, g, b: fwd(x, g, b)[0].astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    assert _compile(fn, one_chip, ((r, c), jnp.bfloat16),
                    ((c,), jnp.float32), ((c,), jnp.float32)) >= 1


@pytest.mark.parametrize('w_dtype', [jnp.int8, jnp.bfloat16],
                         ids=['int8', 'bf16'])
def test_serving_stack(one_chip, w_dtype):
    from mlcomp_tpu.ops.serving_stack import serving_stack
    shapes = [((64, 8192), jnp.bfloat16), ((8, 8192, 8192), w_dtype)]
    if w_dtype == jnp.int8:
        shapes.append(((8, 8192), jnp.float32))
    assert _compile(serving_stack, one_chip, *shapes) == 1


def test_int8_matmul(one_chip):
    from mlcomp_tpu.ops.int8_matmul import int8_matmul

    def fn(x, w_qt, scale):
        return int8_matmul(x, w_qt, scale, impl='pallas')

    assert _compile(fn, one_chip, ((64, 8192), jnp.bfloat16),
                    ((8192, 8192), jnp.int8),
                    ((8192,), jnp.float32)) == 1


def test_fused_ce(one_chip):
    from mlcomp_tpu.ops.fused_ce import softmax_ce_per_example

    def loss(logits, labels):
        return softmax_ce_per_example(
            logits, labels, impl='pallas').sum()

    # fwd kernel + bwd kernel
    assert _compile(jax.grad(loss), one_chip,
                    ((8192, 32768), jnp.bfloat16),
                    ((8192,), jnp.int32)) >= 2


# ---- the kernels of qwen3_next at the published widths (PR 28)
@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
def test_grouped_query_flash_attention(one_chip, grad):
    """16 query heads over 2 key-value heads of 256 at 8,192 tokens,
    what a step of ``qwen3-next-80b-a3b.steady`` hands the kernels: the
    limits they ask for have to be granted (1024-tiles; in the backward
    a head's K, V, dK, dV whole: 50 MB)."""
    shapes = [((2, 8192, 16, 256), jnp.bfloat16),
              ((2, 8192, 2, 256), jnp.bfloat16),
              ((2, 8192, 2, 256), jnp.bfloat16)]
    assert _compile(_flash(grad), one_chip, *shapes) == (2 if grad else 1)


@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
@pytest.mark.parametrize('sequences', [1, 2], ids=['one', 'the_cell'])
def test_gated_delta_rule(one_chip, sequences, grad):
    """One block of 8 value heads of 128 x 128 at 8,192 tokens; with 2
    sequences it is what a step of ``qwen3-next-80b-a3b.steady`` hands
    the kernels for each of its 4 head blocks."""
    from mlcomp_tpu.ops.gated_delta import gated_delta_rule

    def fwd(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta, impl='pallas')

    fn = fwd if not grad else jax.grad(
        lambda *a: fwd(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))
    wide = ((sequences, 8192, 8, 128), jnp.bfloat16)
    gate = ((sequences, 8192, 8), jnp.float32)
    # forward: gated_delta_prepare, gated_delta_fwd; the backward makes
    # the operands again (prepare), then gated_delta_bwd_scan and
    # gated_delta_prepare_bwd
    assert _compile(fn, one_chip, wide, wide, wide, gate, gate) \
        == (5 if grad else 2)


def test_expert_grouped_matmul(one_chip):
    """The held experts' gate, up and down products and their backward
    (megablox gmm / tgmm) over a buffer of 40,960 routed rows."""
    from mlcomp_tpu.models.decoder_parts import grouped_matmul

    def fwd(x, gate, up, down, sizes):
        hidden = jax.nn.silu(grouped_matmul(x, gate, sizes, 'gmm')) \
            * grouped_matmul(x, up, sizes, 'gmm')
        return grouped_matmul(hidden, down, sizes, 'gmm')

    fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2, 3))
    assert _compile(fn, one_chip, ((40960, 2048), jnp.bfloat16),
                    ((32, 2048, 512), jnp.bfloat16),
                    ((32, 2048, 512), jnp.bfloat16),
                    ((32, 512, 2048), jnp.bfloat16),
                    ((32,), jnp.int32)) >= 8


@pytest.mark.parametrize('full', [False, True], ids=['linear', 'full'])
def test_qwen3_next_remat_holds_the_kernels_results(one_chip, full):
    """``jax.grad`` of one `remat`ted layer of ``qwen3-next-80b-a3b.
    steady`` at its widths and 2 x 8,192 tokens: with the save-by-name
    policy (``REMAT_SAVED``) the backward pass runs no forward kernel
    again — the delta op's operands are made once more
    (``gated_delta_prepare``, the backward rule's own), its scan and
    the flash forward are not."""
    import collections
    import json
    import re

    import flax
    from mlcomp_tpu.models import create_model, qwen3_next
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, 'benchmark/configs/qwen3-next-80b-a3b.json')) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = create_model(**dict(
        kwargs, attn_impl='pallas', delta_impl='pallas',
        moe_impl='gmm')).cfg
    assert cfg.remat
    layer = qwen3_next._layer_class(cfg)(cfg, full)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x)['params']))

    def loss(p, x):
        y = layer.apply({'params': p}, x, mutable=['intermediates'])[0]
        return y.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = collections.Counter(
        re.search(r'gated_delta_\w+|gqa_attn|tgmm|gmm|$', re.search(
            r'op_name="([^"]*)"', line).group(1)).group(0)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    if full:
        # forward and the one backward kernel (PR 34)
        assert kernels['gqa_attn'] == 2
        # heads of 256 keep their rows (PR 36: ``_panels_for``); what
        # the op holds is its folded rows, so the backward folds them no
        # more — 7 layout copies where the parent had 9
        from mlcomp_tpu.ops.flash_attention import layout_copies
        assert layout_copies(text, 'gqa_attn') == layout_copies(text) <= 7
    else:
        assert kernels['gated_delta_fwd'] == 1
        assert kernels['gated_delta_prepare'] == 2
        assert kernels['gated_delta_bwd_scan'] == 1
        assert kernels['gated_delta_prepare_bwd'] == 1
    # the grouped products are not held: three made again and three
    # for the rows' gradients (the forward's own are dead code here: a
    # sum's gradient does not read the layer's output), three for the
    # weights' gradients
    assert kernels['gmm'] == 6 and kernels['tgmm'] == 3, kernels
    assert '' not in kernels        # no kernel but these
    # a layer routes once: `lax.top_k`'s sort of the probabilities and
    # the argsort of the (token, expert) pairs, by the ops' own names
    sorts = [line for line in text.splitlines() if ' sort(' in line]
    assert sum('/moe/top_k"' in line for line in sorts) == 1
    assert sum('/moe/jit(argsort)/sort"' in line for line in sorts) == 1


# ---- the kernels of lfm2_moe at the published widths (PR 33)
@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
def test_grouped_query_flash_attention_at_head_size_64(one_chip, grad):
    """32 query heads over 8 key-value heads of 64 (half a lane tile) at
    2 x 8,192 tokens: what a step of ``lfm2-8b-a1b.steady`` hands the
    two flash kernels."""
    shapes = [((2, 8192, 32, 64), jnp.bfloat16),
              ((2, 8192, 8, 64), jnp.bfloat16),
              ((2, 8192, 8, 64), jnp.bfloat16)]
    assert _compile(_flash(grad), one_chip, *shapes) == (2 if grad else 1)


@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
@pytest.mark.parametrize('block_t', [128, 256, 512])
def test_gated_short_conv(one_chip, block_t, grad):
    """``short_conv_fwd`` / ``short_conv_bwd`` over 2 x 8,192 rows of
    3 x 2,048 channels: the sublane rolls, the 16-row halo blocks and
    the whole-width tiles have to fit the VMEM the kernels ask for."""
    from mlcomp_tpu.ops.short_conv import gated_short_conv

    def fwd(bcx, taps):
        return gated_short_conv(bcx, taps, impl='pallas', block_t=block_t)

    fn = fwd if not grad else jax.grad(
        lambda *a: (fwd(*a).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1))
    assert _compile(fn, one_chip, ((2, 8192, 6144), jnp.bfloat16),
                    ((3, 2048), jnp.float32)) == (2 if grad else 1)


def test_expert_grouped_matmul_at_2048_rows_an_expert(one_chip):
    """8 held experts of 2,048 x 1,792 over the worst-case buffer of
    65,536 routed rows at the cell's row tile, forward and backward
    (megablox gmm / tgmm)."""
    from mlcomp_tpu.models.decoder_parts import grouped_matmul, row_tile
    tile = row_tile(2048, 65536)
    assert tile == 512

    def fwd(x, gate, up, down, sizes):
        hidden = jax.nn.silu(grouped_matmul(x, gate, sizes, 'gmm', tile)) \
            * grouped_matmul(x, up, sizes, 'gmm', tile)
        return grouped_matmul(hidden, down, sizes, 'gmm', tile)

    fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2, 3))
    assert _compile(fn, one_chip, ((65536, 2048), jnp.bfloat16),
                    ((8, 2048, 1792), jnp.bfloat16),
                    ((8, 2048, 1792), jnp.bfloat16),
                    ((8, 1792, 2048), jnp.bfloat16),
                    ((8,), jnp.int32)) >= 8


@pytest.mark.parametrize('kind', ['conv', 'full_attention'])
def test_lfm2_moe_remat_holds_the_kernels_results(one_chip, kind):
    """``jax.grad`` of one `remat`ted sparse layer of
    ``lfm2-8b-a1b.steady`` at its widths and 2 x 8,192 tokens: with the
    save-by-name policy (``models/lfm2_moe.py`` ``REMAT_SAVED``) the
    backward pass runs no forward kernel again — not the conv op, not
    the flash forward, not one grouped product."""
    import collections
    import json
    import re

    import flax
    from mlcomp_tpu.models import create_model, lfm2_moe
    from mlcomp_tpu.models.decoder_parts import remat_saving
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, 'benchmark/configs/lfm2-8b-a1b.json')) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = create_model(**dict(
        kwargs, attn_impl='pallas', conv_impl='pallas',
        moe_impl='gmm')).cfg
    assert cfg.remat
    layer = remat_saving(lfm2_moe.Lfm2MoeLayer, True,
                         lfm2_moe.REMAT_SAVED)(cfg, kind, True)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x)['params']))

    def loss(p, x):
        y = layer.apply({'params': p}, x, mutable=['intermediates'])[0]
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = collections.Counter(
        re.search(r'short_conv_\w+|gqa_attn|tgmm|gmm|$', re.search(
            r'op_name="([^"]*)"', line).group(1)).group(0)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    if kind == 'conv':
        assert kernels['short_conv_fwd'] == 1
        assert kernels['short_conv_bwd'] == 1
    else:
        assert kernels['gqa_attn'] == 2     # forward, one backward
        # heads of 64 go in as panels (PR 36): 3 layout copies where
        # the parent had 10
        from mlcomp_tpu.ops.flash_attention import layout_copies
        assert layout_copies(text, 'gqa_attn') == layout_copies(text) <= 3
    # gate, up, down once; three for the rows' gradients, three for the
    # weights'
    assert kernels['gmm'] == 6 and kernels['tgmm'] == 3, kernels
    assert '' not in kernels        # no kernel but these


# ---- the kernels of deepseek_v3 at the published widths (PR 35)
@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'fwd_bwd'])
def test_latent_attention_flash_at_192_and_128(one_chip, grad):
    """32 heads whose scores are 192 deep (one and a half lane tiles)
    and whose values are 128 wide, at 2 x 8,192 tokens: what a step of
    ``kanana-2-30b-a3b.steady`` hands the two flash kernels."""
    shapes = [((2, 8192, 32, 192), jnp.bfloat16),
              ((2, 8192, 32, 192), jnp.bfloat16),
              ((2, 8192, 32, 128), jnp.bfloat16)]
    assert _compile(_flash(grad), one_chip, *shapes) == (2 if grad else 1)


@pytest.mark.parametrize('sparse', [False, True], ids=['dense', 'sparse'])
def test_deepseek_v3_remat_holds_the_kernels_results(one_chip, sparse):
    """``jax.grad`` of one `remat`ted layer of ``kanana-2-30b-a3b.steady``
    at its widths and 2 x 8,192 tokens: with the save-by-name policy
    (``models/deepseek_v3.py`` ``REMAT_SAVED``) the backward pass runs no
    forward kernel again — not the flash forward, not one grouped
    product — and both flash kernels carry the scope ``mla_attn``. A
    step of the cell is the dense layer and four sparse ones: 10 + 36 =
    46 kernel calls (``step.kernel_calls``, ``PERF.md`` section 3)."""
    import collections
    import json
    import re

    import flax
    from mlcomp_tpu.models import create_model, deepseek_v3
    from mlcomp_tpu.models.decoder_parts import remat_saving
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, 'benchmark/configs/kanana-2-30b-a3b.json')) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = create_model(**dict(
        kwargs, attn_impl='pallas', moe_impl='gmm')).cfg
    assert cfg.remat and cfg.n_layers - cfg.n_dense_layers == 4
    layer = remat_saving(deepseek_v3.DeepseekV3Layer, True,
                         deepseek_v3.REMAT_SAVED)(cfg, sparse)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x)['params']))

    def loss(p, x):
        y = layer.apply({'params': p}, x, mutable=['intermediates'])[0]
        return (y.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    kernels = collections.Counter(
        re.search(r'mla_attn|tgmm|gmm|$', re.search(
            r'op_name="([^"]*)"', line).group(1)).group(0)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels['mla_attn'] == 2         # forward, one backward
    # gate, up, down once; three for the rows' gradients, three for the
    # weights' (768 wide: a tile of 512 and a padded half)
    assert (kernels['gmm'], kernels['tgmm']) == ((6, 3) if sparse
                                                 else (0, 0)), kernels
    assert '' not in kernels        # no kernel but these
    # PR 36: q, k, v reach the kernels as the head panels q_proj's and
    # kv_b_proj's matmuls write, and dq, dk, dv leave as panels — 3
    # layout copies a layer and step where the parent had 9 (4.4 GB of
    # traffic), all of them the op's own (``step.flash_layout_copies``
    # reads 15 for the cell's five layers)
    from mlcomp_tpu.ops.flash_attention import layout_copies
    assert layout_copies(text, 'mla_attn') == layout_copies(text) <= 3
    assert not re.findall(r'= bf16\[2,32,8192,192\]\S* copy\(', text)
    if not sparse:      # the parent's layer: 2.023 GB
        assert compiled.memory_analysis().temp_size_in_bytes < 2.02e9


# ---- the routing round the experts at the sparse cells' shapes
@pytest.mark.parametrize('cell', ['lfm2-8b-a1b', 'kanana-2-30b-a3b',
                                  'qwen3-next-80b-a3b'])
def test_sparse_layer_moves_its_rows_by_the_rules_form(one_chip, cell):
    """``jax.grad`` of one cell's ``SparseMoe`` (gmm) over 2 x 8,192
    tokens: where the buffer holds every (token, expert) pair (lfm2) the
    layer gathers both ways and its compiled program holds no scatter
    over [16384, 2048] rows; elsewhere what ``gathers_rows`` picks, two
    such scatters (the combine and the dispatch's gradient) or none —
    counted as ``step.wide_scatters`` counts them."""
    import json

    import flax
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.models.decoder_parts import (
        MoeConfig, SparseMoe, buffer_rows, gathers_rows,
    )
    from mlcomp_tpu.telemetry.op_blocks import row_scatters
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f'benchmark/configs/{cell}.json')) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = MoeConfig.of(create_model(**dict(kwargs, moe_impl='gmm')).cfg)
    layer = SparseMoe(cfg, name='moe')
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x)['params']))

    def loss(p, x):
        y = layer.apply({'params': p}, x, mutable=['intermediates'])[0]
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    gathers = gathers_rows(16384 * cfg.top_k, buffer_rows(cfg, 16384))
    assert gathers or cell != 'lfm2-8b-a1b'
    assert row_scatters(text) == (0 if gathers else 2)


def test_ouro_layer_at_its_widths(one_chip):
    """``jax.grad`` of one `remat`ted layer of ``ouro-2.6b.steady`` at
    its widths and 2 x 4,096 tokens: the flash kernels at 16 heads of
    128 under the scope ``gqa_attn`` and no other kernel — the forward,
    `remat`'s second forward (``models/ouro.py`` ``REMAT_SAVED`` holds
    nothing: the stage's memory goes to the 32 layer applications'
    inputs) and the one backward."""
    import collections
    import json
    import re

    import flax
    from mlcomp_tpu.models import create_model, ouro
    from mlcomp_tpu.models.decoder_parts import remat_saving
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, 'benchmark/configs/ouro-2.6b.json')) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = create_model(**dict(kwargs, attn_impl='pallas')).cfg
    assert cfg.remat and ouro.REMAT_SAVED == ()
    layer = remat_saving(ouro.OuroLayer, True, ouro.REMAT_SAVED)(cfg)
    x = jax.ShapeDtypeStruct((2, 4096, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x, None)['params']))

    def loss(p, x):
        y = layer.apply({'params': p}, x, None)[0]
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = collections.Counter(
        re.search(r'gqa_attn|$', re.search(
            r'op_name="([^"]*)"', line).group(1)).group(0)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == {'gqa_attn': 3}, kernels
