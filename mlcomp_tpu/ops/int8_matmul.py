"""Weight-only int8 matmul for the serving path.

Small-batch inference is weight-bandwidth-bound: at M tokens per step
the [K, N] weight read from HBM dwarfs the activations. Storing weights
as int8 (per-output-channel f32 scales, transposed [N, K] layout)
halves the weight memory outright:

    y[M, N] = (x[M, K] @ dequant(w_qt[N, K]).T) * scale[N]

**Measured honestly on the v5e chip** (8-layer K=N=8192 serving stack
at M=64; bench.py ``serving_int8`` records the driver-visible numbers
every round). Two measurement rules: the per-call dispatch and
result fetch must amortize over ~100 stacks per dispatch, and weights
must pass as jit ARGUMENTS (closed-over arrays embed as ~1 GB of HLO
literal constants the compiler must carry). With both kept (round 5):

- this module's auto path (transposed [N, K] int8 + dot_general with
  POST-scaling — the scale applies once to the f32 output, keeping the
  weight-operand read a pure int8->bf16 convert; measured faster than
  pre-scaling) runs ~1.4-1.5x vs the plain bf16 ``x @ w`` chain a
  stack of Dense layers executes;
- the FUSED whole-stack kernel (ops/serving_stack.py: all layers in
  one Pallas program, activation resident in VMEM) edges it further,
  1.52-1.55x with a paired-range floor >1.2 — the bench headline;
- this module's per-op Pallas kernel ties the XLA lowering; like
  ops/fused_ce.py it stays a verified-exact opt-in reference, and
  ``impl='auto'`` resolves to the DENSE formulation. "Don't
  hand-schedule what the compiler already does" — the win that DID
  materialize (serving_stack) came from restructuring (one program,
  resident activation), not re-scheduling one op.

The dependable part is **memory**: weights at rest in HBM halve
(2x more/larger models per chip). The deliverable is the formulation +
integration: ``make_predictor(..., quantize='int8')`` (train/export.py)
reroutes a model export's Dense projections through ``int8_matmul``.
Quantization is symmetric per-output-channel (absmax / 127);
classifier-head prediction drift is below 1e-2 on the digits example
(tests assert it).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def quantize_int8(w):
    """Symmetric per-output-channel quantization of a [K, N] weight.
    Returns (w_qt int8 [N, K] — TRANSPOSED, see module docstring —
    and scale f32 [N]) with ``dequant = (w_qt * scale[:, None]).T``."""
    w = jnp.asarray(w).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    w_q = jnp.clip(jnp.round(w / scale), -127, 127)
    return jnp.asarray(w_q.T, jnp.int8), scale.astype(jnp.float32)


def reference_int8_matmul(x, w_qt, scale, compute_dtype=jnp.bfloat16):
    """The XLA formulation — oracle and the ``impl='auto'`` path.

    POST-scaling: the dot contracts the raw int8 values (cast to bf16 —
    exact, int8 fits bf16's mantissa) and the per-channel scale applies
    ONCE to the f32 [M, N] output. vs pre-scaling (scale folded into
    the weight operand) this keeps the operand read a pure
    convert — measured 1.15x vs 1.12x over bf16 at the serving shape
    (interleaved trials, M=64 8x8192^2) — and is bit-identical to the
    Pallas kernel's accumulation."""
    y = jax.lax.dot_general(
        x.astype(compute_dtype), w_qt.astype(compute_dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y * scale[None, :]


def _fit(n: int, want: int, unit: int):
    start = (min(want, n) // unit) * unit
    for cand in range(start, unit - 1, -unit):
        if n % cand == 0:
            return cand
    return None


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequantize the int8 tile in VMEM (VPU) straight into the MXU dot;
    # per-channel scales apply once at the end so the accumulation stays
    # a plain f32 GEMM. w tile is [bn, bk]: contract both on dim-1.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...].astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finalise():
        o_ref[...] = acc_ref[...] * s_ref[...]


def _pallas_int8_matmul(x, w_qt, scale, block_n, block_k,
                        interpret=False):
    m, k = x.shape
    n, _ = w_qt.shape
    n_k = k // block_k
    kernel = functools.partial(_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=(n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((m, block_k), lambda j, kk: (0, kk)),
            pl.BlockSpec((block_n, block_k), lambda j, kk: (j, kk)),
            pl.BlockSpec((1, block_n), lambda j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, kk: (0, j)),
        scratch_shapes=[pltpu.VMEM((m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
    )(x.astype(jnp.bfloat16), w_qt, scale.reshape(1, n))


def int8_matmul(x, w_qt, scale, impl: str = 'auto',
                block_n: int = 512, block_k: int = 4096,
                interpret: bool = False):
    """``x [M, K] @ dequant(w_qt [N, K]).T -> f32 [M, N]``.

    ``impl``: 'auto' (the dense formulation — XLA fuses the dequant
    into the dot and it is the measured-fastest path, see module
    docstring), 'pallas' (the opt-in kernel), 'dense'.
    """
    m, k = x.shape
    n, k2 = w_qt.shape
    if k != k2 or scale.shape != (n,):
        raise ValueError(
            f'shape mismatch: x {x.shape}, w_qt {w_qt.shape} '
            f'(transposed [N, K] from quantize_int8), '
            f'scale {scale.shape}')
    bn = _fit(n, block_n, 128)
    bk = _fit(k, block_k, 128)
    tiles = bn is not None and bk is not None and m % 8 == 0
    if impl == 'auto':
        use_pallas = False   # dense measured faster (docstring)
    elif impl == 'pallas':
        if not tiles:
            raise ValueError(
                f'({m}, {k}) @ ({n}, {k2})^T does not tile '
                f'(need M%8==0, K%128==0, N%128==0)')
        use_pallas = True
    elif impl == 'dense':
        use_pallas = False
    else:
        raise ValueError(f'unknown impl {impl!r}')
    if not use_pallas:
        return reference_int8_matmul(x, w_qt, scale)
    return _pallas_int8_matmul(x, w_qt, scale, bn, bk,
                               interpret=interpret)


# --------------------------------------------------------------- training
# Dynamic int8 TRAINING matmul (the serving quantizer extended to the
# train step). Both operands are quantized per step, per channel —
# activations per ROW (each token/sample scales over its K features),
# weights per COLUMN (each output channel scales over its K inputs) —
# the MXU contracts the raw int8 values (cast to bf16: exact, int8
# fits bf16's mantissa) with f32 accumulation, and both scales apply
# ONCE to the f32 [M, N] output (the POST-scaling lesson from the
# serving path, module docstring).
#
# Gradients are straight-through on the quantizer (the standard STE of
# quantized training): the vjp differentiates ``dequant(q(x)) @
# dequant(q(w))`` treating q∘dequant as identity, so
#
#     dx = (dy * sw) @ qw^T        dw = qx^T @ (dy * sx)
#
# — the backward contracts the SAME int8 residuals the forward saved.
# That is the byte story: the residuals held for the backward are int8
# (4x smaller than f32 saves, 2x smaller than bf16), and every
# weight/activation operand read in all three matmuls is int8.
# ``reference_int8_train_matmul`` is the jnp STE oracle the vjp is
# pinned against in tests (fwd AND grads).


def _quantize_rows(x):
    """Per-ROW symmetric int8 quantization of [M, K]: scale [M]."""
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    return q.astype(jnp.int8), scale


def _quantize_cols(w):
    """Per-COLUMN symmetric int8 quantization of [K, N]: scale [N]."""
    w = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127)
    return q.astype(jnp.int8), scale


def _accum_dot(a, b, dims, compute_dtype):
    """dot_general with int8 operands cast to the compute dtype (bf16
    on the MXU path — exact for int8 values) and f32 accumulation."""
    return jax.lax.dot_general(
        a.astype(compute_dtype), b.astype(compute_dtype), (dims, ((), ())),
        preferred_element_type=jnp.float32)


def reference_int8_train_matmul(x, w, compute_dtype=jnp.bfloat16):
    """The STE oracle: ``dequant(q(x)) @ dequant(q(w))`` with the
    quantizer wrapped straight-through (``v + stop_grad(dq(q(v)) - v)``)
    so ``jax.grad`` of this function produces exactly the gradients the
    custom vjp must emit. Same cast/accumulation discipline as the fast
    path so test parity is tight."""
    def ste(v, axis):
        v32 = v.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(v32), axis=axis, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        dq = jnp.clip(jnp.round(v32 / scale), -127, 127) * scale
        return v32 + jax.lax.stop_gradient(dq - v32)

    y = jax.lax.dot_general(
        ste(x, 1).astype(compute_dtype), ste(w, 0).astype(compute_dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def int8_train_matmul(x, w, compute_dtype=jnp.bfloat16):
    """``x [M, K] @ w [K, N] -> f32 [M, N]`` with both operands
    dynamically quantized to int8 per channel (straight-through
    gradients; see the training section of the module docstring).

    ``compute_dtype`` is the MXU operand dtype for the scale-folded
    side of each dot (int8 residuals cast exactly; bf16 default —
    pass f32 for bit-tight CPU parity tests)."""
    y, _ = _int8_train_fwd(x, w, compute_dtype)
    return y


def _int8_train_fwd(x, w, compute_dtype):
    qx, sx = _quantize_rows(x)
    qw, sw = _quantize_cols(w)
    y = _accum_dot(qx, qw, ((1,), (0,)), compute_dtype)
    y = y * sx[:, None] * sw[None, :]
    # zero-size carriers keep the primal dtypes in the residual tree
    # (a bare np.dtype is not a valid pytree leaf)
    return y, (qx, sx, qw, sw,
               jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))


def _int8_train_bwd(compute_dtype, res, dy):
    qx, sx, qw, sw, x_proto, w_proto = res
    x_dtype, w_dtype = x_proto.dtype, w_proto.dtype
    dy = dy.astype(jnp.float32)
    # dx = dy @ dequant(w)^T: fold the per-column scale into dy so the
    # weight operand read stays a pure int8 convert
    dx = _accum_dot((dy * sw[None, :]).astype(compute_dtype), qw,
                    ((1,), (1,)), compute_dtype)
    # dw = dequant(x)^T @ dy: the per-row scale folds into dy the same
    # way, so the saved activation read stays a pure int8 convert
    dw = _accum_dot(qx, (dy * sx[:, None]).astype(compute_dtype),
                    ((0,), (0,)), compute_dtype)
    return dx.astype(x_dtype), dw.astype(w_dtype)


int8_train_matmul.defvjp(_int8_train_fwd, _int8_train_bwd)


__all__ = ['quantize_int8', 'int8_matmul', 'reference_int8_matmul',
           'int8_train_matmul', 'reference_int8_train_matmul']
