"""Fused multi-layer serving stack — ONE Pallas kernel for N matmul
layers at small batch.

Small-batch serving is weight-bandwidth-bound, but XLA executes an
8-layer K=N=8192 stack as 8 separate fusions: measured on the v5e, the
per-op overhead leaves the chain ~4x off the HBM roofline (scripts/
int8_probe.py), which also dilutes weight-only int8's 2x byte saving
to ~1.2x end-to-end. This kernel runs the WHOLE stack in one program:

- the activation ([M, K] bf16, ~1 MB at M=64) lives in VMEM scratch
  across layers — it never round-trips HBM;
- weights stream tile-by-tile ([L, N, K] stacked, int8 or bf16),
  double-buffered by Pallas's pipeline — HBM traffic is exactly the
  weight bytes, where int8's 2x shows up undiluted;
- per-output-channel scales apply on the accumulator tile; between
  layers the max-abs renormalization (the bench chain's stand-in for
  an activation) happens in-register at layer boundaries.

Grid (L, N/bn, K/bk), fully sequential ('arbitrary'): scratch carries
the activation and the layer accumulator, so iteration order IS the
dataflow. Exactness is pinned against the pure-jnp chain in
tests/test_ops.py (interpret mode).

Capability beyond the reference: its serving story stops at model rows
(reference server/back/app.py:264-297); bench.py's serving legs record
this kernel's effect every round.
"""

import functools
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FEED_EPS = 1e-6


def reference_stack(x, w_stack, scales=None, feed: bool = True):
    """Pure-jnp oracle: y_l = x_l @ dequant(W_l).T; x_{l+1} =
    feed(y_l). ``w_stack`` [L, N, K] (transposed layout, int8 or
    bf16); ``scales`` [L, N] or None. Returns the LAST layer's f32
    output (pre-feed)."""
    y = None
    for li in range(w_stack.shape[0]):
        if li > 0:
            x = (y / (jnp.max(jnp.abs(y)) + FEED_EPS)) \
                .astype(jnp.bfloat16) if feed else y.astype(jnp.bfloat16)
        y = jax.lax.dot_general(
            x.astype(jnp.bfloat16), w_stack[li].astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if scales is not None:
            y = y * scales[li][None, :]
    return y


def _stack_kernel(x_ref, w_ref, s_ref, o_ref, x_scr, y_scr,
                  *, n_l, n_j, n_k, bn, bk, feed):
    li = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((li == 0) & (j == 0) & (k == 0))
    def _load_input():
        x_scr[...] = x_ref[...]

    @pl.when((li > 0) & (j == 0) & (k == 0))
    def _layer_feed():
        y = y_scr[...]
        if feed:
            y = y / (jnp.max(jnp.abs(y)) + FEED_EPS)
        x_scr[...] = y.astype(x_scr.dtype)

    # j-th output tile accumulates over k; the accumulator is the
    # j-slice of the full-width y scratch (the next layer contracts
    # over ALL of it, so it must persist per layer)
    acc = jax.lax.dot_general(
        x_scr[:, pl.dslice(k * bk, bk)], w_ref[0].astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _first():
        y_scr[:, pl.dslice(j * bn, bn)] = acc

    @pl.when(k > 0)
    def _rest():
        y_scr[:, pl.dslice(j * bn, bn)] = \
            y_scr[:, pl.dslice(j * bn, bn)] + acc

    @pl.when(k == n_k - 1)
    def _scale_tile():
        y_scr[:, pl.dslice(j * bn, bn)] = \
            y_scr[:, pl.dslice(j * bn, bn)] * s_ref[0]

    @pl.when((li == n_l - 1) & (k == n_k - 1))
    def _emit():
        o_ref[...] = y_scr[:, pl.dslice(j * bn, bn)]


def serving_stack(x, w_stack, scales=None, feed: bool = True,
                  block_n: int = 1024, block_k: int = 2048,
                  interpret: bool = False):
    """Run the fused stack. ``x`` [M, K] (any float dtype), ``w_stack``
    [L, N, K] with N == K (the activation width must be constant
    across layers), ``scales`` [L, N] f32 or None (bf16 weights).
    Returns f32 [M, N] — the last layer's pre-feed output."""
    m, kdim = x.shape
    n_l, n, k2 = w_stack.shape
    if k2 != kdim or n != kdim:
        raise ValueError(
            f'stack needs square layers matching x: x {x.shape}, '
            f'w_stack {w_stack.shape}')
    if n % block_n or kdim % block_k:
        raise ValueError(
            f'({n}, {kdim}) does not tile by ({block_n}, {block_k})')
    if scales is None:
        scales = jnp.ones((n_l, n), jnp.float32)
    n_j, n_k = n // block_n, kdim // block_k
    kernel = functools.partial(
        _stack_kernel, n_l=n_l, n_j=n_j, n_k=n_k, bn=block_n,
        bk=block_k, feed=feed)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=(n_l, n_j, n_k),
        in_specs=[
            pl.BlockSpec((m, kdim), lambda l, j, k: (0, 0)),
            pl.BlockSpec((1, block_n, block_k),
                         lambda l, j, k: (l, j, k)),
            # scales ride as [L, 1, N]: a (1, 1, bn) block keeps the
            # second-to-last dim FULL (TPU blocks need the last two
            # dims (8, 128)-divisible or whole)
            pl.BlockSpec((1, 1, block_n), lambda l, j, k: (l, 0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda l, j, k: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((m, kdim), jnp.bfloat16),   # resident activation
            pltpu.VMEM((m, n), jnp.float32),       # layer accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary', 'arbitrary')),
        interpret=interpret,
    )(x.astype(jnp.bfloat16), w_stack,
      scales.astype(jnp.float32).reshape(n_l, 1, n))


def quantize_stack(ws: Sequence) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[K, N] float weights -> stacked ([L, N, K] int8, [L, N] f32)
    via the serving quantizer (ops/int8_matmul.py)."""
    from mlcomp_tpu.ops.int8_matmul import quantize_int8
    qs, ss = zip(*(quantize_int8(w) for w in ws))
    return jnp.stack(qs), jnp.stack(ss)


def stack_feed(y):
    """The inter-layer renormalization of the bench chain — the ONE
    definition both the kernel (between its layers) and the host-side
    harnesses (between stacks / per-op layers) must share, or the
    bf16-vs-int8 comparison silently stops being apples-to-apples."""
    return (y / (jnp.max(jnp.abs(y)) + FEED_EPS)).astype(jnp.bfloat16)


def make_chain_runner(step, args, x0, reps: int, recorder=None,
                      metric: str = 'serving.chain_ms'):
    """Timed-chain harness: operands pass as jit ARGUMENTS (closed-
    over arrays embed as HLO literal constants — ~1 GB here — which
    the compiler must carry) and reps ride a ``lax.scan`` (the
    unrolled program is as large), with enough reps per dispatch to
    amortize the per-call dispatch and the result fetch that is the
    barrier. ``step(x, *args)``
    runs ONE stack; returns a no-arg callable whose float() forces
    completion.

    ``recorder`` (a telemetry ``MetricRecorder``) turns the driver into
    its own latency histogram: each call after the first observes the
    per-stack wall-clock (ms) under ``metric`` — the first call is the
    compile+warm pass every harness makes, and a one-off compile in a
    steady-state latency histogram would poison mean/max — so a flush
    emits ``<metric>.p50/.p99/…`` summary rows next to the ratios the
    bench publishes (the in-DB counterpart of bench.py's JSON mins)."""
    def run(x, *a):
        def body(x, _):
            return step(x, *a), None
        x, _ = jax.lax.scan(body, x, None, length=reps)
        return jnp.sum(x.astype(jnp.float32))
    fn = jax.jit(run)
    warmed = [False]

    def call():
        t0 = time.perf_counter()
        out = float(fn(x0, *args))
        if recorder is not None and warmed[0]:
            recorder.observe(
                metric, (time.perf_counter() - t0) / reps * 1e3)
        warmed[0] = True
        return out
    return call


__all__ = ['serving_stack', 'reference_stack', 'quantize_stack',
           'stack_feed', 'make_chain_runner', 'FEED_EPS']
