"""Fused batch-norm(+activation) — ONE Pallas program per norm site.

The round-5 CIFAR ablation (docs/performance.md, scripts/
cifar_probe.py) billed BatchNorm at 28% of ALL train-step bytes
(2.8 GB/step at bs=512): XLA lowers train-mode BN into separate
statistics reductions and a normalize pass, with the pre-activation
normalized intermediate materialized in HBM between the norm and the
relu that always follows it in a ResNet block. This kernel is the
byte-count answer, in the serving-stack mold (restructure the
dataflow, don't re-schedule one op):

- the input is viewed as ``[R, C]`` (R = N*H*W rows, C channels) and
  the grid runs two PASSES over the row blocks inside one program:
  pass 0 accumulates per-channel sum/sum-of-squares in VMEM scratch
  (one read of x), pass 1 applies ``act(gamma * xhat + beta)`` and
  writes the block (second read + one write);
- the normalized intermediate and the pre-relu tensor never exist in
  HBM — total traffic is exactly 2 reads + 1 write of x plus the [C]
  statistics, with the activation folded in;
- batch mean/var are emitted as [C] outputs (the running-stats update
  and the backward need them; they are ~KBs).

Training gradients go through a ``custom_vjp`` whose backward is the
standard dense batch-norm backward (through the batch statistics) —
measured lesson from fused_ce: the backward is a plain
elementwise+reduction composition XLA already fuses well, so only the
forward (where the fusion barrier and the extra intermediate lived)
gets a kernel.

``fused_norm_act`` is the entry point; models/resnet.py's
``norm='fused'`` wires it into the CIFAR blocks. CPU tests run the
kernel in interpret mode; ``impl='auto'`` uses the kernel only on TPU
when shapes tile (C a multiple of 128, rows a multiple of 8) and
falls back to the identical dense formulation otherwise.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def reference_norm_act(x2d, gamma, beta, eps: float = 1e-5,
                       act: bool = True,
                       stats: Optional[Tuple] = None):
    """Dense oracle and fallback: batch-norm over rows of [R, C] (+
    relu when ``act``). Returns (y, mean, var). ``stats`` = (mean,
    var) uses the given statistics instead (the eval/running path)."""
    x32 = x2d.astype(jnp.float32)
    if stats is None:
        mean = jnp.mean(x32, axis=0)
        var = jnp.mean(x32 * x32, axis=0) - mean * mean
        var = jnp.maximum(var, 0.0)
    else:
        mean, var = (s.astype(jnp.float32) for s in stats)
    inv = jax.lax.rsqrt(var + eps)
    y = (x32 - mean[None, :]) * (inv * gamma.astype(jnp.float32)
                                 )[None, :] + beta.astype(
                                     jnp.float32)[None, :]
    if act:
        y = jnp.maximum(y, 0.0)
    return y.astype(x2d.dtype), mean, var


def _fit(n: int, want: int, unit: int):
    start = (min(want, n) // unit) * unit
    for cand in range(start, unit - 1, -unit):
        if n % cand == 0:
            return cand
    return None


def _norm_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, var_ref,
                 sum_scr, sq_scr, *, n_r, inv_n, eps, act):
    phase = pl.program_id(0)
    r = pl.program_id(1)

    @pl.when((phase == 0) & (r == 0))
    def _init():
        sum_scr[...] = jnp.zeros_like(sum_scr)
        sq_scr[...] = jnp.zeros_like(sq_scr)

    @pl.when(phase == 0)
    def _accumulate():
        x = x_ref[...].astype(jnp.float32)
        sum_scr[...] += jnp.sum(x, axis=0, keepdims=True)
        sq_scr[...] += jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when((phase == 0) & (r == n_r - 1))
    def _stats():
        mean = sum_scr[...] * inv_n
        var = jnp.maximum(sq_scr[...] * inv_n - mean * mean, 0.0)
        mean_ref[...] = mean
        var_ref[...] = var
        # stash inv-std and the shift in the scratch for pass 1 — the
        # stats outputs are written once, the scratch is VMEM-resident
        sum_scr[...] = jax.lax.rsqrt(var + eps) \
            * g_ref[...].astype(jnp.float32)
        sq_scr[...] = mean

    @pl.when(phase == 1)
    def _normalize():
        x = x_ref[...].astype(jnp.float32)
        y = (x - sq_scr[...]) * sum_scr[...] \
            + b_ref[...].astype(jnp.float32)
        if act:
            y = jnp.maximum(y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)


def _pallas_norm_act(x2d, gamma, beta, eps, act, block_r,
                     interpret=False):
    r, c = x2d.shape
    n_r = r // block_r
    kernel = functools.partial(
        _norm_kernel, n_r=n_r, inv_n=1.0 / r, eps=float(eps), act=act)
    y, mean, var = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((r, c), x2d.dtype),
                   jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        grid=(2, n_r),
        in_specs=[
            pl.BlockSpec((block_r, c), lambda p, rr: (rr, 0)),
            pl.BlockSpec((1, c), lambda p, rr: (0, 0)),
            pl.BlockSpec((1, c), lambda p, rr: (0, 0)),
        ],
        out_specs=(
            # rr*p clamps the block index to 0 through the whole
            # statistics pass: the index never changes there, so Pallas
            # never flushes a garbage block — y is written exactly once
            # per block, all during the normalize pass
            pl.BlockSpec((block_r, c), lambda p, rr: (rr * p, 0)),
            pl.BlockSpec((1, c), lambda p, rr: (0, 0)),
            pl.BlockSpec((1, c), lambda p, rr: (0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=interpret,
    )(x2d, gamma.reshape(1, c), beta.reshape(1, c))
    return y, mean.reshape(c), var.reshape(c)


def _use_pallas(impl: str, r: int, c: int) -> bool:
    # full lanes (C a multiple of 128) or a lane-padded narrow block
    # (C a divisor of 128, >= 8) — the CIFAR stem/stage-1 sites are
    # C=64, and they carry the LARGEST activations; refusing them
    # would exempt the biggest byte sites from the fused kernel
    c_ok = (c % 128 == 0) or (c >= 8 and 128 % c == 0)
    tiles = c_ok and (r % 8 == 0)
    if impl == 'pallas' or impl == 'interpret':
        if not tiles:
            raise ValueError(
                f'[{r}, {c}] does not tile for the fused-norm kernel '
                f'(need R%8==0 and C a multiple of 128, or a '
                f'lane-padded narrow block: C>=8 dividing 128)')
        return True
    if impl == 'dense':
        return False
    if impl != 'auto':
        raise ValueError(f'unknown impl {impl!r}')
    return tiles and jax.default_backend() == 'tpu'


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_norm_act(x2d, gamma, beta, eps: float = 1e-5,
                   act: bool = True, impl: str = 'auto',
                   block_r: int = 1024):
    """Train-mode batch norm over the rows of ``x2d`` [R, C] with the
    activation folded in: ``(act(gamma*xhat+beta), mean, var)``.
    Differentiable in (x, gamma, beta) through the batch statistics
    (the standard BN backward)."""
    y, _ = _fused_fwd(x2d, gamma, beta, eps, act, impl, block_r)
    return y


def _forward(x2d, gamma, beta, eps, act, impl, block_r):
    r, c = x2d.shape
    if _use_pallas(impl, r, c):
        br = _fit(r, block_r, 8)
        return _pallas_norm_act(
            x2d, gamma.astype(jnp.float32), beta.astype(jnp.float32),
            eps, act, br, interpret=(impl == 'interpret'))
    return reference_norm_act(x2d, gamma, beta, eps=eps, act=act)


def _fused_fwd(x2d, gamma, beta, eps, act, impl, block_r):
    y, mean, var = _forward(x2d, gamma, beta, eps, act, impl, block_r)
    return (y, mean, var), (x2d, gamma, beta, mean, var)


def _fused_bwd(eps, act, impl, block_r, res, cot):
    """Dense BN backward through the batch statistics. With the
    activation folded, the relu mask is recomputed from (x, stats,
    gamma, beta) — cheaper than saving the pre-activation tensor the
    kernel exists to avoid materializing. The mean/var outputs are
    auxiliary (running-stats updates); gradients do not flow through
    them — their cotangents are ignored, stop_gradient semantics."""
    x2d, gamma, beta, mean, var = res
    dy, _, _ = cot          # cotangents of (y, mean, var)
    r = x2d.shape[0]
    x32 = x2d.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    g32 = gamma.astype(jnp.float32)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean[None, :]) * inv[None, :]
    if act:
        pre = xhat * g32[None, :] + beta.astype(jnp.float32)[None, :]
        dy = dy * (pre > 0)
    dgamma = jnp.sum(dy * xhat, axis=0)
    dbeta = jnp.sum(dy, axis=0)
    dxhat = dy * g32[None, :]
    dx = (inv[None, :] / r) * (
        r * dxhat
        - jnp.sum(dxhat, axis=0)[None, :]
        - xhat * jnp.sum(dxhat * xhat, axis=0)[None, :])
    return (dx.astype(x2d.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype))


fused_norm_act.defvjp(_fused_fwd, _fused_bwd)


__all__ = ['fused_norm_act', 'reference_norm_act']
