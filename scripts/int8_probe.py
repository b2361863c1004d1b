"""Probe int8 serving matmul variants on the real chip.

Variants at the serving shape (8-layer stack, K=N=8192, M=64):
  bf16       : plain x @ w chain (baseline)
  dense      : auto path (int8 -> bf16 convert inside dot_general)
  int8dot    : x quantized per-row to int8, int8 x int8 dot -> int32
  pallas     : per-op dequant-in-VMEM kernel
  stack_*    : fused whole-stack megakernel (ops/serving_stack.py)

Measurement rules live in ops/serving_stack.make_chain_runner (weights
as jit arguments, scan over reps, reps high enough to amortize the
per-call dispatch and result fetch).
"""
import os
import sys

# resolve the repo root by file location so the probe runs from any cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import time  # noqa: E402

# first, before jax: the package bootstrap places the compile cache
import mlcomp_tpu  # noqa: E402,F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mlcomp_tpu.ops.int8_matmul import (  # noqa: E402
    _pallas_int8_matmul, quantize_int8, reference_int8_matmul,
)
from mlcomp_tpu.ops.serving_stack import (  # noqa: E402
    make_chain_runner, serving_stack, stack_feed,
)

KN = 8192
LAYERS = 8
REPS = 100      # amortizes the per-call dispatch + result fetch
TRIALS = 5


def main():
    key = jax.random.PRNGKey(0)

    @jax.jit
    def make(k):
        w = jax.random.normal(k, (KN, KN), jnp.float32) * 0.02
        wq, sc = quantize_int8(w)
        return w.astype(jnp.bfloat16), wq, sc

    w_bf, packs = [], []
    for i in range(LAYERS):
        w, wq, sc = make(jax.random.fold_in(key, i))
        w_bf.append(w)
        packs.append((wq, sc))
    jax.block_until_ready((w_bf, packs))
    print('weights ready', flush=True)

    m = 64
    x0 = jax.random.normal(jax.random.fold_in(key, 99), (m, KN),
                           jnp.bfloat16)

    def per_layer(body, args):
        def step(x, *a):
            for i in range(LAYERS):
                x = stack_feed(body(x, i, *a))
            return x
        return make_chain_runner(step, args, x0, REPS)

    def int8dot(x, i, *flat):
        wq, sc = flat[2 * i], flat[2 * i + 1]
        xf = x.astype(jnp.float32)
        am = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
        xs = jnp.where(am > 0, am / 127.0, 1.0)
        xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            xq, wq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * xs * sc[None, :]

    flat_packs = [t for pack in packs for t in pack]
    variants = {
        'bf16': per_layer(lambda x, i, *ws: jnp.dot(
            x, ws[i], preferred_element_type=jnp.float32), w_bf),
        'dense': per_layer(
            lambda x, i, *flat: reference_int8_matmul(
                x, flat[2 * i], flat[2 * i + 1]), flat_packs),
        'int8dot': per_layer(int8dot, flat_packs),
    }
    for bn, bk in ((512, 4096), (2048, 2048)):
        variants[f'pallas_{bn}x{bk}'] = per_layer(
            lambda x, i, *flat, bn=bn, bk=bk: _pallas_int8_matmul(
                x, flat[2 * i], flat[2 * i + 1], bn, bk), flat_packs)

    wq_stack = jnp.stack([p[0] for p in packs])
    sc_stack = jnp.stack([p[1] for p in packs])
    w_stack_bf = jnp.stack([jnp.transpose(w) for w in w_bf])
    for bn, bk in ((1024, 2048), (1024, 4096), (512, 2048)):
        variants[f'stack_bf16_{bn}x{bk}'] = make_chain_runner(
            lambda x, w, bn=bn, bk=bk: stack_feed(serving_stack(
                x, w, block_n=bn, block_k=bk)), [w_stack_bf], x0, REPS)
        variants[f'stack_int8_{bn}x{bk}'] = make_chain_runner(
            lambda x, w, s, bn=bn, bk=bk: stack_feed(serving_stack(
                x, w, s, block_n=bn, block_k=bk)),
            [wq_stack, sc_stack], x0, REPS)

    good = {}
    for name, fn in variants.items():
        t0 = time.perf_counter()
        try:
            fn()
            good[name] = fn
            print(f'  [{name} compiled+warm '
                  f'{time.perf_counter()-t0:.1f}s]', flush=True)
        except Exception as e:
            print(f'  [{name} ERR {str(e)[:100]}]', flush=True)

    if 'bf16' not in good:
        raise SystemExit('bf16 baseline failed to compile — no '
                         'reference to compare against')
    base = good.pop('bf16')
    results = {name: [] for name in good}
    base_ts = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        base()
        b = time.perf_counter() - t0
        base_ts.append(b)
        for name, fn in good.items():
            t0 = time.perf_counter()
            fn()
            results[name].append((time.perf_counter() - t0, b))
    bmin = min(base_ts)
    print(f'bf16: min {bmin/REPS*1e3:.3f} ms/stack')
    for name, rows in results.items():
        ts = [r[0] for r in rows]
        ratios = sorted(r[1] / r[0] for r in rows)
        print(f'{name:22s} min={min(ts)/REPS*1e3:7.3f} ms/stk '
              f'min-ratio x{bmin/min(ts):5.3f} '
              f'paired med x{ratios[len(ratios)//2]:5.3f} '
              f'range [{ratios[0]:.3f}, {ratios[-1]:.3f}]')


if __name__ == '__main__':
    main()
