"""Sweep flash-attention backward block shapes on the real chip.

The pre-elision sweep (round 3) measured larger backward blocks 2-5x
slower — but that included the causally-dead k/v tile DMA the clamped
index maps now elide. Re-sweep fwd+bwd at the flagship shape
(B=1, H=16, T=8192, D=64, bf16) to pick backward defaults.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import functools  # noqa: E402
import time  # noqa: E402

# first, before jax: the package bootstrap places the compile cache
import mlcomp_tpu  # noqa: E402,F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mlcomp_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention_backward, flash_attention_forward,
)

B, H, T, D = 1, 16, 8192, 64
REPS = 10


def main():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    q, k, v, do = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                   for kk in ks)

    fwd = jax.jit(functools.partial(
        flash_attention_forward, causal=True, with_lse=True))
    out, lse = fwd(q, k, v)
    jax.block_until_ready(out)

    def timer(fn, *args):
        # fetching a VALUE is the barrier (same rule as bench.py)
        float(jnp.sum(fn(*args)[0].astype(jnp.float32)))
        best = float('inf')
        for _ in range(3):
            t0 = time.perf_counter()
            acc = None
            for _ in range(REPS):
                r = fn(*args)
                acc = r[0] if acc is None else acc + r[0]
            float(jnp.sum(acc.astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / REPS)
        return best * 1e3

    ms = timer(fwd, q, k, v)
    print(f'forward (bq512/bk1024): {ms:6.2f} ms', flush=True)

    for bq, bk in ((512, 512), (512, 1024), (1024, 512),
                   (1024, 1024), (256, 1024), (2048, 512)):
        try:
            bwd = jax.jit(functools.partial(
                flash_attention_backward, causal=True,
                block_q=bq, block_k=bk))
            ms = timer(bwd, q, k, v, out, lse, do)
            print(f'backward bq={bq:4d} bk={bk:4d}: {ms:6.2f} ms',
                  flush=True)
        except Exception as e:
            print(f'backward bq={bq:4d} bk={bk:4d}: ERR '
                  f'{str(e)[:90]}', flush=True)


if __name__ == '__main__':
    main()
