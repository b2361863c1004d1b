"""Time the flash-attention kernels alone on the chip, at the shapes of
the four token cells of ``BENCHMARK.json``.

    python scripts/flash_probe.py [--module other/flash_attention.py]
                                  [--strips 1,2,4] [--block 512,1024]

Prints one JSON line a (shape, variant): forward (with lse) and backward
in ms — the wall time of the call with its layout copies, and the Pallas
kernels' own device time from a trace — and the latter as a share of
197 TFLOP/s on the FLOPs causal attention REQUIRES (2 products forward,
4 backward over T (T + 1) / 2 pairs, one of a pair as deep as the score
head and one as wide as the value head), so two commits' lines compare.
The operands arrive as the cell's projections lay them out (``SHAPES``'
last column, read from the compiled step, PR 36): ``tminor`` is a
[B, T, H, D] array whose tokens are minor (a head's [D, T] panel — what
XLA makes of q_proj, k_proj, v_proj and kv_b_proj), ``rows`` one whose
head size is (OLMo's fused qkv); dq, dk and dv leave the same way. So
the wall time holds the copies the cell's step holds round the kernels,
and no others.
``--module`` times another checkout's file (the parent's, unpacked
beside this one) on the same chip; ``--strips`` and ``--block`` sweep
the strips a diagonal tile is walked in and the tile, by overriding the
file's own rule (which takes no argument) for both kernels.
"""
import argparse
import functools
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# first, before jax: the package bootstrap places the compile cache
import mlcomp_tpu  # noqa: E402,F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

PEAK = 197e12
REPS = 20
# (batch, tokens, query heads, key-value heads, score head size, value
# head size, how the cell's projections lay q, k, v out)
SHAPES = {
    'olmo-1b': (4, 2048, 16, 16, 128, 128, 'rows'),
    'lfm2-8b-a1b': (2, 8192, 32, 8, 64, 64, 'tminor'),
    'qwen3-next-80b-a3b': (2, 8192, 16, 2, 256, 256, 'tminor'),
    'kanana-2-30b-a3b': (2, 8192, 32, 32, 192, 128, 'tminor'),
    # the CPU rehearsals (interpret mode)
    'tiny': (1, 256, 2, 1, 128, 128, 'rows'),
    'tiny-unequal': (1, 256, 2, 2, 192, 128, 'tminor'),
}


def laid_out(fn, layout, n_in, grads=False):
    """``fn`` over q, k, v (its first ``n_in`` arguments are the three)
    given as the producers lay them out: ``tminor`` arrays are held as
    [B, H, D, T] and turned to [B, T, H, D] inside the jitted call, so
    the kernels' own fold is what XLA has to copy, or not; ``grads``
    leave the same way."""
    if layout == 'rows':
        return fn

    def call(*args):
        args = [jnp.transpose(a, (0, 3, 1, 2)) if i < n_in else a
                for i, a in enumerate(args)]
        out = fn(*args)
        return tuple(jnp.transpose(g, (0, 2, 3, 1)) for g in out) \
            if grads else out
    return call


def load(path):
    if not path:
        from mlcomp_tpu.ops import flash_attention
        return flash_attention
    spec = importlib.util.spec_from_file_location('flash_other', path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float('inf')
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / REPS)
    return best * 1e3


def kernel_ms(fn, *args):
    """Device time of the Pallas kernels alone (the `custom-call` events
    of the trace's `XLA Ops` line) in one call of ``fn``: what the
    roofline readers see, without the layout copies round the kernels
    that the wall time holds. None where no device plane is written."""
    import glob
    import shutil
    import tempfile
    from jax.profiler import ProfileData
    folder = tempfile.mkdtemp(prefix='flash_probe_')
    try:
        with jax.profiler.trace(folder):
            for _ in range(REPS):
                out = fn(*args)
            jax.block_until_ready(out)
        files = glob.glob(os.path.join(
            folder, 'plugins', 'profile', '*', '*.xplane.pb'))
        total = 0
        for plane in ProfileData.from_file(files[0]).planes:
            if not plane.name.startswith('/device:TPU:0'):
                continue
            for line in plane.lines:
                if line.name == 'XLA Ops':
                    total += sum(ev.duration_ns for ev in line.events
                                 if 'custom-call' in ev.name)
        return total / REPS / 1e6 if total else None
    finally:
        shutil.rmtree(folder, ignore_errors=True)


def share(need, ms):
    return round(100 * need / (ms * 1e-3) / PEAK, 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--module', default='')
    ap.add_argument('--strips', default='')
    ap.add_argument('--block', default='')
    ap.add_argument('--shapes', default=','.join(list(SHAPES)[:4]))
    args = ap.parse_args()
    fa = load(args.module)
    rule = getattr(fa, '_strips', None)
    counts = [int(x) for x in args.strips.split(',') if x] or [None]
    blocks = [int(x) for x in args.block.split(',') if x] or [None]
    for name in args.shapes.split(','):
        b, t, h, h_kv, d, dv, layout = SHAPES[name]
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (
            jax.random.normal(kk, (b, t, heads, width), jnp.bfloat16)
            for kk, heads, width in zip(ks, (h, h_kv, h_kv, h),
                                        (d, d, dv, dv)))
        # the operands as the producers hold them
        held = [jnp.transpose(x, (0, 2, 3, 1)) if layout == 'tminor'
                else x for x in (q, k, v)]
        # forward FLOPs
        need = 2 * (t * (t + 1) // 2) * (d + dv) * b * h
        for block in blocks:
            for strips in counts:
                if strips is not None:
                    fa._strips = lambda *_sizes, n=strips: n
                elif rule is not None:
                    fa._strips = rule
                kw = {'interpret': jax.default_backend() != 'tpu'}
                if block is not None:
                    kw.update(block_q=block, block_k=block)
                line = {'shape': name, 'module': args.module or 'this',
                        'layout': layout, 'block': block, 'strips': strips}
                plain = jax.jit(functools.partial(
                    fa.flash_attention_forward, causal=True,
                    with_lse=True, interpret=kw['interpret']))
                out, lse = plain(q, k, v)
                try:
                    fwd = jax.jit(laid_out(functools.partial(
                        fa.flash_attention_forward, causal=True,
                        with_lse=True, **kw), layout, 3))
                    f_ms = timed(fwd, *held)
                    line.update(fwd_ms=round(f_ms, 4))
                    f_ms = kernel_ms(fwd, *held)
                    if f_ms:
                        line.update(fwd_kernel_ms=round(f_ms, 4),
                                    fwd_pct=share(need, f_ms))
                except Exception as e:  # a tile the compiler refuses
                    line['fwd_error'] = str(e)[:300]
                try:
                    bwd = jax.jit(laid_out(functools.partial(
                        fa.flash_attention_backward, causal=True, **kw),
                        layout, 3, grads=True))
                    b_ms = timed(bwd, *held, out, lse, do)
                    grads = jax.jit(functools.partial(
                        fa.flash_attention_backward, causal=True, **kw))(
                            q, k, v, out, lse, do)
                    line.update(
                        bwd_ms=round(b_ms, 4),
                        # to compare two files' results on one input
                        sums=[float(jnp.sum(jnp.abs(
                            x.astype(jnp.float32))))
                            for x in (out,) + tuple(grads)])
                    b_ms = kernel_ms(bwd, *held, out, lse, do)
                    if b_ms:
                        line.update(bwd_kernel_ms=round(b_ms, 4),
                                    bwd_pct=share(2 * need, b_ms))
                except Exception as e:
                    line['bwd_error'] = str(e)[:300]
                print(json.dumps(line), flush=True)


if __name__ == '__main__':
    main()
