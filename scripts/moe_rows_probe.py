"""Time the two ways ``SparseMoe`` moves rows between the tokens and its
expert-sorted buffer, on the chip, at the three sparse cells' shapes.

    python scripts/moe_rows_probe.py [--shapes lfm2-8b-a1b,...] [--layer 1]

Prints one JSON line a shape. ``gathers_ms`` / ``scatters_ms``: one
forward and backward of ``through_gathers`` / ``through_scatters``
(``models/decoder_parts.py``) with the experts left out (an identity the
compiler cannot see through), on bfloat16 rows of d_model, routing made
beforehand from a seeded router; ``*_row_us``: that time over the rows
each form moves — every (token, expert) pair's for the gathers, the
buffer's for the scatters — and ``scatter_row_cost`` the ratio of the
two (``SCATTER_ROW_COST``). ``slots_ms`` / ``slots_by_sort_ms``: the
buffer row of every pair by ``buffer_slots``' running count, and by a
second sort (the argsort of the full order); ``weights_gather_ms``: the
backward's gather of each buffer row's weight. Every time is the device
time of the jitted call (the trace's ``XLA Modules`` line), with the
wall time beside it; ``gap``: the two forms' largest difference in the
output and the gradients, over the largest value. ``--layer 1`` adds
the whole layer (``SparseMoe`` with the cell's configuration and the
grouped products) forward and backward in each form.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# first, before jax: the package bootstrap places the compile cache
import mlcomp_tpu  # noqa: E402,F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mlcomp_tpu.models import decoder_parts  # noqa: E402

REPS = 20
# tokens, d_model, experts, held, top-k, buffer rows (the cells' own)
SHAPES = {
    'lfm2-8b-a1b': (16384, 2048, 32, 8, 4, 65536),
    'kanana-2-30b-a3b': (16384, 2048, 128, 16, 6, 49152),
    'qwen3-next-80b-a3b': (16384, 2048, 512, 32, 10, 40960),
    # the CPU rehearsal
    'tiny': (256, 128, 16, 4, 4, 512),
}
CONFIGS = {name: f'benchmark/configs/{name}.json' for name in SHAPES}


def timed(fn, *args):
    """(device ms, wall ms) of one call of the jitted ``fn``."""
    jax.block_until_ready(fn(*args))
    best = float('inf')
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / REPS)
    return device_ms(fn, *args), round(best * 1e3, 4)


def device_ms(fn, *args):
    """The device time of one call: the ``XLA Modules`` events of the
    trace. None where no device plane is written (the CPU)."""
    from jax.profiler import ProfileData
    folder = tempfile.mkdtemp(prefix='moe_rows_probe_')
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
                if line.name == 'XLA Modules':
                    total += sum(ev.duration_ns for ev in line.events)
        return round(total / REPS / 1e6, 4) if total else None
    finally:
        shutil.rmtree(folder, ignore_errors=True)


def routing(key, n, experts, held, k, rows):
    """What ``SparseMoe`` hands the moves: each token's top-k of a
    seeded router's scores, weights renormalised, the pairs on the held
    experts [0, held) sorted by expert."""
    scores = jax.random.normal(key, (n, experts))
    top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(scores), k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    local = jnp.where(top_i < held, top_i, held).reshape(-1)
    order = jnp.argsort(local, stable=True)[:rows]
    sizes = jnp.bincount(local, length=held + 1)[:held]
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    return top_w, local, order, sizes, ends


def opaque(xs):
    return jax.lax.optimization_barrier(xs)


def moves(form):
    """fn(flat, top_w, local, order, sizes, ends, d_out) -> (out, d flat,
    d top_w): one form's forward and backward."""
    def fn(flat, top_w, local, order, sizes, ends, d_out):
        def f(flat, top_w):
            if form == 'gathers':
                out = decoder_parts.through_gathers(
                    flat, top_w, local, order, sizes, opaque, flat.dtype)
            else:
                out = decoder_parts.through_scatters(
                    flat, top_w, order, ends, opaque, flat.dtype)
            return out.astype(flat.dtype)
        out, pull = jax.vjp(f, flat, top_w)
        return (out,) + pull(d_out)
    return jax.jit(fn)


def gap(a, b):
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        worst = max(worst, float(jnp.max(jnp.abs(x - y))
                                 / jnp.maximum(jnp.max(jnp.abs(y)), 1e-30)))
    return worst


def layer_line(name, key, tokens=(2, 8192)):
    """The cell's whole ``SparseMoe`` forward and backward in each form
    (the form forced through ``SCATTER_ROW_COST``)."""
    import flax
    from mlcomp_tpu.models import create_model
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, CONFIGS[name])) as f:
        kwargs = json.load(f)['executor']['model']
    cfg = decoder_parts.MoeConfig.of(create_model(**kwargs).cfg)
    layer = decoder_parts.SparseMoe(cfg, name='moe')
    x = jax.random.normal(key, tokens + (cfg.d_model,), jnp.bfloat16)
    params = flax.core.meta.unbox(jax.jit(layer.init)(key, x)['params'])

    def loss(p, x):
        y = layer.apply({'params': p}, x, mutable=['intermediates'])[0]
        return (y.astype(jnp.float32) ** 2).sum()

    line, grads, kept = {'shape': name, 'what': 'layer'}, {}, \
        decoder_parts.SCATTER_ROW_COST
    try:
        for form, cost in (('gathers', float('inf')), ('scatters', 0.0)):
            decoder_parts.SCATTER_ROW_COST = cost
            step = jax.jit(jax.grad(loss, argnums=(0, 1)))
            grads[form] = step(params, x)
            line[f'{form}_ms'], line[f'{form}_wall_ms'] = timed(
                step, params, x)
    finally:
        decoder_parts.SCATTER_ROW_COST = kept
    line['gap'] = gap(grads['gathers'], grads['scatters'])
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--shapes', default=','.join(list(SHAPES)[:3]))
    ap.add_argument('--layer', type=int, default=0)
    args = ap.parse_args()
    for name in args.shapes.split(','):
        n, m, experts, held, k, rows = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(38), 4)
        top_w, local, order, sizes, ends = jax.jit(
            routing, static_argnums=(1, 2, 3, 4, 5))(
            keys[0], n, experts, held, k, rows)
        flat = jax.random.normal(keys[1], (n, m), jnp.bfloat16)
        d_out = jax.random.normal(keys[2], (n, m), jnp.bfloat16)
        line = {'shape': name, 'pairs': n * k, 'rows': rows,
                'landed': int(jnp.sum(sizes)), 'fitted': int(ends[-1]),
                'rule_gathers': decoder_parts.gathers_rows(n * k, rows)}
        args_ = (flat, top_w, local, order, sizes, ends, d_out)
        results = {}
        for form in ('gathers', 'scatters'):
            fn = moves(form)
            results[form] = fn(*args_)
            line[f'{form}_ms'], line[f'{form}_wall_ms'] = timed(fn, *args_)
        line['gap'] = gap(results['gathers'], results['scatters'])
        for form, count in (('gathers', n * k), ('scatters', rows)):
            ms = line[f'{form}_ms'] or line[f'{form}_wall_ms']
            line[f'{form}_row_us'] = round(ms * 1e3 / count, 6)
        line['scatter_row_cost'] = round(
            line['scatters_row_us'] / line['gathers_row_us'], 4)
        by_count = jax.jit(decoder_parts.buffer_slots, static_argnums=2)

        def by_sort(local, ends):
            at = jnp.argsort(jnp.argsort(local, stable=True))
            return jnp.where(at < ends[-1], at, rows)

        by_sort = jax.jit(by_sort)
        same = bool(jnp.all(by_count(local, sizes, rows)
                            == by_sort(local, ends)))
        line['slots_ms'], line['slots_wall_ms'] = timed(
            by_count, local, sizes, rows)
        line['slots_by_sort_ms'], line['slots_by_sort_wall_ms'] = timed(
            by_sort, local, ends)
        line['slots_agree'] = same
        weights = jax.jit(lambda w, order: w.reshape(-1)[order])
        line['weights_gather_ms'], line['weights_gather_wall_ms'] = timed(
            weights, top_w, order)
        print(json.dumps(line), flush=True)
        if args.layer:
            print(json.dumps(layer_line(name, keys[3])), flush=True)


if __name__ == '__main__':
    main()
