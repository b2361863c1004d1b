"""Attribute the CIFAR ResNet-18 roofline residual on the real chip.

docs/performance.md derives a 0.61 memory-bound MFU ceiling and the
measured 0.51 sits at 84% of it; this probe bills the residual by
ablation (XLA's cost analysis is aggregate; the device trace is
reduced by telemetry/trace_parse.py and is the better instrument once
ROADMAP S1 lands):

  full       : the production train step (bs=512, bf16)
  remat      : residual blocks under nn.remat — recompute activations
               in the backward instead of writing+reading them (trades
               FLOPs for HBM bytes; promising exactly because the
               step is memory-bound)
  no_bn      : BatchNorm replaced by identity — bills BN's statistics
               + elementwise HBM traffic
  fwd_only   : forward pass alone

Each variant reports ms/step and XLA's cost-analysis bytes/FLOPs, so
the bytes-vs-time correlation is explicit.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import time  # noqa: E402

# first, before jax: the package bootstrap places the compile cache
import mlcomp_tpu  # noqa: E402,F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BATCH = 512
STEPS = 30
PEAK = 197e12


def cost(fn, *args):
    try:
        c = fn.lower(*args).compile().cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return float(c.get('flops', 0)), float(
            c.get('bytes accessed', 0))
    except Exception:
        return None, None


def timed(fn, state, x, y, label, flops=None, bytes_=None):
    # state threads CONTINUOUSLY: the train step donates its input
    # state, so restarting a trial from a donated buffer poisons the
    # run (surfaces as an opaque backend error at the next fetch)
    s = state
    for _ in range(5):
        s, m = fn(s, x, y)
    float(m['loss'])
    best = float('inf')
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            s, m = fn(s, x, y)
        float(m['loss'])
        best = min(best, time.perf_counter() - t0)
    ms = best / STEPS * 1e3
    extra = ''
    if flops:
        mfu = flops * (1 / (best / STEPS)) / PEAK
        extra = (f'  {flops/1e12:.2f} TF  {bytes_/1e9:.2f} GB  '
                 f'mfu={mfu:.3f}  hbm_floor={bytes_/820e9*1e3:.1f} ms')
    print(f'{label:10s} {ms:7.2f} ms/step{extra}', flush=True)
    return ms


def main():
    import flax.linen as nn

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.models.resnet import BasicBlock, ResNet
    from mlcomp_tpu.parallel import mesh_from_spec
    from mlcomp_tpu.train import (
        create_train_state, loss_for_task, make_optimizer,
        make_train_step,
    )
    from mlcomp_tpu.train.data import create_dataset, place_batch

    mesh = mesh_from_spec({'dp': -1})
    optimizer, _ = make_optimizer(
        {'name': 'sgd', 'lr': 0.1, 'momentum': 0.9}, 1000)
    loss_fn = loss_for_task('softmax_ce')
    data = create_dataset('cifar10', n_train=BATCH * 2, n_valid=256)
    x_np, y_np = data['x_train'][:BATCH], data['y_train'][:BATCH]

    def build(model, label):
        state = create_train_state(model, optimizer, x_np[:1],
                                   jax.random.PRNGKey(0), mesh=mesh)
        step = make_train_step(model, optimizer, loss_fn, mesh=mesh)
        x, y = place_batch((x_np, y_np), mesh)
        ms = timed(step, state, x, y, label)
        f, b = cost(step, state, x, y)
        if f:
            mfu = f / (ms / 1e3) / PEAK
            print(f'           cost: {f/1e12:.2f} TF {b/1e9:.2f} GB '
                  f'mfu={mfu:.3f} hbm_floor={b/820e9*1e3:.1f} ms',
                  flush=True)

    build(create_model('resnet18', num_classes=10, dtype='bfloat16'),
          'full')
    build(ResNet(stage_sizes=[2, 2, 2, 2], block=nn.remat(BasicBlock),
                 num_classes=10, cifar_stem=True,
                 dtype=jnp.bfloat16), 'remat')
    # round-6 byte-count variants (the answers to the no_bn ablation
    # row below): the fused Pallas norm+act kernel, and no norm at all
    # (weight-standardized convs + SkipInit)
    norm_impl = os.environ.get('PROBE_FUSED_NORM_IMPL', 'pallas')
    try:
        build(create_model('resnet18', num_classes=10,
                           dtype='bfloat16', norm='fused',
                           norm_impl=norm_impl), 'fused')
    except Exception as e:
        print(f'fused      FAILED: {type(e).__name__}: {e}',
              flush=True)
    try:
        build(create_model('resnet18', num_classes=10,
                           dtype='bfloat16', norm='none'), 'ws_skip')
    except Exception as e:
        print(f'ws_skip    FAILED: {type(e).__name__}: {e}',
              flush=True)

    import mlcomp_tpu.models.resnet as R

    class _NoNorm(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x

    orig = R.norm_partial
    R.norm_partial = lambda dtype, train: (lambda **kw: _NoNorm())
    try:
        build(create_model('resnet18', num_classes=10,
                           dtype='bfloat16'), 'no_bn')
    finally:
        R.norm_partial = orig

    # forward only
    model = create_model('resnet18', num_classes=10, dtype='bfloat16')
    state = create_train_state(model, optimizer, x_np[:1],
                               jax.random.PRNGKey(0), mesh=mesh)
    x, y = place_batch((x_np, y_np), mesh)

    @jax.jit
    def fwd(s, x, y):
        logits = model.apply(
            {'params': s.params, 'batch_stats': s.batch_stats}, x,
            train=False)
        return s, {'loss': jnp.mean(logits)}
    f, b = cost(fwd, state, x, y)
    timed(fwd, state, x, y, 'fwd_only', f, b)


if __name__ == '__main__':
    main()
