"""Device-resident input pipeline (bench honesty work, VERDICT item 4):
quantization, on-device augmentation, indexed/scanned train steps,
prefetch, cifar10 loader."""

import numpy as np
import pytest


class TestQuantize:
    def test_float01_packs_uint8(self):
        from mlcomp_tpu.train.device_data import quantize_dataset
        x = np.random.rand(10, 4, 4, 3).astype(np.float32)
        q, dq = quantize_dataset(x)
        assert q.dtype == np.uint8 and dq
        np.testing.assert_allclose(q / 255.0, x, atol=1 / 255)

    def test_uint8_passthrough(self):
        from mlcomp_tpu.train.device_data import quantize_dataset
        x = (np.random.rand(4, 2, 2, 3) * 255).astype(np.uint8)
        q, dq = quantize_dataset(x)
        assert q is x and dq

    def test_out_of_range_float_kept(self):
        from mlcomp_tpu.train.device_data import quantize_dataset
        x = np.random.randn(4, 2, 2, 3).astype(np.float32) * 10
        q, dq = quantize_dataset(x)
        assert q.dtype == np.float32 and not dq


class TestAugmentSpec:
    def test_device_expressible(self):
        from mlcomp_tpu.train.device_data import normalize_augment_spec
        spec = normalize_augment_spec(
            ['hflip', {'name': 'pad_crop', 'pad': 4}])
        assert spec == [('hflip', {}), ('pad_crop', {'pad': 4})]
        assert normalize_augment_spec(None) == []
        assert normalize_augment_spec(['transpose']) is None


class TestDeviceAugment:
    def test_shapes_and_determinism(self):
        import jax
        from mlcomp_tpu.train.device_data import make_device_augment
        aug = make_device_augment(
            [('pad_crop', {'pad': 2}), ('hflip', {}),
             ('cutout', {'size': 4})], (8, 8, 3))
        x = np.random.rand(6, 8, 8, 3).astype(np.float32)
        out1 = np.asarray(aug(x, jax.random.PRNGKey(0)))
        out2 = np.asarray(aug(x, jax.random.PRNGKey(0)))
        out3 = np.asarray(aug(x, jax.random.PRNGKey(1)))
        assert out1.shape == x.shape
        np.testing.assert_array_equal(out1, out2)
        assert not np.array_equal(out1, out3)

    def test_hflip_p1_flips_everything(self):
        import jax
        from mlcomp_tpu.train.device_data import make_device_augment
        aug = make_device_augment([('hflip', {'p': 1.0})], (4, 4, 3))
        x = np.random.rand(3, 4, 4, 3).astype(np.float32)
        out = np.asarray(aug(x, jax.random.PRNGKey(0)))
        np.testing.assert_allclose(out, x[:, :, ::-1, :])


def _clone(state):
    """Deep-copy device buffers — donating jits delete their inputs, so
    comparing two step variants needs independent states."""
    import jax.numpy as jnp
    import jax
    return jax.tree.map(lambda a: jnp.array(np.asarray(a))
                        if isinstance(a, jax.Array) else a, state)


class TestIndexedSteps:
    def _setup(self, mesh):
        import jax
        from mlcomp_tpu.models import create_model
        from mlcomp_tpu.train import (
            create_train_state, loss_for_task, make_optimizer,
        )
        model = create_model('mlp', num_classes=4, hidden=[16],
                             dtype='float32')
        opt, _ = make_optimizer({'name': 'sgd', 'lr': 0.1}, 100)
        x = np.random.rand(64, 4, 4, 1).astype(np.float32)
        y = np.random.randint(0, 4, 64).astype(np.int32)
        state = create_train_state(model, opt, x[:8],
                                   jax.random.PRNGKey(0), mesh=mesh)
        return model, opt, x, y, state, loss_for_task('softmax_ce')

    def test_device_step_matches_host_step(self):
        """Same batch, same params: indexed device step must produce the
        same loss as the host-batch step."""
        import jax
        from mlcomp_tpu.parallel import mesh_from_spec
        from mlcomp_tpu.parallel.sharding import batch_sharding
        from mlcomp_tpu.train import make_train_step
        from mlcomp_tpu.train.data import place_batch
        from mlcomp_tpu.train.device_data import place_dataset
        from mlcomp_tpu.train.loop import make_device_train_step

        mesh = mesh_from_spec({'dp': -1})
        model, opt, x, y, state, loss_fn = self._setup(mesh)
        state2 = _clone(state)

        host_step = make_train_step(model, opt, loss_fn, mesh=mesh)
        dev_step = make_device_train_step(model, opt, loss_fn, mesh=mesh)
        x_all, y_all = place_dataset(x, y, mesh)
        idx = np.arange(32, dtype=np.int32)

        xb, yb = place_batch((x[:32], y[:32]), mesh)
        _, m_host = host_step(state, xb, yb)
        _, m_dev = dev_step(
            state2, x_all, y_all,
            jax.device_put(idx, batch_sharding(mesh, 1)))
        assert float(m_host['loss']) == pytest.approx(
            float(m_dev['loss']), rel=1e-5)

    def test_dequantize_matches_float(self):
        import jax
        from mlcomp_tpu.parallel import mesh_from_spec
        from mlcomp_tpu.parallel.sharding import batch_sharding
        from mlcomp_tpu.train.device_data import (
            place_dataset, quantize_dataset,
        )
        from mlcomp_tpu.train.loop import make_device_train_step

        mesh = mesh_from_spec({'dp': -1})
        model, opt, x, y, state, loss_fn = self._setup(mesh)
        x = np.round(x * 255) / 255  # exactly representable
        state2 = _clone(state)
        idx = jax.device_put(np.arange(16, dtype=np.int32),
                             batch_sharding(mesh, 1))

        xf_all, y_all = place_dataset(x.astype(np.float32), y, mesh)
        plain = make_device_train_step(model, opt, loss_fn, mesh=mesh)
        _, m_f = plain(state, xf_all, y_all, idx)

        xq, dq = quantize_dataset(x)
        assert dq
        xq_all, y_all2 = place_dataset(xq, y, mesh)
        quant = make_device_train_step(model, opt, loss_fn, mesh=mesh,
                                       dequantize=True)
        _, m_q = quant(state2, xq_all, y_all2, idx)
        assert float(m_f['loss']) == pytest.approx(
            float(m_q['loss']), rel=1e-4)


def _echo_setup(mesh):
    """(model, optimizer, state) of a model that hands its input back
    as the logits; with ``_echo_loss``, which hands them on as a
    metric, what a step returns is the very batch it built from the
    resident set."""
    import flax.linen as nn
    import jax
    from mlcomp_tpu.train import create_train_state, make_optimizer

    class Echo(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            self.param('w', nn.initializers.zeros, ())
            return x

    model = Echo()
    opt, _ = make_optimizer({'name': 'sgd', 'lr': 0.1}, 10)
    state = create_train_state(
        model, opt, np.zeros((8, 2), np.float32),
        jax.random.PRNGKey(0), mesh=mesh)
    return model, opt, state


def _echo_loss(logits, y, weights=None):
    import jax.numpy as jnp
    return jnp.zeros((), jnp.float32), {'batch': logits, 'labels': y}


def _resident_rows(case):
    """(rows as the dataset gives them, device augment spec)."""
    rng = np.random.RandomState(7)
    crop_flip = [('pad_crop', {'pad': 2}), ('hflip', {})]
    if case == 'uint8_images':
        return rng.randint(0, 256, (64, 8, 8, 3)).astype(np.uint8), \
            crop_flip
    if case == 'float_images_outside_01':
        return (rng.randn(64, 8, 8, 3) * 10).astype(np.float32), \
            crop_flip
    return rng.randn(64, 24).astype(np.float32), []     # rank-1 rows


class TestFlatResidentSet:
    """`place_dataset` holds rows flat and the step makers reshape the
    gathered batch only (device_data.gather_rows): the model must see
    the rows it saw when the set was held in the dataset's own shape."""

    N, B = 64, 16

    def _mesh(self, devices):
        import jax
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:devices]), ('dp',))

    def _run(self, kind, mesh, x_all, y_all, perm, **kw):
        """The batches one maker's step builds for the rows ``perm``
        ([steps, B]), stacked."""
        import jax
        from mlcomp_tpu.parallel.sharding import batch_sharding
        from mlcomp_tpu.train.loop import (
            make_device_eval_step, make_device_train_step,
        )
        model, opt, state = _echo_setup(mesh)
        sh1 = batch_sharding(mesh, 1)
        if kind == 'eval':
            kw.pop('augment', None)
            fn = make_device_eval_step(model, _echo_loss, mesh=mesh,
                                       **kw)
            w = jax.device_put(np.ones(self.B, np.float32), sh1)
            out = [fn(state, x_all, y_all, jax.device_put(idx, sh1), w)
                   for idx in perm]
        else:
            fn = make_device_train_step(model, opt, _echo_loss,
                                        mesh=mesh, **kw)
            out = []
            for idx in perm:
                state, m = fn(state, x_all, y_all,
                              jax.device_put(idx, sh1))
                out.append(m)
        return (np.stack([np.asarray(m['batch']) for m in out]),
                np.stack([np.asarray(m['labels']) for m in out]))

    @pytest.mark.parametrize('devices', [1, 8])
    @pytest.mark.parametrize('kind', ['train', 'eval'])
    @pytest.mark.parametrize('case', [
        'uint8_images', 'float_images_outside_01', 'rank1_rows'])
    def test_batch_is_the_original_rows(self, case, kind, devices):
        import jax
        from mlcomp_tpu.parallel.sharding import replicated
        from mlcomp_tpu.train.device_data import (
            make_device_augment, place_dataset, quantize_dataset,
        )
        x, augs = _resident_rows(case)
        y = np.arange(self.N, dtype=np.int32)
        mesh = self._mesh(devices)
        perm = np.random.RandomState(3).permutation(self.N).astype(
            np.int32).reshape(-1, self.B)
        x_q, dequant = quantize_dataset(x)
        assert x_q is x and dequant == (case == 'uint8_images')
        x_all, y_all = place_dataset(x_q, y, mesh)
        assert x_all.shape == (self.N, int(np.prod(x.shape[1:])))

        # the gathered rows, nothing else done to them: bit for bit
        # x[idx] of the array the dataset gave, in idx's order
        got, labels = self._run(kind, mesh, x_all, y_all, perm,
                                row_shape=x.shape[1:])
        assert got.dtype == x.dtype
        np.testing.assert_array_equal(got, x[perm])
        np.testing.assert_array_equal(labels, perm)

        # as the executor builds the step (augment, dequantize): the
        # same bits as from the set held in its own shape, which is
        # the step as it was before the set was held flat
        kw = dict(dequantize=dequant)
        if augs:
            kw['augment'] = make_device_augment(augs, x.shape[1:])
        held_as_given = jax.device_put(x, replicated(mesh))
        want, _ = self._run(kind, mesh, held_as_given, y_all, perm,
                            **kw)
        got, _ = self._run(kind, mesh, x_all, y_all, perm,
                           row_shape=x.shape[1:], **kw)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
        if augs and kind != 'eval':
            assert not np.array_equal(
                got, x[perm] / (255.0 if dequant else 1.0))

    def test_lowered_gather_reads_a_rank2_set(self):
        """Structural guard: a resident set of rank 4 made the v5e
        compiler copy ALL of it into another layout in every step
        (PERF.md, PR 26). Nothing in the lowered train step may have
        the set's leading dimension and a rank above 2."""
        import re
        import jax
        from mlcomp_tpu.parallel.sharding import batch_sharding
        from mlcomp_tpu.train.device_data import (
            make_device_augment, place_dataset,
        )
        from mlcomp_tpu.train.loop import make_device_train_step
        x, augs = _resident_rows('uint8_images')
        mesh = self._mesh(1)
        x_all, y_all = place_dataset(
            x, np.zeros(self.N, np.int32), mesh)
        model, opt, state = _echo_setup(mesh)
        step = make_device_train_step(
            model, opt, _echo_loss, mesh=mesh, dequantize=True,
            augment=make_device_augment(augs, x.shape[1:]),
            row_shape=x.shape[1:])
        text = step.lower(
            state, x_all, y_all, jax.ShapeDtypeStruct(
                (self.B,), np.int32,
                sharding=batch_sharding(mesh, 1))).as_text()
        gathers = re.findall(
            r'stablehlo\.gather"?\(.*?:\s*\(tensor<([0-9x]+)x\w+>',
            text)
        operands = [tuple(int(d) for d in g.split('x'))
                    for g in gathers]
        assert (self.N, 8 * 8 * 3) in operands, operands
        whole_set = set(re.findall(
            r'tensor<(%dx[0-9x]+)x\w+>' % self.N, text))
        assert whole_set == {'%dx%d' % (self.N, 8 * 8 * 3)}, whole_set


class TestExecutorSelection:
    def test_jax_train_device_path_with_augment_runs(self, tmp_path):
        """auto path + on-device augmentation runs end to end (the
        synthetic iid-noise prototypes are NOT shift-invariant, so no
        accuracy bar here — test_train's test_mlp_learns covers learning
        through the same device path without augmentation)."""
        from test_train import DummyStep
        from mlcomp_tpu.train import JaxTrain
        ex = JaxTrain(
            model={'name': 'mlp', 'num_classes': 4, 'hidden': [32],
                   'dtype': 'float32'},
            dataset={'name': 'synthetic_images', 'n_train': 256,
                     'n_valid': 64, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            batch_size=64, epochs=2,
            augment=[{'name': 'pad_crop', 'pad': 1}, 'hflip'],
            checkpoint_dir=str(tmp_path / 'ck'))
        ex.step = DummyStep()
        ex.task = None
        ex.session = None
        ex.additional_info = {}
        result = ex.work()
        assert result['best_score'] is not None
        assert np.isfinite(result['best_score'])

    def test_host_path_when_augment_not_device_expressible(self,
                                                           tmp_path):
        from test_train import DummyStep
        from mlcomp_tpu.train import JaxTrain
        ex = JaxTrain(
            model={'name': 'mlp', 'num_classes': 4, 'hidden': [16],
                   'dtype': 'float32'},
            dataset={'name': 'synthetic_images', 'n_train': 128,
                     'n_valid': 32, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            batch_size=32, epochs=1,
            augment=['transpose'],     # not in DEVICE_AUGMENTS
            checkpoint_dir=str(tmp_path / 'ck'))
        ex.step = DummyStep()
        ex.task = None
        ex.session = None
        ex.additional_info = {}
        result = ex.work()
        assert result['best_score'] is not None

    @pytest.mark.parametrize('option, value', [
        ('epoch_scan', True), ('log_every', 10)])
    def test_a_removed_option_is_an_unknown_key(self, option, value,
                                                tmp_path, monkeypatch):
        """The whole-epoch-scan switch and ``log_every`` are gone: a
        config that still sets one is told so, loudly, and trains on
        the per-step path like any other."""
        from test_train import DummyStep
        import mlcomp_tpu.train.loop as loop
        from mlcomp_tpu.train import JaxTrain

        dispatches = []
        make = loop.make_device_train_step

        def counted(*args, **kwargs):
            step = make(*args, **kwargs)

            def call(state, *feed):
                dispatches.append(len(feed))
                return step(state, *feed)
            return call

        monkeypatch.setattr(loop, 'make_device_train_step', counted)
        ex = JaxTrain(
            model={'name': 'mlp', 'num_classes': 4, 'hidden': [16],
                   'dtype': 'float32'},
            dataset={'name': 'synthetic_images', 'n_train': 128,
                     'n_valid': 32, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            batch_size=32, epochs=2, device_data=True,
            checkpoint_dir=str(tmp_path / 'ck'), **{option: value})
        assert not hasattr(ex, option)
        ex.step = DummyStep()
        said = []
        ex.step.info = said.append
        ex.task = None
        ex.session = None
        ex.additional_info = {}
        result = ex.work()
        assert result['best_score'] is not None
        warned = [m for m in said if m.startswith('WARNING: config keys')]
        assert len(warned) == 1 and repr([option]) in warned[0]
        # a dispatch a step, each fed (x_all, y_all, idx)
        assert dispatches == [3] * (2 * 128 // 32)


    @pytest.mark.parametrize('device_data', [True, False])
    def test_the_dropped_tail_is_said_once_a_dispatch(self, device_data,
                                                      tmp_path):
        """Both input paths go through one epoch loop: the samples that
        fill no batch are named in the first epoch a dispatch trains,
        fresh or resumed, and in no later one."""
        from test_train import DummyStep
        from mlcomp_tpu.train import JaxTrain

        def dispatch(epochs):
            ex = JaxTrain(
                model={'name': 'mlp', 'num_classes': 4, 'hidden': [16],
                       'dtype': 'float32'},
                dataset={'name': 'synthetic_images', 'n_train': 100,
                         'n_valid': 32, 'image_size': 8, 'channels': 1,
                         'num_classes': 4},
                batch_size=32, epochs=epochs, device_data=device_data,
                checkpoint_dir=str(tmp_path / 'ck'))
            ex.step = DummyStep()
            said = []
            ex.step.info = said.append
            ex.task = None
            ex.session = None
            ex.additional_info = {}
            ex.work()
            return said

        said = dispatch(2)
        assert len([m for m in said if 'dropping 4 tail' in m]) == 1
        said = dispatch(4)      # resumes at epoch 2, trains 2 more
        assert any(m.startswith('resumed from checkpoint') for m in said)
        assert len([m for m in said if 'dropping 4 tail' in m]) == 1
        assert len([m for m in said if '] epoch ' in m]) == 2


class TestDataHelpers:
    def test_prefetch_preserves_order_and_count(self):
        from mlcomp_tpu.parallel import mesh_from_spec
        from mlcomp_tpu.train.data import iterate_batches, prefetch_batches
        mesh = mesh_from_spec({'dp': -1})
        x = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        got = list(prefetch_batches(
            iterate_batches(x, None, 8), mesh))
        assert len(got) == 4
        np.testing.assert_array_equal(np.asarray(got[0][0]), x[:8])
        np.testing.assert_array_equal(np.asarray(got[-1][0]), x[24:])

    def test_iterate_batches_logs_dropped_tail(self):
        from mlcomp_tpu.train.data import iterate_batches
        messages = []
        list(iterate_batches(np.zeros((10, 2)), None, 4,
                             logger=messages.append))
        assert any('dropping 2 tail samples' in m for m in messages)

    def test_cifar10_loader_real_npz(self, tmp_path, monkeypatch):
        from mlcomp_tpu.train.data import create_dataset
        x = (np.random.rand(20, 32, 32, 3) * 255).astype(np.uint8)
        y = np.arange(20) % 10
        path = tmp_path / 'cifar10.npz'
        np.savez(path, x_train=x, y_train=y, x_test=x[:5], y_test=y[:5])
        monkeypatch.setenv('CIFAR10_NPZ', str(path))
        data = create_dataset('cifar10')
        assert data['source'] == str(path)
        assert data['x_train'].shape == (20, 32, 32, 3)
        assert data['x_train'].max() <= 1.0

    def test_cifar10_loader_synthetic_fallback(self):
        from mlcomp_tpu.train.data import create_dataset
        data = create_dataset('cifar10', n_train=64, n_valid=16)
        assert data['source'] == 'synthetic'
        assert data['x_train'].shape == (64, 32, 32, 3)


class TestAggregateMetrics:
    def test_mean_and_weighted(self):
        import jax.numpy as jnp
        from mlcomp_tpu.train.loop import aggregate_metrics
        ms = [{'loss': jnp.asarray(1.0), 'acc': jnp.asarray(0.5)},
              {'loss': jnp.asarray(3.0), 'acc': jnp.asarray(1.0)}]
        agg = aggregate_metrics(ms)
        assert agg == {'loss': 2.0, 'acc': 0.75}
        weighted = aggregate_metrics(ms, weights=[3, 1])
        assert weighted['loss'] == pytest.approx(1.5)
        assert aggregate_metrics([]) == {}


class TestDeviceEval:
    def test_device_eval_matches_host_eval(self):
        """Indexed HBM-resident eval == the host-batch eval step,
        including zero-weight tail padding."""
        import jax
        from mlcomp_tpu.models import create_model
        from mlcomp_tpu.parallel import mesh_from_spec
        from mlcomp_tpu.parallel.sharding import batch_sharding
        from mlcomp_tpu.train import (
            create_train_state, loss_for_task, make_optimizer,
        )
        from mlcomp_tpu.train.data import place_batch
        from mlcomp_tpu.train.device_data import place_dataset
        from mlcomp_tpu.train.loop import (
            make_device_eval_step, make_eval_step,
        )
        mesh = mesh_from_spec({'dp': -1})
        model = create_model('mlp', num_classes=4, hidden=[16],
                             dtype='float32')
        opt, _ = make_optimizer({'name': 'sgd', 'lr': 0.1}, 10)
        loss_fn = loss_for_task('softmax_ce')
        x = np.random.rand(20, 4, 4, 1).astype(np.float32)
        y = np.random.randint(0, 4, 20).astype(np.int32)
        state = create_train_state(model, opt, x[:8],
                                   jax.random.PRNGKey(0), mesh=mesh)
        x_all, y_all = place_dataset(x, y, mesh)
        # a padded tail batch: 4 real rows padded to 8, zero weights
        take = np.resize(np.arange(16, 20), 8)
        w = np.array([1, 1, 1, 1, 0, 0, 0, 0], np.float32)
        w_dev = jax.device_put(w, batch_sharding(mesh, 1))
        dev = make_device_eval_step(model, loss_fn, mesh=mesh)
        m_dev = dev(state, x_all, y_all,
                    jax.device_put(take.astype(np.int32),
                                   batch_sharding(mesh, 1)), w_dev)
        host = make_eval_step(model, loss_fn, mesh=mesh)
        xb, yb = place_batch((x[take], y[take]), mesh)
        m_host = host(state, xb, yb, w_dev)
        for k in m_host:
            assert float(m_dev[k]) == pytest.approx(float(m_host[k]),
                                                    rel=1e-6), k


class TestCheckpointCadence:
    def test_last_of_stage_always_saved(self, tmp_path):
        """Even with a huge checkpoint_every, the stage's final epoch
        writes `last` (resume/export depend on it)."""
        import os
        from test_train import DummyStep
        from mlcomp_tpu.train import JaxTrain
        from mlcomp_tpu.train.checkpoint import load_meta
        ex = JaxTrain(
            model={'name': 'mlp', 'num_classes': 4, 'hidden': [16],
                   'dtype': 'float32'},
            dataset={'name': 'synthetic_images', 'n_train': 128,
                     'n_valid': 32, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            batch_size=32, epochs=3, checkpoint_every=1000,
            checkpoint_dir=str(tmp_path / 'ck'))
        ex.step = DummyStep()
        ex.task = None
        ex.session = None
        ex.additional_info = {}
        ex.work()
        assert os.path.exists(tmp_path / 'ck' / 'last.msgpack')
        meta = load_meta(str(tmp_path / 'ck'))
        assert meta['stage_epoch'] == 2  # the stage's FINAL epoch

    def test_resume_after_cadenced_run(self, tmp_path):
        from test_train import DummyStep
        from mlcomp_tpu.train import JaxTrain

        def run(epochs):
            ex = JaxTrain(
                model={'name': 'mlp', 'num_classes': 4, 'hidden': [16],
                       'dtype': 'float32'},
                dataset={'name': 'synthetic_images', 'n_train': 128,
                         'n_valid': 32, 'image_size': 8, 'channels': 1,
                         'num_classes': 4},
                batch_size=32, checkpoint_every=1000,
                stages=[{'name': 's1', 'epochs': epochs,
                         'optimizer': {'name': 'adam', 'lr': 3e-3}}],
                checkpoint_dir=str(tmp_path / 'ck'))
            ex.step = DummyStep()
            ex.task = None
            ex.session = None
            ex.additional_info = {}
            return ex.work()

        run(2)
        # re-run with more epochs: resumes past the 2 completed ones
        result = run(4)
        assert result['best_score'] is not None


def test_augment_wide_integer_pixels_exact():
    """Integer pixel data wider than 1 byte survives augmentation
    bit-exactly with its dtype preserved — the crop takes the native-
    dtype gather path (no float dtype could hold int32 > 2^24), not
    the bf16 MXU fast path reserved for 1-byte dtypes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mlcomp_tpu.train.device_data import make_device_augment

    x = jnp.asarray(np.random.RandomState(0).randint(
        0, 65536, (4, 8, 8, 1)), jnp.uint16)
    aug = make_device_augment([('hflip', {'p': 0.0})], (8, 8))
    out = aug(x, jax.random.PRNGKey(0))
    assert out.dtype == jnp.uint16
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    # pad_crop with zero displacement range is also an exact copy
    aug2 = make_device_augment([('pad_crop', {'pad': 0})], (8, 8))
    out2 = aug2(x, jax.random.PRNGKey(1))
    assert out2.dtype == jnp.uint16
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(x))

    # int32 beyond f32's 2^24 integer range: no float dtype could hold
    # these — the gather crop and dtype-agnostic flips must stay exact
    big = 2 ** 24 + 1
    xi = jnp.full((2, 8, 8, 1), big, jnp.int32)
    for spec in ([('hflip', {'p': 0.0})], [('pad_crop', {'pad': 2})],
                 [('cutout', {'size': 2, 'p': 0.0})]):
        oi = make_device_augment(spec, (8, 8))(xi, jax.random.PRNGKey(2))
        assert oi.dtype == jnp.int32
        assert int(oi.max()) == big and int(oi.min()) == big, spec
