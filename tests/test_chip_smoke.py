"""CPU rehearsal of ``chip_smoke.py`` (the chip's bring-up proof): the
no-fallback rule pinned, and its dag and serve phases driven at a tiny
size so wrong paths, arguments and waits are found here and not on the
chip. The steering (tiny model and data, an emulated core, the CPU
platform) lives HERE, not in options of the script."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_no_accelerator_no_result():
    """Run as it is where jax finds no TPU: non-zero exit at the device
    phase and no ``"ok": true`` line — it never falls back."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'chip_smoke.py')],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "platform 'cpu'" in proc.stdout, proc.stdout
    assert 'phase device' not in proc.stdout, proc.stdout


def test_parent_stays_off_jax():
    """The parent owns no chip: importing the script (and the package
    it leans on) must not import jax."""
    code = ('import sys, chip_smoke, mlcomp_tpu; '
            'assert "jax" not in sys.modules, "jax imported"')
    subprocess.run([sys.executable, '-c', code], cwd=REPO, check=True,
                   timeout=60, env=dict(os.environ, JAX_PLATFORMS='cpu'))


def test_dag_and_serve_phases_on_cpu(tmp_path):
    dataset = {'name': 'cifar10', 'n_train': 512, 'n_valid': 128}
    config = chip_smoke.resnet_config()
    train = config['executors']['train']
    assert train['cores'] == 1 and train['batch_size'] == 512
    assert config['executors']['infer']['cores'] == 1
    train.update(
        model={'name': 'mlp', 'num_classes': 10, 'hidden': [32],
               'dtype': 'float32'},
        batch_size=64,
        # a profiler window and the compiled-step attribution are
        # forced on so the CPU run writes the rows the chip must
        telemetry={'profile_every': 4, 'memory_analysis': True})
    train.pop('report_imgs')
    for spec in config['executors'].values():
        spec['dataset'] = dict(dataset)
    ctx = chip_smoke.Ctx(
        str(tmp_path / 'smoke'), platform='cpu',
        env={'JAX_PLATFORMS': 'cpu', 'MLCOMP_TPU_CORES': '1'})
    tasks = chip_smoke.phase_dag(
        ctx, config, expect_cores=1,
        expect_series=('devtime.busy_frac', 'memory.attribution'),
        timeout=300)
    assert set(tasks) == {'train', 'infer', 'valid'}
    # the coreless valid task was pinned off the accelerator
    assert tasks['valid']['cores_assigned'] == '[]'
    health = chip_smoke.phase_serve(ctx, dataset=dataset, batches=2,
                                    batch_size=16)
    assert health['platform'] == 'cpu'
