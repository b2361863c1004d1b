"""The ``task`` kind at a tiny size on the CPU: the runner off jax's
device, children through ``python -m mlcomp_tpu execute``; the result
line, and ``correct`` false once the child's timed path is broken."""

import os

import pytest

from benchmark import rehearse
from test_benchmark_run import ROOT, tiny


TASK = 'resnet18-cifar10.short-task'

#: planted in the CHILD (``python -m mlcomp_tpu execute``) through a
#: sitecustomize on its PYTHONPATH: the runner cannot reach into it
CHILD_FAULTS = {
    'unchanged_updates': (
        'import optax\n'
        'optax.apply_updates = lambda params, updates: params\n'),
    'half_batch': (
        'import mlcomp_tpu.train.loop as loop\n'
        'def half(fn):\n'
        '    def loss(logits, labels, weights=None):\n'
        '        n = logits.shape[0] // 2\n'
        '        return fn(logits[:n], labels[:n],\n'
        '                  None if weights is None else weights[:n])\n'
        '    return loss\n'
        'loop.LOSSES["softmax_ce"] = half(loop.LOSSES["softmax_ce"])\n'),
}


@pytest.fixture(scope='module')
def child_cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp('xla_children'))


def run_task_cell(tmp_path, child_cache, fault=None):
    sizes = tiny(TASK)
    # the children (warm task, measured task, reference) each compile
    # the same programs: let them share what they compiled
    sizes['cell']['child_env']['JAX_COMPILATION_CACHE_DIR'] = child_cache
    sizes['cell']['child_env']['MLCOMP_TPU_ROOT'] = \
        os.environ.get('MLCOMP_TPU_ROOT') or __import__(
            'mlcomp_tpu').ROOT_FOLDER
    if fault:
        site = tmp_path / 'site'
        site.mkdir()
        (site / 'sitecustomize.py').write_text(CHILD_FAULTS[fault])
        sizes['cell']['child_env']['PYTHONPATH'] = os.pathsep.join(
            [str(site), ROOT])
    return rehearse.rehearse(TASK, seed=2_500_000_017, seconds=0.5,
                             tiny=sizes, out=str(tmp_path))


def test_task_result_line(tmp_path, child_cache):
    line = run_task_cell(tmp_path, child_cache)
    assert line['correct'] is True, line['compared']
    assert line['attempted'] >= 1 and line['failed'] == 0
    assert {'cpu_rehearsal.task_wall_s', 'cpu_rehearsal.setup_s'} \
        == set(line['metrics'])
    assert list(line)[-1] == 'compared'


@pytest.mark.parametrize('fault', sorted(CHILD_FAULTS))
def test_a_broken_task_is_not_correct(fault, tmp_path, child_cache):
    line = run_task_cell(tmp_path, child_cache, fault)
    assert line['correct'] is False
    assert any(not value <= limit for _, value, limit in line['compared'])
