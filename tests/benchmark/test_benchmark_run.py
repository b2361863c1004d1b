"""A whole run at a tiny size on the CPU, skipping only the harness's
look for a chip: the result line's keys; `correct` true for the program
as it is and false once the timed path is broken underneath (a step that
returns its state unchanged; half of the batch left out); the control
(the reference in the nearest lower precision) comes out not correct;
and the real entry refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import calibrate, correct, rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ['resnet18-cifar10.steady', 'olmo-1b.steady']


def tiny(workload):
    """The rehearsal's sizes, in float32 so that the CPU's numbers are
    sharp, with limits that fit that size."""
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal',
                           f'{workload}.json')) as fh:
        out = json.load(fh)
    out['config']['executor']['model']['dtype'] = 'float32'
    out['config']['executor']['mesh'] = {'dp': 1}
    # the norms over all leaves: at this size single norm leaves of the
    # first ResNet stage swing by tens of per cent on rounding alone
    out['cell']['limits'] = {'loss_gap': 5e-3, 'grad_gap_all_leaves': 0.05,
                             'delta_gap_all_leaves': 0.1}
    return out


@pytest.fixture(scope='module', autouse=True)
def _compile_once(tmp_path_factory):
    """A run compiles every program twice (warm job, measured job) and
    the tests below repeat runs: keep compiled programs for the length
    of this file, then put jax back as it was."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ('jax_compilation_cache_dir',
             'jax_persistent_cache_min_compile_time_secs',
             'jax_persistent_cache_min_entry_size_bytes')
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path_factory.mktemp('xla')))
    jax.config.update(names[1], 0.5)
    jax.config.update(names[2], 0)
    compilation_cache.reset_cache()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def run(workload, tmp_path, **kwargs):
    return rehearse.rehearse(workload, seed=2_500_000_011, seconds=0.5,
                             tiny=tiny(workload), out=str(tmp_path),
                             **kwargs)


@pytest.mark.parametrize('workload', CELLS)
def test_result_line(workload, tmp_path):
    line = run(workload, tmp_path)
    assert list(line)[:5] == ['correct', 'attempted', 'failed',
                              'metrics', 'device']
    assert list(line)[-1] == 'compared'
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] >= 1
    assert set(line['device']) == {'platform', 'kind', 'count',
                                   'memory_peak_bytes'}
    # a rehearsal never prints under a device metric's name
    assert all(k.startswith('cpu_rehearsal.') for k in line['metrics'])
    assert 'cpu_rehearsal.setup_s' in line['metrics']
    for name, value, limit in line['compared']:
        assert value <= limit, (name, value, limit)
    assert json.loads(json.dumps(line)) == line


def _unchanged_updates(monkeypatch):
    """A step that returns its state unchanged: the update is dropped."""
    import mlcomp_tpu.train.loop as loop
    monkeypatch.setattr(loop.optax, 'apply_updates',
                        lambda params, updates: params)


def _half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    import mlcomp_tpu.train.loop as loop

    def half(fn):
        def loss(logits, labels, weights=None):
            n = logits.shape[0] // 2
            return fn(logits[:n], labels[:n],
                      None if weights is None else weights[:n])
        return loss

    for name in ('softmax_ce', 'lm_ce'):
        monkeypatch.setitem(loop.LOSSES, name, half(loop.LOSSES[name]))


@pytest.mark.parametrize('fault', [_unchanged_updates, _half_batch])
@pytest.mark.parametrize('workload', CELLS)
def test_a_broken_timed_path_is_not_correct(workload, fault, tmp_path,
                                            monkeypatch):
    fault(monkeypatch)
    line = run(workload, tmp_path)
    assert line['correct'] is False
    assert any(not value <= limit for _, value, limit in line['compared'])


@pytest.mark.parametrize('workload', CELLS)
def test_the_control_is_not_correct(workload, tmp_path, monkeypatch):
    """The reference in float8 operands, put in the program's place at
    the test's size, fails the limits the sound run passes."""
    from benchmark import run as harness
    kept = []
    original = harness.result_line

    def keep(this):
        kept.append(this)
        return original(this)

    monkeypatch.setattr(harness, 'result_line', keep)
    line = run(workload, tmp_path)
    assert line['correct'] is True
    this = kept[0]
    family = this.manifest.reference(this.config['reference'])
    job = this.extra['reference_inputs'][0]
    params, feeds = harness.reference_inputs(
        family, this.seed, *this.extra['reference_inputs'])
    ref = family.train(job, params, feeds)
    control = family.train(job, params, feeds, operands='float8')
    gaps = correct.training_gaps(calibrate.as_program(control), ref,
                                 lambda m: m)
    ok, compared = correct.judge(gaps, this.cell['limits'])
    assert ok is False, compared


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               MLCOMP_TPU_ROOT=os.path.join(ROOT, '.bench_out', 'none'))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', CELLS[0], '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert 'no accelerator' in proc.stderr
    assert 'metrics' not in proc.stdout


def test_judge_needs_every_number():
    limits = {'loss_gap': 0.1, 'grad_gap': 0.1}
    assert correct.judge({'loss_gap': 0.01, 'grad_gap': 0.01}, limits)[0]
    assert not correct.judge({'loss_gap': 0.01}, limits)[0]
    assert not correct.judge(
        {'loss_gap': float('nan'), 'grad_gap': 0.0}, limits)[0]
    assert not correct.judge({}, {})[0]

