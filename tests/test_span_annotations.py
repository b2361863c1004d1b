"""One clock for the program's spans and the device trace: ``span()``
opens a profiler annotation of the same name once a training process
has installed the factory (and imports no jax otherwise), and
``JaxTrain`` leaves the set-up and epoch-phase spans the readers under
``benchmark/layer_metrics/`` and ``PERF.md`` name — as DB rows for every
epoch, and on a host line of a ``jax.profiler`` trace."""

import json
import os
import subprocess
import sys

import pytest

from mlcomp_tpu.db.models import Dag, Task
from mlcomp_tpu.db.providers import (
    DagProvider, ProjectProvider, TaskProvider, TelemetrySpanProvider,
)
from mlcomp_tpu.telemetry import spans
from mlcomp_tpu.utils.misc import now

PHASES = ['train.epoch.begin', 'train.epoch.steps', 'train.epoch.drain',
          'train.epoch.valid', 'train.epoch.report',
          'train.epoch.checkpoint']


# ------------------------------------------------------------ the factory
class FakeAnnotation:
    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(('open', self.name))

    def __exit__(self, *exc):
        self.log.append(('close', self.name))


@pytest.fixture()
def annotation_log():
    log = []
    before = spans._annotation_factory
    spans.set_annotation_factory(lambda name: FakeAnnotation(log, name))
    yield log
    spans.set_annotation_factory(before)


def test_span_opens_one_annotation_of_its_name(annotation_log):
    buf = spans.SpanBuffer()
    with spans.span('outer', buffer=buf):
        with spans.span('outer.inner', buffer=buf):
            pass
    assert annotation_log == [
        ('open', 'outer'), ('open', 'outer.inner'),
        ('close', 'outer.inner'), ('close', 'outer')]
    # the rows are what they were: the annotation adds nothing to them
    inner, outer = buf.drain()
    assert inner['parent_id'] == outer['span_id']
    assert set(inner) == {'span_id', 'parent_id', 'task', 'name',
                          'started', 'duration', 'status', 'tags',
                          'trace_id', 'process_role'}


def test_annotation_closes_on_exception(annotation_log):
    buf = spans.SpanBuffer()
    with pytest.raises(ValueError):
        with spans.span('boom', buffer=buf):
            raise ValueError('x')
    assert annotation_log == [('open', 'boom'), ('close', 'boom')]
    assert buf.drain()[0]['status'] == 'error'
    # the next span parents to nothing: the stack was unwound
    with spans.span('after', buffer=buf):
        pass
    assert buf.drain()[0]['parent_id'] is None


def test_no_factory_no_annotation_and_no_jax():
    """The daemons import spans.py and must never bring up jax."""
    src = ('import sys\n'
           'from mlcomp_tpu.telemetry import spans\n'
           'assert spans._annotation_factory is None\n'
           'with spans.span("daemon.tick"):\n'
           '    pass\n'
           'assert len(spans.DEFAULT_BUFFER) == 1\n'
           'assert "jax" not in sys.modules, "jax was imported"\n')
    env = {**os.environ, 'MLCOMP_TPU_KEEP_ROOT': '1'}
    proc = subprocess.run([sys.executable, '-c', src], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------------------ the program
class QuietStep:
    def start(self, *a, **k):
        pass

    def info(self, m):
        pass

    debug = error = info

    def end_all(self):
        pass


def make_task(session):
    provider = ProjectProvider(session)
    if provider.by_name('p_spans') is None:
        provider.add_project('p_spans')
    dag = Dag(name='d', project=provider.by_name('p_spans').id,
              config='', created=now(), docker_img='default')
    DagProvider(session).add(dag)
    task = Task(name='t', executor='e', dag=dag.id, status=0)
    TaskProvider(session).add(task)
    return task


def tiny_job(session, ck_dir, epochs=3, **kwargs):
    from mlcomp_tpu.train import JaxTrain
    task = make_task(session)
    ex = JaxTrain(
        model={'name': 'mlp', 'hidden': [16], 'num_classes': 4},
        dataset={'name': 'synthetic_images', 'n_train': 512,
                 'n_valid': 64, 'image_size': 8, 'channels': 1,
                 'num_classes': 4},
        loss='softmax_ce', batch_size=32, epochs=epochs,
        checkpoint_dir=str(ck_dir), **kwargs)
    ex.step = QuietStep()
    ex.task = task
    ex.dag = DagProvider(session).by_id(task.dag)
    ex.session = session
    ex.additional_info = {}
    return ex, task


INPUT_PATHS = {
    'device_data': dict(device_data=True,
                        telemetry={'cost_analysis': True}),
    'host_prefetch': dict(device_data=False,
                          telemetry={'cost_analysis': True}),
    'device_data_no_checkpoint': dict(device_data=True,
                                      checkpoint_every=0),
}


@pytest.mark.parametrize('path', sorted(INPUT_PATHS))
def test_epoch_and_setup_spans_of_a_job(path, session, tmp_path):
    ex, task = tiny_job(session, tmp_path / 'ck', **INPUT_PATHS[path])
    ex.work()
    rows = sorted(TelemetrySpanProvider(session).by_task(task.id),
                  key=lambda r: (r.started, r.id))
    (work,) = [r for r in rows if r.name == 'train.work']
    assert work.parent_id is None and work.process_role == 'train'

    setup = [r for r in rows if r.name.startswith('train.setup.')]
    want = ['train.setup.data', 'train.setup.state']
    if 'cost_analysis' in (INPUT_PATHS[path].get('telemetry') or {}):
        # the AOT compile of the step, once a job
        want.append('train.setup.introspect')
    assert [r.name for r in setup] == want
    assert all(r.parent_id == work.span_id for r in setup)

    epochs = [r for r in rows if r.name == 'train.epoch']
    assert [json.loads(r.tags) for r in epochs] == [
        {'epoch': e, 'stage': 'stage1'} for e in range(3)]
    phases = PHASES if 'no_checkpoint' not in path else PHASES[:-1]
    for epoch in epochs:
        assert epoch.parent_id == work.span_id
        assert epoch.status == 'ok' and epoch.process_role == 'train'
        children = [r for r in rows if r.parent_id == epoch.span_id]
        assert [r.name for r in children] == phases
        # one after the other, inside the parent
        for a, b in zip(children, children[1:]):
            assert a.started + a.duration <= b.started + 1e-3
        assert children[0].started >= epoch.started - 1e-3
    # the phases ARE the epoch: its self time is the few clock reads
    # between them (summed over the job, so that one scheduler hiccup
    # in a 10 ms epoch of a loaded test box decides nothing)
    covered = sum(r.duration for r in rows
                  if r.parent_id in {e.span_id for e in epochs})
    assert covered >= 0.95 * sum(e.duration for e in epochs)
    # every span of the job is closed and parents inside the job
    ids = {r.span_id for r in rows}
    assert all(r.parent_id in ids for r in rows if r is not work)
    assert spans.current_span_id() is None


def test_two_stages_tag_their_epochs_and_compile_the_step_once(
        session, tmp_path):
    """The stage loop around the phases: an epoch's span names its
    stage and counts epochs across stages, and the introspection
    compile is the first stage's alone."""
    stages = [{'name': 'warm', 'epochs': 1,
               'optimizer': {'name': 'sgd', 'lr': 0.1}},
              {'name': 'main', 'epochs': 2,
               'optimizer': {'name': 'adam', 'lr': 1e-3}}]
    ex, task = tiny_job(session, tmp_path / 'ck', stages=stages,
                        device_data=True,
                        telemetry={'cost_analysis': True})
    result = ex.work()
    assert result['stage'] == 'main'
    rows = sorted(TelemetrySpanProvider(session).by_task(task.id),
                  key=lambda r: (r.started, r.id))
    assert [json.loads(r.tags) for r in rows
            if r.name == 'train.epoch'] == [
        {'epoch': 0, 'stage': 'warm'}, {'epoch': 1, 'stage': 'main'},
        {'epoch': 2, 'stage': 'main'}]
    assert [r.name for r in rows if r.name.startswith('train.setup.')] \
        == ['train.setup.data', 'train.setup.state',
            'train.setup.introspect']
    assert all(r.status == 'ok' for r in rows)


def test_the_introspection_counts_the_steps_kernels(session, tmp_path,
                                                    monkeypatch):
    """``step.kernel_calls``: one gauge a job, the `tpu_custom_call`s
    of the compiled train step (none on the CPU; with two named in the
    text, two); beside it ``step.flash_layout_copies``, the copies the
    flash op's layout code left in it (``layout_copies``)."""
    from jax import stages
    from mlcomp_tpu.db.providers.telemetry import MetricProvider

    def job(name):
        ex, task = tiny_job(session, tmp_path / name, epochs=1,
                            device_data=True,
                            telemetry={'cost_analysis': True})
        ex.work()
        return [MetricProvider(session).recent_values(task.id, key)
                for key in ('step.kernel_calls',
                            'step.flash_layout_copies')]

    assert job('cpu') == [[0.0], [0.0]]
    as_text = stages.Compiled.as_text
    copy = ('  %copy.1 = bf16[2,8,4]{1,2,0} copy(%p), metadata={op_name='
            '"jit(step)/attn/flash_layout/transpose"}\n')
    monkeypatch.setattr(
        stages.Compiled, 'as_text', lambda self, *a, **k:
        as_text(self, *a, **k) + 'tpu_custom_call\ntpu_custom_call\n'
        + copy)
    assert job('two') == [[2.0], [1.0]]


def test_a_deepseek_v3_job_writes_its_counters_and_the_kernel_gauge(
        session, tmp_path):
    """The latent-attention decoder through ``JaxTrain``: a row an epoch
    of ``mla_attn.rows`` (tokens x attention layers a step) and of the
    three ``moe.*`` series, and ONE ``step.kernel_calls`` gauge a job —
    0 on the CPU; on the chip the step of ``kanana-2-30b-a3b.steady``
    holds 46 (``tests/test_chip_compile.py`` counts a layer's: 2 flash
    calls, and 9 grouped products in a sparse layer; ``PERF.md``
    section 3); beside it ``step.wide_scatters``, 0: at these shapes (a
    buffer row for every pair) the sparse layers gather."""
    values = deepseek_job(session, tmp_path)
    assert values('step.kernel_calls') == [0.0]
    assert values('step.flash_layout_copies') == [0.0]
    assert values('step.wide_scatters') == [0.0]
    assert values('mla_attn.rows') == [4 * 16 * 3.0] * 2
    assert values('moe.dropped') == [0.0] * 2
    assert len(values('moe.local_assign_share')) == 2
    assert all(v >= 1 for v in values('moe.load_max_over_mean'))
    assert values('short_conv.rows') == []


def test_a_deepseek_v3_job_counts_the_scatters_it_is_made_to_take(
        session, tmp_path, monkeypatch):
    """With the rule's constant at 0 every sparse layer scatters: the
    gauge counts two a layer (the combine and the dispatch's gradient),
    four for the job's two sparse layers."""
    from mlcomp_tpu.models import decoder_parts
    monkeypatch.setattr(decoder_parts, 'SCATTER_ROW_COST', 0.0)
    values = deepseek_job(session, tmp_path)
    assert values('step.wide_scatters') == [4.0]
    assert values('moe.dropped') == [0.0] * 2


def deepseek_job(session, tmp_path):
    """Run a small deepseek_v3 job; returns its metric values by name."""
    from mlcomp_tpu.db.providers.telemetry import MetricProvider
    from mlcomp_tpu.train import JaxTrain
    task = make_task(session)
    ex = JaxTrain(
        model={'name': 'deepseek_v3', 'vocab_size': 64, 'd_model': 32,
               'n_layers': 3, 'n_dense_layers': 1, 'd_ff': 48,
               'n_heads': 2, 'kv_lora_rank': 16, 'qk_nope_head_dim': 8,
               'qk_rope_head_dim': 4, 'v_head_dim': 8, 'n_experts': 8,
               'top_k': 2, 'd_expert': 8, 'experts_held': 4,
               'expert_bias_update_rate': 0.001, 'dtype': 'float32'},
        dataset={'name': 'synthetic_lm', 'n_train': 32, 'n_valid': 8,
                 'seq_len': 16, 'vocab_size': 64},
        loss='lm_ce', batch_size=4, epochs=2, mesh={'dp': 1},
        checkpoint_dir=str(tmp_path / 'ck'), checkpoint_every=0,
        telemetry={'cost_analysis': True})
    ex.step, ex.task, ex.session = QuietStep(), task, session
    ex.dag, ex.additional_info = DagProvider(session).by_id(task.dag), {}
    ex.work()
    return lambda name: MetricProvider(session).recent_values(
        task.id, name)


def _planted(*args, **kwargs):
    raise RuntimeError('planted')


def _plant_in_begin(ex, monkeypatch):
    # the step rows of epoch 1 fail
    def start(level, name, index):
        if name == 'epoch 1':
            raise RuntimeError('planted')

    ex.step.start = start


def _plant_in_report(ex, monkeypatch):
    ex._report_series = _planted


#: phase -> how a fault is planted in it: (module, attribute) to replace
#: with a call that raises (or that builds a step that raises), or a
#: function that plants it
FAULTS = {
    'train.setup.data': ('mlcomp_tpu.train.executor', 'create_dataset'),
    'train.setup.state': ('mlcomp_tpu.train.executor',
                          'create_train_state'),
    'train.setup.introspect': ('mlcomp_tpu.telemetry',
                               'memory_attribution'),
    'train.epoch.begin': _plant_in_begin,
    'train.epoch.steps': ('mlcomp_tpu.train.loop',
                          'make_device_train_step', 'built'),
    'train.epoch.drain': ('mlcomp_tpu.train.executor',
                          'aggregate_metrics'),
    'train.epoch.valid': ('mlcomp_tpu.train.loop',
                          'make_device_eval_step', 'built'),
    'train.epoch.report': _plant_in_report,
    'train.epoch.checkpoint': ('mlcomp_tpu.train.ckpt_shard',
                               'state_needs_sharded_ckpt'),
}


@pytest.mark.parametrize('phase', sorted(FAULTS))
def test_an_exception_closes_the_open_phases_as_errors(
        phase, session, tmp_path, monkeypatch):
    import importlib
    ex, task = tiny_job(session, tmp_path / 'ck', epochs=2,
                        device_data=True,
                        telemetry={'memory_analysis': True})
    fault = FAULTS[phase]
    if callable(fault):
        fault(ex, monkeypatch)
    else:
        module, name, *built = fault
        monkeypatch.setattr(
            importlib.import_module(module), name,
            (lambda *args, **kwargs: _planted) if built else _planted)
    with pytest.raises(RuntimeError, match='planted'):
        ex.work()
    rows = TelemetrySpanProvider(session).by_task(task.id)
    # exactly that phase and its parents, and nothing is left open
    parents = {'train.work'} | (
        {'train.epoch'} if phase.startswith('train.epoch.') else set())
    assert {r.name for r in rows
            if r.status == 'error'} == parents | {phase}
    assert len([r for r in rows if r.status == 'error']) \
        == len(parents) + 1
    assert all(r.duration is not None for r in rows)
    assert spans.current_span_id() is None


def test_without_telemetry_no_span_is_opened(session, tmp_path):
    ex, task = tiny_job(session, tmp_path / 'ck', epochs=1,
                        telemetry=False)
    spans.DEFAULT_BUFFER.drain()        # what earlier tests left
    ex.work()
    assert TelemetrySpanProvider(session).by_task(task.id) == []
    assert len(spans.DEFAULT_BUFFER) == 0


# -------------------------------------------------------------- the trace
def test_a_profiler_trace_of_an_epoch_holds_the_phase_names(session,
                                                            tmp_path):
    """The benchmark's traced epoch, on the CPU: opened and closed at the
    executor's epoch seam, it holds one whole ``train.epoch`` with its
    phases on a host line, between the runner's two marks."""
    from benchmark import hooks, program_spans, trace_reduce
    ex, task = tiny_job(session, tmp_path / 'ck', epochs=3,
                        device_data=True)
    probe = hooks.Probe(seed=7, trace_dir=str(tmp_path / 'trace'),
                        trace_epoch=1)
    with hooks.installed(probe):
        ex.work()
    assert probe.trace_marks and probe.trace_marks[1] is not None
    trace = trace_reduce.load_xplane(
        trace_reduce.find_xplane(probe.trace_dir))
    lo, hi = trace_reduce.marks(trace)
    notes = program_spans.annotations(trace, lo, hi)
    assert [n[0] for n in notes] == ['train.epoch'] + PHASES
    (_, e_lo, e_hi), phases = notes[0], notes[1:]
    assert lo <= e_lo and e_hi <= hi
    for (_, a, b), (_, a2, _) in zip(phases, phases[1:]):
        assert e_lo <= a < b <= a2
    assert phases[-1][2] <= e_hi
    # the phases cover the annotation as they cover the row
    assert sum(b - a for _, a, b in phases) >= 0.95 * (e_hi - e_lo)
    # and only the seam lies outside the epoch: a small part of it
    assert (e_hi - e_lo) >= 0.5 * (hi - lo)
    # no device plane on the CPU: the reader says nothing, not zero
    assert program_spans.idle_by_annotation(trace) is None
