"""Telemetry subsystem tests: span nesting/flush, metric-series
persistence round trips, API endpoints, profiler control plane, and the
hot-path overhead guard."""

import json
import time
import urllib.request

import numpy as np
import pytest

from mlcomp_tpu import TOKEN
from mlcomp_tpu.db.models import Dag, Task
from mlcomp_tpu.db.providers import (
    DagProvider, MetricProvider, TaskProvider, TelemetrySpanProvider,
)
from mlcomp_tpu.telemetry import (
    Histogram, MetricRecorder, SpanBuffer, TaskProfiler, flush_spans,
    request_stop, request_trace, span, trace_status,
)
from mlcomp_tpu.utils.misc import now


def make_task(session, name='t'):
    from mlcomp_tpu.db.providers import ProjectProvider
    provider = ProjectProvider(session)
    project = provider.by_name('p_telemetry')
    if project is None:
        provider.add_project('p_telemetry')
        project = provider.by_name('p_telemetry')
    dag = Dag(name='d', project=project.id, config='', created=now(),
              docker_img='default')
    DagProvider(session).add(dag)
    task = Task(name=name, executor='e', dag=dag.id, status=0)
    TaskProvider(session).add(task)
    return task


class TestSpans:
    def test_nesting_and_flush(self, session):
        task = make_task(session)
        buf = SpanBuffer()
        with span('outer', task=task.id, buffer=buf) as outer:
            outer.tag('k', 'v')
            with span('inner', buffer=buf):
                time.sleep(0.01)
        assert flush_spans(session, buf) == 2
        provider = TelemetrySpanProvider(session)
        rows = provider.by_task(task.id)
        by_name = {r.name: r for r in rows}
        # inner inherits the task AND parents to outer automatically
        assert by_name['inner'].parent_id == by_name['outer'].span_id
        assert by_name['inner'].task == task.id
        assert by_name['inner'].duration >= 0.01
        assert by_name['outer'].duration >= by_name['inner'].duration
        tree = provider.tree(task.id)
        assert len(tree) == 1
        assert tree[0]['tags'] == {'k': 'v'}
        assert [c['name'] for c in tree[0]['children']] == ['inner']

    def test_error_status_recorded(self, session):
        task = make_task(session)
        buf = SpanBuffer()
        with pytest.raises(ValueError):
            with span('boom', task=task.id, buffer=buf):
                raise ValueError('x')
        flush_spans(session, buf)
        (row,) = TelemetrySpanProvider(session).by_task(task.id)
        assert row.status == 'error'

    def test_ring_bounds_and_drop_count(self):
        buf = SpanBuffer(capacity=4)
        for i in range(7):
            with span(f's{i}', buffer=buf):
                pass
        assert len(buf) == 4
        assert buf.dropped_count == 3
        names = [r['name'] for r in buf.drain()]
        assert names == ['s3', 's4', 's5', 's6']  # oldest dropped

    def test_flush_empty_and_sessionless(self, session):
        buf = SpanBuffer()
        assert flush_spans(session, buf) == 0
        with span('s', buffer=buf):
            pass
        assert flush_spans(None, buf) == 0


class TestMetrics:
    def test_series_round_trip_across_flush_boundary(self, session):
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=5)
        for i in range(12):     # crosses two auto-flush boundaries
            rec.series('loss', np.float32(1.0 - 0.05 * i), step=i)
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        points = series['loss']
        assert [p['step'] for p in points] == list(range(12))
        assert points[0]['value'] == pytest.approx(1.0)
        assert points[-1]['value'] == pytest.approx(0.45)

    def test_device_array_values_convert_at_flush(self, session):
        import jax.numpy as jnp
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             flush_every=10 ** 9)
        rec.series('loss', jnp.float32(0.25), step=0)
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        assert series['loss'][0]['value'] == pytest.approx(0.25)

    def test_counters_and_histograms_emit_summaries(self, session):
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             flush_every=10 ** 9)
        rec.count('dispatched', 3)
        rec.count('dispatched', 2)
        for v in (1.0, 2.0, 3.0, 4.0):
            rec.observe('lat_ms', v)
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        assert series['dispatched'][0]['value'] == 5.0
        assert series['dispatched'][0]['kind'] == 'counter'
        assert series['lat_ms.count'][0]['value'] == 4.0
        assert series['lat_ms.min'][0]['value'] == 1.0
        assert series['lat_ms.max'][0]['value'] == 4.0
        assert series['lat_ms.p50'][0]['value'] == pytest.approx(2.5)

    def test_sessionless_recorder_drops_and_counts(self):
        rec = MetricRecorder(flush_every=10 ** 9)
        rec.series('x', 1.0, step=0)
        assert rec.flush() == 0
        assert rec.dropped_count == 1

    def test_histogram_summary(self):
        h = Histogram()
        assert h.summary() == {}
        for v in range(100):
            h.observe(float(v))
        s = h.summary()
        assert s['count'] == 100
        assert s['mean'] == pytest.approx(49.5)
        assert s['p99'] >= 95


@pytest.fixture()
def api(session):
    from mlcomp_tpu.server.api import ApiServer
    server = ApiServer(host='127.0.0.1', port=0).start_background()
    base = f'http://127.0.0.1:{server.port}'

    def call(path, data=None, token=TOKEN, method='POST'):
        if method == 'GET':
            req = urllib.request.Request(base + path)
        else:
            req = urllib.request.Request(
                base + path, data=json.dumps(data or {}).encode(),
                headers={'Content-Type': 'application/json'})
        if token is not None:
            req.add_header('Authorization', token)
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    call.base = base    # raw-fetch routes (text /metrics) need the url
    yield call
    server.shutdown()


class TestApi:
    def _seed(self, session):
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        for i in range(4):
            rec.series('loss', 1.0 - 0.1 * i, step=i)
            rec.series('step_time_ms', 100.0 + i, step=i)
        rec.flush()
        buf = SpanBuffer()
        with span('task.pipeline', task=task.id, buffer=buf):
            with span('task.execute', buffer=buf):
                pass
        flush_spans(session, buf)
        return task

    def test_get_series(self, api, session):
        task = self._seed(session)
        out = api(f'/telemetry/series?task={task.id}', method='GET',
                  token=None)  # no-auth introspection tier
        assert out['task'] == task.id
        assert [p['value'] for p in out['series']['loss']] == \
            pytest.approx([1.0, 0.9, 0.8, 0.7])
        assert len(out['series']['step_time_ms']) == 4
        named = api(f'/telemetry/series?task={task.id}&name=loss',
                    method='GET', token=None)
        assert list(named['series']) == ['loss']

    def test_get_spans(self, api, session):
        task = self._seed(session)
        out = api(f'/telemetry/spans?task={task.id}', method='GET',
                  token=None)
        assert len(out['spans']) == 1
        root = out['spans'][0]
        assert root['name'] == 'task.pipeline'
        assert [c['name'] for c in root['children']] == ['task.execute']

    def test_post_routes(self, api, session):
        task = self._seed(session)
        out = api('/api/telemetry/series', {'task': task.id})
        assert 'loss' in out['series']
        out = api('/api/telemetry/spans', {'task': task.id})
        assert out['spans'][0]['name'] == 'task.pipeline'

    def test_spans_requires_task(self, api):
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/api/telemetry/spans', {})
        assert e.value.code == 400

    def test_non_integer_task_is_client_error(self, api):
        # GET args arrive as strings; garbage is the caller's 400,
        # not a 500 out of int()
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/telemetry/series?task=nope', method='GET', token=None)
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/api/telemetry/spans', {'task': 'nope'})
        assert e.value.code == 400

    def test_profile_toggle_requires_auth(self, api, session):
        import urllib.error
        task = self._seed(session)
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/api/telemetry/profile',
                {'task': task.id, 'action': 'start'}, token='wrong')
        assert e.value.code == 401
        out = api('/api/telemetry/profile',
                  {'task': task.id, 'action': 'start'})
        assert out['status'] == 'requested'
        out = api('/api/telemetry/profile',
                  {'task': task.id, 'action': 'status'})
        assert out['status'] == 'requested'


class TestProfilerControl:
    def test_request_trace_drives_worker_state_machine(self, session,
                                                       tmp_path):
        task = make_task(session)
        started, stopped = [], []
        prof = TaskProfiler(session, task.id, str(tmp_path),
                            tracer_start=started.append,
                            tracer_stop=lambda: stopped.append(True))
        assert prof.poll() is False            # nothing requested
        request_trace(session, task.id, max_epochs=2)
        assert prof.poll() is True             # starts the trace
        assert len(started) == 1
        assert trace_status(session, task.id)['status'] == 'tracing'
        assert prof.poll() is True             # epoch 1 of 2
        assert prof.poll() is False            # epoch 2 → auto stop
        assert stopped == [True]
        status = trace_status(session, task.id)
        assert status['status'] == 'done'
        assert status['epochs'] == 2

    def test_stop_request_wins_over_max_epochs(self, session, tmp_path):
        task = make_task(session)
        prof = TaskProfiler(session, task.id, str(tmp_path),
                            tracer_start=lambda d: None,
                            tracer_stop=lambda: None)
        request_trace(session, task.id, max_epochs=100)
        assert prof.poll() is True
        request_stop(session, task.id)
        assert prof.poll() is False
        assert trace_status(session, task.id)['status'] == 'done'

    def test_close_stops_open_trace(self, session, tmp_path):
        task = make_task(session)
        stopped = []
        prof = TaskProfiler(session, task.id, str(tmp_path),
                            tracer_start=lambda d: None,
                            tracer_stop=lambda: stopped.append(True))
        request_trace(session, task.id, max_epochs=100)
        prof.poll()
        prof.close()
        assert stopped == [True]
        assert trace_status(session, task.id)['status'] == 'done'


class TestTrainLoopWiring:
    def test_jax_train_records_per_step_series(self, session, tmp_path):
        """The acceptance-criterion path: a jax_train run records
        per-step loss + throughput from INSIDE the loop, queryable via
        the metric provider by task id."""
        from mlcomp_tpu.train import JaxTrain

        class DummyStep:
            def start(self, *a, **k):
                pass

            def info(self, m):
                pass

            def debug(self, m):
                pass

            def error(self, m):
                pass

            def end_all(self):
                pass

        task = make_task(session)
        ex = JaxTrain(
            model={'name': 'mlp', 'hidden': [16], 'num_classes': 4},
            dataset={'name': 'synthetic_images', 'n_train': 256,
                     'n_valid': 64, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            loss='softmax_ce', batch_size=32, epochs=2,
            telemetry={'flush_every': 16},
            checkpoint_dir=str(tmp_path / 'ck'))
        ex.step = DummyStep()
        ex.task = task
        ex.dag = DagProvider(session).by_id(task.dag)
        ex.session = session
        ex.additional_info = {}
        ex.work()

        series = MetricProvider(session).series(task_id=task.id)
        assert 'loss' in series and 'step_time_ms' in series
        # 2 epochs x 8 steps — every step's loss recorded in order
        assert [p['step'] for p in series['loss']] == list(range(16))
        # nobody read these; the step clock and the report series say it
        assert not {'throughput', 'epoch_time_s', 'epoch_throughput',
                    'compile.count', 'host_sync.suspect_count'} & set(series)

    def test_telemetry_false_disables_recording(self, session,
                                                tmp_path):
        from mlcomp_tpu.train import JaxTrain

        class DummyStep:
            def start(self, *a, **k):
                pass

            def info(self, m):
                pass

            def debug(self, m):
                pass

            def error(self, m):
                pass

            def end_all(self):
                pass

        task = make_task(session)
        ex = JaxTrain(
            model={'name': 'mlp', 'hidden': [8], 'num_classes': 4},
            dataset={'name': 'synthetic_images', 'n_train': 64,
                     'n_valid': 32, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            loss='softmax_ce', batch_size=32, epochs=1,
            telemetry=False, checkpoint_dir=str(tmp_path / 'ck'))
        ex.step = DummyStep()
        ex.task = task
        ex.dag = DagProvider(session).by_id(task.dag)
        ex.session = session
        ex.additional_info = {}
        ex.work()
        assert MetricProvider(session).series(task_id=task.id) == {}


class TestOverheadGuard:
    def test_instrumented_step_within_5pct_of_bare(self):
        """The telemetry hot path (perf_counter + 3 buffered appends)
        must be noise against a real step: instrumented = bare +
        wrapper cost, so the guard asserts the wrapper's isolated
        per-step cost is under 5% of the measured bare step time.
        (Differencing two timed loops cannot resolve a few-percent
        budget through this harness's ±10% scheduler drift — the same
        reason bench.py publishes ``telemetry_overhead_pct`` from the
        isolated measurement.)"""
        import jax
        import jax.numpy as jnp

        from mlcomp_tpu.train.loop import instrumented_step

        @jax.jit
        def step(state, x, y):
            return state, {'loss': jnp.sum(jnp.dot(x, x))}

        x = jnp.ones((512, 512), jnp.float32)
        step(0.0, x, None)          # compile
        bare = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(50):
                state, metrics = step(0.0, x, None)
            jax.block_until_ready(metrics['loss'])
            bare = min(bare, (time.perf_counter() - t0) / 50)

        # wrapper cost in isolation: the identical wrapper around a
        # no-op step, so the loop measures ONLY the telemetry path
        rec = MetricRecorder(flush_every=10 ** 9, capacity=10 ** 6)
        fake_metrics = {'loss': np.float32(0.5)}
        instr = instrumented_step(
            lambda s, xb, yb: (s, fake_metrics), rec)
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            instr(0.0, None, None)
        wrapper_cost = (time.perf_counter() - t0) / n

        assert wrapper_cost <= bare * 0.05, (wrapper_cost, bare)


class TestDeviceStats:
    def test_record_device_stats_noop_on_cpu(self, session):
        from mlcomp_tpu.telemetry import (
            device_memory_stats, record_device_stats,
        )
        stats = device_memory_stats()
        # jax IS imported in the test process: every local device is
        # reported (CPU devices usually carry no bytes_limit)
        assert isinstance(stats, list)
        rec = MetricRecorder(session=session, task=None,
                             flush_every=10 ** 9)
        record_device_stats(rec)    # must not raise without HBM stats

    def test_mfu_arithmetic(self):
        from mlcomp_tpu.telemetry import mfu
        # 1 TFLOP/step at 100 steps/s on 1 chip of 200 TFLOPs → 0.5
        assert mfu(1e12, 100, 1, 200) == pytest.approx(0.5)

    def test_compiled_cost_on_cpu_step(self):
        import jax
        import jax.numpy as jnp

        from mlcomp_tpu.telemetry import compiled_cost

        @jax.jit
        def f(x):
            return jnp.dot(x, x)

        cost = compiled_cost(f, jnp.ones((64, 64), jnp.float32))
        # XLA:CPU reports flops for a matmul; {} acceptable only if the
        # backend hides cost analysis — either way the call must not
        # raise
        if cost:
            assert cost['flops'] is None or cost['flops'] > 0


class TestServingDriverHistogram:
    def test_chain_runner_observes_latency_after_warm(self):
        """ops/serving_stack.make_chain_runner with a recorder: each
        call after the compile+warm first one lands a per-stack latency
        sample in the named histogram."""
        import jax.numpy as jnp

        from mlcomp_tpu.ops.serving_stack import make_chain_runner

        rec = MetricRecorder(flush_every=10 ** 9)
        run = make_chain_runner(
            lambda x: x * 1.0, [], jnp.ones((4, 4), jnp.float32),
            reps=3, recorder=rec, metric='serving.toy_ms')
        run()                       # compile+warm: NOT recorded
        assert rec.histogram_summaries() == {}
        run()
        run()
        summary = rec.histogram_summaries()['serving.toy_ms']
        assert summary['count'] == 2
        assert summary['min'] >= 0


class TestSupervisorTelemetry:
    def test_tick_records_gauges(self, session):
        from mlcomp_tpu.server.supervisor import SupervisorBuilder
        sup = SupervisorBuilder(session=session)
        sup.build()
        sup.telemetry.flush()
        series = MetricProvider(session).series(component='supervisor')
        assert 'supervisor.tick_ms' in series
        assert series['supervisor.tick_ms'][0]['value'] >= 0


class TestApiLimits:
    """GET/POST /telemetry/series|spans: limit/offset are validated
    (negative/garbage -> 400) and capped, never handed raw to SQL."""

    def _seed(self, session):
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        for i in range(6):
            rec.series('loss', 1.0 - 0.1 * i, step=i)
        rec.flush()
        buf = SpanBuffer()
        with span('task.pipeline', task=task.id, buffer=buf):
            with span('task.execute', buffer=buf):
                pass
        flush_spans(session, buf)
        return task

    def test_negative_limit_is_400(self, api, session):
        import urllib.error
        task = self._seed(session)
        for url in (f'/telemetry/series?task={task.id}&limit=-1',
                    f'/telemetry/spans?task={task.id}&offset=-5'):
            with pytest.raises(urllib.error.HTTPError) as e:
                api(url, method='GET', token=None)
            assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/api/telemetry/series',
                {'task': task.id, 'limit': 'lots'})
        assert e.value.code == 400

    def test_limit_and_offset_page_series(self, api, session):
        task = self._seed(session)
        out = api(f'/telemetry/series?task={task.id}&limit=2',
                  method='GET', token=None)
        assert sum(len(v) for v in out['series'].values()) == 2
        page2 = api(
            f'/telemetry/series?task={task.id}&limit=2&offset=2',
            method='GET', token=None)
        steps = [p['step'] for p in page2['series']['loss']]
        assert steps == [2, 3]

    def test_spans_limit(self, api, session):
        task = self._seed(session)
        out = api(f'/telemetry/spans?task={task.id}&limit=1',
                  method='GET', token=None)
        assert len(out['spans']) == 1
        assert out['spans'][0]['children'] == []

    def test_huge_limit_is_capped_not_error(self, api, session):
        task = self._seed(session)
        out = api(f'/telemetry/series?task={task.id}&limit=999999999',
                  method='GET', token=None)
        assert len(out['series']['loss']) == 6

    def test_tail_returns_newest_window_per_name(self, api, session):
        """tail=N: the newest N samples of EVERY name, each ascending
        — the dashboard performance card's read (a plain ascending
        limit truncates the newest samples of later-sorting names)."""
        task = self._seed(session)
        out = api(f'/telemetry/series?task={task.id}&tail=2',
                  method='GET', token=None)
        steps = [p['step'] for p in out['series']['loss']]
        assert steps == [4, 5]          # newest two, ascending
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/telemetry/series?tail=2', method='GET', token=None)
        assert e.value.code == 400      # tail requires task
        with pytest.raises(urllib.error.HTTPError) as e:
            api(f'/telemetry/series?task={task.id}&tail=0',
                method='GET', token=None)
        assert e.value.code == 400


class TestTraceContext:
    def test_span_records_trace_and_role(self, session):
        from mlcomp_tpu.telemetry import new_trace_id
        task = make_task(session)
        tid = new_trace_id()
        buf = SpanBuffer()
        with span('outer', task=task.id, buffer=buf, trace_id=tid,
                  role='supervisor'):
            # nested spans do NOT auto-inherit the explicit arg — they
            # read the process context, unset here
            with span('inner', buffer=buf, trace_id=tid, role='worker'):
                pass
        flush_spans(session, buf)
        from mlcomp_tpu.db.providers import TelemetrySpanProvider
        rows = {r.name: r for r in
                TelemetrySpanProvider(session).by_task(task.id)}
        assert rows['outer'].trace_id == tid
        assert rows['outer'].process_role == 'supervisor'
        assert rows['inner'].trace_id == tid
        assert rows['inner'].process_role == 'worker'

    def test_context_env_round_trip(self):
        from mlcomp_tpu.telemetry import trace_context_env
        env = trace_context_env(trace_id='abc123',
                                process_role='train')
        assert env == {'MLCOMP_TRACE_ID': 'abc123',
                       'MLCOMP_PROCESS_ROLE': 'train'}

    def test_trace_tree_assembles_across_processes(self, api, session):
        """Acceptance: one trace_id joins spans from 3 DISTINCT
        processes — supervisor (this process), worker and train (real
        subprocess entries that pick the context up from the
        environment) — and GET /telemetry/trace/<id> returns the
        assembled tree."""
        import os
        import subprocess
        import sys
        from mlcomp_tpu.db.providers import TelemetrySpanProvider
        from mlcomp_tpu.telemetry import new_trace_id, trace_context_env

        task = make_task(session)
        tid = new_trace_id()
        buf = SpanBuffer()
        with span('supervisor.dispatch', task=task.id, buffer=buf,
                  trace_id=tid, role='supervisor'):
            pass
        flush_spans(session, buf)

        child_src = (
            'import sys\n'
            'from mlcomp_tpu.db.core import Session\n'
            'from mlcomp_tpu.telemetry import span, flush_spans\n'
            's = Session.create_session()\n'
            'with span(sys.argv[1], task=int(sys.argv[2])):\n'
            '    pass\n'
            'raise SystemExit(0 if flush_spans(s) == 1 else 1)\n')
        for name, role in (('task.pipeline', 'worker'),
                           ('train.work', 'train')):
            env = {**os.environ,
                   'MLCOMP_TPU_KEEP_ROOT': '1',  # don't wipe the
                   # parent's sandbox on child import
                   **trace_context_env(trace_id=tid,
                                       process_role=role)}
            subprocess.run(
                [sys.executable, '-c', child_src, name, str(task.id)],
                env=env, check=True, timeout=120)

        tree = TelemetrySpanProvider(session).trace_tree(tid)
        assert tree['span_count'] == 3
        assert {p['role'] for p in tree['processes']} == \
            {'supervisor', 'worker', 'train'}
        # three DISTINCT pids — the span-id prefix is the pid
        assert len({p['pid'] for p in tree['processes']}) == 3

        out = api(f'/telemetry/trace/{tid}', method='GET', token=None)
        assert out['span_count'] == 3
        assert {s['name'] for s in out['spans']} == \
            {'supervisor.dispatch', 'task.pipeline', 'train.work'}
        for s in out['spans']:
            assert s['trace_id'] == tid

    def test_trace_api_requires_id(self, api):
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as e:
            api('/api/telemetry/trace', {})
        assert e.value.code == 400

    def test_unknown_trace_is_empty_not_error(self, api):
        out = api('/telemetry/trace/nope', method='GET', token=None)
        assert out['span_count'] == 0
        assert out['spans'] == []


class TestCrashFlush:
    def test_sigterm_flushes_spans_and_metrics(self, session):
        """The satellite: a SIGTERM'd task process must not take its
        telemetry down with it — the handler converts the signal into
        SystemExit (so the open span exits with status=error) and the
        atexit drain lands both buffers in the DB."""
        import os
        import subprocess
        import sys
        from mlcomp_tpu.db.providers import TelemetrySpanProvider

        task = make_task(session)
        child_src = (
            'import os, signal, sys, time\n'
            'from mlcomp_tpu.db.core import Session\n'
            'from mlcomp_tpu.telemetry import MetricRecorder, span\n'
            'from mlcomp_tpu.worker.tasks import _install_crash_flush\n'
            's = Session.create_session()\n'
            'task = int(sys.argv[1])\n'
            'rec = MetricRecorder(session=s, task=task,\n'
            '                     component="train",\n'
            '                     flush_every=10 ** 9)\n'
            'rec.series("loss", 0.5, step=0)\n'
            '_install_crash_flush(s)\n'
            'with span("doomed", task=task):\n'
            '    os.kill(os.getpid(), signal.SIGTERM)\n'
            '    time.sleep(60)\n')
        proc = subprocess.run(
            [sys.executable, '-c', child_src, str(task.id)],
            env={**os.environ, 'MLCOMP_TPU_KEEP_ROOT': '1'},
            timeout=120)
        assert proc.returncode == 143        # SystemExit(143), not -15

        (row,) = TelemetrySpanProvider(session).by_task(task.id)
        assert row.name == 'doomed'
        assert row.status == 'error'         # SIGTERM mid-span
        series = MetricProvider(session).series(task_id=task.id)
        assert series['loss'][0]['value'] == pytest.approx(0.5)

class TestStepAttribution:
    def test_phase_split_and_series_emission(self, session):
        from mlcomp_tpu.telemetry import StepAttribution
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        attr = StepAttribution(recorder=rec)
        for step in range(3):
            attr.begin('data_wait')
            time.sleep(0.002)
            attr.begin('h2d')
            attr.begin('compute')
            time.sleep(0.005)
            attr.begin('telemetry')
            attr.step_end(step=step)
        assert attr.steps == 3
        totals = attr.totals_ms()
        assert totals['compute'] > totals['data_wait'] > 0
        eff = attr.efficiency()
        assert 0.0 < eff < 1.0
        assert eff > 0.5            # compute slept longer
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        for phase in ('data_wait', 'h2d', 'compute', 'telemetry'):
            pts = series[f'step.phase.{phase}_ms']
            assert [p['step'] for p in pts] == [0, 1, 2]

    def test_emit_epoch_gauges_efficiency_and_resets(self, session):
        from mlcomp_tpu.telemetry import StepAttribution
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        attr = StepAttribution(recorder=rec)
        attr.begin('compute')
        time.sleep(0.002)
        attr.step_end(step=0)
        out = attr.emit_epoch(epoch=0)
        assert out['efficiency'] == pytest.approx(1.0)
        assert out['steps'] == 1
        assert attr.steps == 0 and attr.totals_ms() == {}
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        (pt,) = series['step.pipeline_efficiency']
        assert pt['value'] == pytest.approx(1.0)
        assert pt['step'] == 0

    def test_no_steps_means_no_verdict(self):
        from mlcomp_tpu.telemetry import StepAttribution
        attr = StepAttribution()
        assert attr.efficiency() is None
        assert attr.emit_epoch()['efficiency'] is None

    def test_instrumented_step_emits_phases(self, session):
        """The production wiring: instrumented_step marks compute/
        telemetry and closes each step — step.phase.* series appear
        without the executor doing anything per-step."""
        from mlcomp_tpu.telemetry import StepAttribution
        from mlcomp_tpu.train.loop import instrumented_step
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        attr = StepAttribution(recorder=rec)
        instr = instrumented_step(
            lambda s, x, y: (s, {'loss': np.float32(0.1)}), rec,
            attribution=attr)
        for _ in range(4):
            attr.begin('data_wait')
            instr(None, None, None)
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        assert len(series['step.phase.compute_ms']) == 4
        assert len(series['step.phase.data_wait_ms']) == 4
        assert 'step.phase.telemetry_ms' in series

    def test_prefetch_batches_marks_input_phases(self):
        from mlcomp_tpu.parallel import mesh_from_spec
        from mlcomp_tpu.telemetry import StepAttribution
        from mlcomp_tpu.train.data import (
            iterate_batches, prefetch_batches,
        )
        mesh = mesh_from_spec({'dp': -1})
        attr = StepAttribution()
        x = np.random.RandomState(0).rand(32, 8, 8, 1).astype(
            np.float32)
        y = np.zeros(32, np.int32)
        n = 0
        for bx, by in prefetch_batches(
                iterate_batches(x, y, 8), mesh, attribution=attr):
            attr.begin('compute')
            n += 1
        attr.step_end()
        assert n == 4
        totals = attr.totals_ms()
        assert totals.get('data_wait', 0) > 0
        assert totals.get('h2d', 0) > 0


class TestCompileEvents:
    def test_shape_varying_jit_records_compiles_with_steps(
            self, session):
        """Shape-varying jit calls after install land as
        compile.backend_ms samples carrying the stamped step."""
        import jax
        import jax.numpy as jnp

        from mlcomp_tpu.telemetry import CompileEventRecorder
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        comp = CompileEventRecorder(recorder=rec)
        if not comp.install():
            pytest.skip('jax.monitoring hooks unavailable')
        try:
            @jax.jit
            def f(x):
                return x * 2 + 1

            for i, n in enumerate((3, 5, 7)):
                comp.step = 100 + i
                f(jnp.ones((n,)))       # new shape → recompile
        finally:
            comp.uninstall()
        assert len(comp.events) >= 3
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        pts = series['compile.backend_ms']
        assert len(pts) >= 3
        steps = {p['step'] for p in pts}
        assert {100, 101, 102} <= steps
        assert all(p['value'] > 0 for p in pts)

    def test_uninstall_stops_recording(self):
        import jax
        import jax.numpy as jnp

        from mlcomp_tpu.telemetry import CompileEventRecorder
        comp = CompileEventRecorder()
        if not comp.install():
            pytest.skip('jax.monitoring hooks unavailable')
        comp.uninstall()

        @jax.jit
        def g(x):
            return x + 3

        g(jnp.ones((11,)))
        assert len(comp.events) == 0

    def test_reinstall_after_uninstall_records_again(self):
        import jax
        import jax.numpy as jnp

        from mlcomp_tpu.telemetry import CompileEventRecorder
        comp = CompileEventRecorder()
        if not comp.install():
            pytest.skip('jax.monitoring hooks unavailable')
        comp.uninstall()
        assert comp.install() is True    # re-arm resets the dead flag

        @jax.jit
        def h(x):
            return x - 7

        try:
            h(jnp.ones((13,)))
            assert len(comp.events) >= 1
        finally:
            comp.uninstall()

    def test_install_without_jax_monitoring_is_noop(self, monkeypatch):
        import sys as _sys

        from mlcomp_tpu.telemetry import CompileEventRecorder
        monkeypatch.setitem(_sys.modules, 'jax.monitoring', None)
        comp = CompileEventRecorder()
        assert comp.install() is False
        assert comp.installed is False

    def test_tripwire_flags_outlier_not_baseline(self, session):
        from mlcomp_tpu.telemetry import HostSyncTripwire
        task = make_task(session)
        rec = MetricRecorder(session=session, task=task.id,
                             component='train', flush_every=10 ** 9)
        wire = HostSyncTripwire(recorder=rec, factor=10.0, min_ms=50.0,
                                warmup_steps=5)
        for step in range(8):
            assert wire.observe(10.0, step=step) is False
        assert wire.observe(900.0, step=8) is True     # 90x median
        assert wire.observe(10.0, step=9) is False     # baseline clean
        assert wire.suspects == 1
        rec.flush()
        series = MetricProvider(session).series(task_id=task.id)
        (pt,) = series['host_sync.suspect_ms']
        assert pt['step'] == 8 and pt['value'] == pytest.approx(900.0)

    def test_tripwire_quiet_during_warmup(self):
        from mlcomp_tpu.telemetry import HostSyncTripwire
        wire = HostSyncTripwire(warmup_steps=10)
        # huge first interval (the compile step) must not flag: the
        # baseline is not established yet
        assert wire.observe(5000.0) is False

    def test_instrumented_step_exempts_compile_steps(self):
        """A step whose interval contains a recorded compile is slow
        for a KNOWN reason — the tripwire must not double-report it."""
        from mlcomp_tpu.telemetry import (
            CompileEventRecorder, HostSyncTripwire,
        )
        from mlcomp_tpu.train.loop import instrumented_step
        rec = MetricRecorder(flush_every=10 ** 9)
        comp = CompileEventRecorder()
        flagged = []

        class Wire(HostSyncTripwire):
            def observe(self, dt_ms, step=None):
                flagged.append(step)
                return False

        instr = instrumented_step(
            lambda s, x: (s, {}), rec, attribution=None,
            tripwire=Wire(), compile_events=comp)
        instr(None, None)               # first step: no interval
        comp._dirty = True              # a compile landed mid-step
        instr(None, None)               # exempt
        instr(None, None)               # observed again
        assert flagged == [2]


class TestTraceCorrelatedLogs:
    def test_formatter_injects_trace_context(self):
        import logging

        from mlcomp_tpu.telemetry import set_trace_context
        from mlcomp_tpu.utils.logging import create_logger
        logger = create_logger(name='mlcomp_tpu_tracetest')
        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(self.format(record))

        cap = Capture()
        cap.setFormatter(logging.Formatter('%(trace)s %(message)s'))
        logger.addHandler(cap)
        try:
            set_trace_context('feedbeef12345678', 'train')
            logger.info('inside the dispatch')
            set_trace_context(None)
            logger.info('outside any trace')
        finally:
            set_trace_context(None)
            logger.removeHandler(cap)
        assert '[trace=feedbeef12345678 role=train]' in records[0]
        assert 'trace=' not in records[1]

    def test_grep_by_trace_id_finds_the_line(self):
        """The satellite's contract: one trace id greps out the log
        lines of that dispatch from the standard formatter."""
        import logging

        from mlcomp_tpu.telemetry import new_trace_id, set_trace_context
        from mlcomp_tpu.utils.logging import create_logger
        logger = create_logger(name='mlcomp_tpu_greptest')
        lines = []

        class Capture(logging.Handler):
            def emit(self, record):
                lines.append(self.format(record))

        cap = Capture()
        cap.setFormatter(logging.Formatter(
            '%(module)s:%(lineno)d%(trace)s %(message)s'))
        logger.addHandler(cap)
        tid = new_trace_id()
        try:
            set_trace_context(tid, 'worker')
            logger.info('claimed task 7')
            logger.error('task 7 failed')
        finally:
            set_trace_context(None)
            logger.removeHandler(cap)
        hits = [ln for ln in lines if tid in ln]
        assert len(hits) == 2


class TestProfilerEdgeCases:
    """Satellite: the injectable-tracer lifecycle paths that were
    untested — a failing tracer, a sessionless profiler, polling
    after done."""

    def test_tracer_start_failure_writes_failed_status(self, session,
                                                       tmp_path):
        task = make_task(session)

        def boom(d):
            raise RuntimeError('no backend')

        prof = TaskProfiler(session, task.id, str(tmp_path),
                            tracer_start=boom,
                            tracer_stop=lambda: None)
        request_trace(session, task.id)
        assert prof.poll() is False
        assert prof.tracing is False
        status = trace_status(session, task.id)
        assert status['status'] == 'failed'
        assert 'no backend' in status['error']

    def test_sessionless_profiler_is_inert(self, tmp_path):
        prof = TaskProfiler(None, 1, str(tmp_path),
                            tracer_start=lambda d: None,
                            tracer_stop=lambda: None)
        assert prof.poll() is False
        prof.close()                    # must not raise

    def test_poll_after_done_stays_off(self, session, tmp_path):
        task = make_task(session)
        calls = []
        prof = TaskProfiler(session, task.id, str(tmp_path),
                            tracer_start=lambda d: calls.append('s'),
                            tracer_stop=lambda: calls.append('e'))
        request_trace(session, task.id, max_epochs=1)
        assert prof.poll() is True
        assert prof.poll() is False     # max_epochs expired → done
        assert prof.poll() is False     # done row does NOT restart
        assert calls == ['s', 'e']

class TestAttributionInRealRun:
    def test_jax_train_persists_phase_and_efficiency_series(
            self, session, tmp_path):
        """Acceptance: a real jax_train run records step.phase.* for
        every step and a per-epoch step.pipeline_efficiency gauge —
        bench's number, from inside production."""
        from mlcomp_tpu.train import JaxTrain

        class DummyStep:
            def start(self, *a, **k):
                pass

            def info(self, m):
                pass

            def debug(self, m):
                pass

            def error(self, m):
                pass

            def end_all(self):
                pass

        task = make_task(session)
        ex = JaxTrain(
            model={'name': 'mlp', 'hidden': [16], 'num_classes': 4},
            dataset={'name': 'synthetic_images', 'n_train': 256,
                     'n_valid': 64, 'image_size': 8, 'channels': 1,
                     'num_classes': 4},
            loss='softmax_ce', batch_size=32, epochs=2,
            telemetry={'flush_every': 16},
            checkpoint_dir=str(tmp_path / 'ck'))
        ex.step = DummyStep()
        ex.task = task
        ex.dag = DagProvider(session).by_id(task.dag)
        ex.session = session
        ex.additional_info = {}
        ex.work()

        series = MetricProvider(session).series(task_id=task.id)
        # 2 epochs x 8 steps of per-step phase attribution
        for phase in ('data_wait', 'h2d', 'compute', 'telemetry'):
            pts = series[f'step.phase.{phase}_ms']
            assert len(pts) == 16, phase
            assert all(p['value'] >= 0 for p in pts)
        eff = series['step.pipeline_efficiency']
        assert [p['step'] for p in eff] == [0, 1]   # one per epoch
        assert all(0.0 < p['value'] <= 1.0 for p in eff)
        # the compile listener saw the first-step compiles (skipped
        # quietly if this jax build has no monitoring hooks)
        from mlcomp_tpu.telemetry import CompileEventRecorder
        if CompileEventRecorder().install():
            assert 'compile.backend_ms' in series
