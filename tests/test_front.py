"""Dashboard ↔ API contract (VERDICT r2 next-#3/#5).

No browser ships in this image, so the UI is held to its API contract:
every route the dashboard JS calls must exist, every UI-facing route in
``_ROUTES`` must be reachable from the dashboard source, and each call
the JS makes is replayed here with the same payload shape it sends —
including the layout-driven report rendering path (resolved layout +
series + galleries + confusion filtering) and the dialogs' routes.
"""

import re

import pytest

from mlcomp_tpu.server.api import _ROUTES
from mlcomp_tpu.server.front import dashboard_html

# routes that are deliberately NOT in the dashboard:
#   /api/db       — the RemoteSession wire protocol for remote workers
#                   (a SQL proxy has no place in a browser UI)
NON_UI_ROUTES = {'/api/db'}


def ui_called_paths():
    src = dashboard_html()
    called = {p for p in re.findall(r"api\('([\w/]+)'", src)
              if not p.endswith('/')}
    # dynamic route names composed in JS:
    #   api('dag/'+action) with action in stop/start/remove
    for m in re.findall(r"api\('(\w+)/'\s*\+\s*action", src):
        for action in ('stop', 'start', 'remove'):
            called.add(f'{m}/{action}')
    #   api(kind+'/toogle_report') with kind in dag/task
    for m in re.findall(r"api\(kind\s*\+\s*'(/\w+)'", src):
        for kind in ('dag', 'task'):
            called.add(f'{kind}{m}')
    #   galleryHtml: api(kind, ...) where kind is the layout item type
    if re.search(r'await api\(kind,', src):
        for t in ('img_classify', 'img_segment'):
            if f"'{t}'" in src:
                called.add(t)
    # GET endpoints referenced as links/fetches
    called |= set(re.findall(r"/api/([\w/]+)\?", src))
    called |= set(re.findall(r"fetch\('/api/([\w/]+)'", src))
    return {f'/api/{p}' for p in called}


class TestRouteCoverage:
    def test_every_ui_call_has_a_route(self):
        unknown = ui_called_paths() - set(_ROUTES) - {'/api/code_download'}
        assert not unknown, f'dashboard calls unregistered routes: {unknown}'

    def test_every_route_reachable_from_ui(self):
        """VERDICT r2 #3 'Done' criterion: every _ROUTES entry is
        reachable from the UI (modulo the documented non-UI set)."""
        reachable = ui_called_paths() | {'/api/code_download'}
        missing = set(_ROUTES) - reachable - NON_UI_ROUTES
        assert not missing, f'routes unreachable from the UI: {missing}'


@pytest.fixture()
def seeded(session):
    """A dag with a train task, series, imgs, report and model — the
    data shapes every dashboard view renders."""
    import numpy as np

    from mlcomp_tpu.db.models import Model, ReportImg, ReportSeries
    from mlcomp_tpu.db.providers import (
        ModelProvider, ReportImgProvider, ReportProvider,
        ReportSeriesProvider, TaskProvider,
    )
    from mlcomp_tpu.server.create_dags.standard import dag_standard
    from mlcomp_tpu.utils.misc import now
    from mlcomp_tpu.utils.plot import img_to_bytes

    config = {
        'info': {'name': 'ui_dag', 'project': 'ui_proj',
                 'layout': 'img_classify'},
        'executors': {'train': {'type': 'jax_train'}},
    }
    dag, tasks = dag_standard(session, config)
    task_id = tasks['train'][0]
    sp = ReportSeriesProvider(session)
    for epoch in range(3):
        for name, part, val in (('loss', 'train', 1.0 - 0.2 * epoch),
                                ('loss', 'valid', 1.1 - 0.2 * epoch),
                                ('accuracy', 'valid', 0.5 + 0.1 * epoch)):
            sp.add(ReportSeries(task=task_id, name=name, epoch=epoch,
                                value=val, part=part, time=now(),
                                stage='stage1'))
    imgs = ReportImgProvider(session)
    rng = np.random.RandomState(0)
    for i in range(20):
        imgs.add(ReportImg(
            group='img_classify', task=task_id, dag=dag.id,
            project=dag.project, epoch=2, part='valid',
            y=i % 3, y_pred=(i + (i % 4 == 0)) % 3, score=0.9,
            img=img_to_bytes(rng.rand(8, 8, 3))))
    ModelProvider(session).add(Model(
        name='ui_model', project=dag.project, dag=dag.id,
        score_local=0.9, created=now(),
        equations='v1: "load(\'ui_model\')"'))
    report_id = session.query_one(
        'SELECT report FROM dag WHERE id=?', (dag.id,))['report']
    return {'dag': dag.id, 'task': task_id, 'report': report_id,
            'project': dag.project}


class TestUiPayloads:
    """Replay each dashboard call with the payload shape the JS sends."""

    def test_tables_paginate_and_filter(self, api, seeded):
        pag = {'page_number': 0, 'page_size': 25}
        dags = api('/api/dags', {'name': 'ui', 'paginator': pag})
        assert dags['total'] == 1 and dags['data'][0]['name'] == 'ui_dag'
        tasks = api('/api/tasks', {'status': [0], 'paginator': pag})
        assert all(t['status'] == 0 for t in tasks['data'])
        page2 = api('/api/tasks',
                    {'paginator': {'page_number': 1, 'page_size': 25}})
        assert page2['data'] == []
        logs = api('/api/logs', {'message': 'no-such', 'paginator': pag})
        assert logs['total'] == 0
        projects = api('/api/projects', {'name': 'ui_p', 'paginator': pag})
        assert projects['total'] == 1
        assert projects['data'][0]['dag_count'] == 1

    def test_project_crud(self, api, seeded):
        api('/api/project/add', {'name': 'p2', 'class_names': '[a, b]'})
        pid = [p for p in api('/api/projects', {})['data']
               if p['name'] == 'p2'][0]['id']
        api('/api/project/edit', {'id': pid, 'name': 'p2renamed'})
        names = [p['name'] for p in api('/api/projects', {})['data']]
        assert 'p2renamed' in names
        api('/api/project/remove', {'id': pid})
        names = [p['name'] for p in api('/api/projects', {})['data']]
        assert 'p2renamed' not in names

    def test_report_detail_is_layout_driven(self, api, seeded):
        """The report page consumes the RESOLVED layout: panels exist,
        series items map through items{}.key, galleries declared."""
        detail = api('/api/report', {'id': seeded['report']})
        layout = detail['layout']
        assert layout['items'], 'resolved layout has items'
        panels = layout['layout']
        assert any(p.get('title') == 'base' for p in panels)
        # the img_classify layout (extends classify extends base)
        # declares the gallery item the dashboard renders
        types = {i.get('type') for p in panels for i in p.get('items', [])}
        assert 'img_classify' in types
        assert 'series' in types
        # series the layout references resolve to data
        keys = {spec.get('key') for spec in layout['items'].values()
                if spec.get('type') == 'series'}
        have = {s['name'] for s in detail['series']}
        assert {'loss', 'accuracy'} <= keys
        assert {'loss', 'accuracy'} <= have

    def test_gallery_confusion_and_filters(self, api, seeded):
        res = api('/api/img_classify',
                  {'task': seeded['task'],
                   'paginator': {'page_number': 0, 'page_size': 16}})
        assert res['total'] == 20
        assert len(res['data']) == 16
        assert res['data'][0]['img']          # base64 payload
        cm = res['confusion']
        assert cm['n'] == 3
        assert sum(sum(r) for r in cm['matrix']) == 20
        # click a confusion cell -> y/y_pred filter
        filt = api('/api/img_classify',
                   {'task': seeded['task'], 'y': 1, 'y_pred': 1,
                    'paginator': {'page_number': 0, 'page_size': 16}})
        assert filt['total'] == cm['matrix'][1][1]
        seg = api('/api/img_segment',
                  {'paginator': {'page_number': 0, 'page_size': 16}})
        assert seg['total'] == 20     # group filter narrows in real segs
        # the dashboard scopes galleries to the report's task LIST
        scoped = api('/api/img_classify',
                     {'tasks': [seeded['task']],
                      'paginator': {'page_number': 0, 'page_size': 5}})
        assert scoped['total'] == 20
        assert scoped['confusion']['n'] == 3
        empty = api('/api/img_classify',
                    {'tasks': [seeded['task'] + 999],
                     'paginator': {'page_number': 0, 'page_size': 5}})
        assert empty['total'] == 0

    def test_layout_editor_flow(self, api, seeded):
        layouts = api('/api/layouts', {})
        names = [l['name'] for l in layouts['data']]
        assert 'base' in names and 'img_classify' in names
        api('/api/layout/add', {'name': 'mine',
                                'content': 'items: {}\nlayout: []'})
        api('/api/layout/edit',
            {'name': 'mine', 'content':
             'items:\n  loss: {type: series, key: loss}\nlayout:\n'
             '- {type: panel, title: custom, items: '
             '[{type: series, source: loss}]}'})
        with pytest.raises(Exception):
            api('/api/layout/edit', {'name': 'mine',
                                     'content': ':::not yaml:::'})
        # switching the report's layout changes what the page renders
        start = api('/api/report/update_layout_start',
                    {'id': seeded['report']})
        assert 'mine' in start['layouts']
        assert start['current'] == 'img_classify'
        api('/api/report/update_layout_end',
            {'id': seeded['report'], 'layout': 'mine'})
        detail = api('/api/report', {'id': seeded['report']})
        assert [p['title'] for p in detail['layout']['layout']] == \
            ['custom']
        api('/api/layout/remove', {'name': 'mine'})

    def test_report_add_and_toggle(self, api, seeded):
        start = api('/api/report/add_start', {})
        assert start['projects'] and 'base' in start['layouts']
        api('/api/report/add_end',
            {'name': 'manual', 'project': seeded['project'],
             'layout': 'classify'})
        reports = api('/api/reports', {})
        new = [r for r in reports['data'] if r['name'] == 'manual'][0]
        api('/api/dag/toogle_report',
            {'id': seeded['dag'], 'report': new['id']})
        detail = api('/api/report', {'id': new['id']})
        assert seeded['task'] in detail['tasks']
        api('/api/task/toogle_report',
            {'id': seeded['task'], 'report': new['id'], 'remove': True})
        detail = api('/api/report', {'id': new['id']})
        assert seeded['task'] not in detail['tasks']

    def test_model_dialogs(self, api, seeded):
        models = api('/api/models', {})
        mid = [m for m in models['data'] if m['name'] == 'ui_model'][0]['id']
        start = api('/api/model/start_begin', {'model_id': mid})
        assert start['model']['name'] == 'ui_model'
        assert start['versions'][0]['name'] == 'v1'
        # name-only model registration (no task)
        api('/api/model/add',
            {'name': 'registered_only', 'project': seeded['project']})
        names = [m['name'] for m in api('/api/models', {})['data']]
        assert 'registered_only' in names
        api('/api/model/remove', {'name': 'registered_only'})

    def test_computers_usage_history(self, api, seeded):
        from mlcomp_tpu.db.providers import ComputerProvider
        from mlcomp_tpu.db.models import Computer
        provider = ComputerProvider(api.session)
        provider.add(Computer(name='c1', cores=8, cpu=16, memory=32))
        for i in range(5):
            provider.add_usage_history(
                'c1', {'cpu': 10.0 + i, 'memory': 50.0, 'tpu_hbm': 5.0})
        res = api('/api/computers', {'usage_history': True})
        c1 = [c for c in res['data'] if c['name'] == 'c1'][0]
        assert len(c1['usage_history']) == 5
        assert c1['usage_history'][-1]['cpu'] == 14.0
        # without the flag the history is not attached (payload size)
        res = api('/api/computers', {})
        assert 'usage_history' not in res['data'][0]

    def test_remove_imgs_and_files(self, api, seeded):
        api('/api/remove_imgs', {'dag': seeded['dag']})
        res = api('/api/img_classify',
                  {'task': seeded['task'],
                   'paginator': {'page_number': 0, 'page_size': 5}})
        assert res['total'] == 0
        api('/api/remove_files', {'dag': seeded['dag']})
        code = api('/api/code', {'id': seeded['dag']})
        assert code['items'] == []

    def test_task_detail_telemetry_calls(self, api, seeded):
        """viewTaskDetail's telemetry calls, replayed with the same
        payload shape the JS sends: series + spans always fetched with
        {task}, the profile buttons post {task, action}."""
        from mlcomp_tpu.telemetry import (
            MetricRecorder, SpanBuffer, flush_spans, span,
        )
        task = seeded['task']
        rec = MetricRecorder(session=api.session, task=task,
                             component='train', flush_every=10 ** 9)
        for i in range(3):
            rec.series('loss', 1.0 - 0.1 * i, step=i)
        rec.gauge('mfu', 0.25)
        rec.flush()
        buf = SpanBuffer()
        with span('task.pipeline', task=task, buffer=buf):
            with span('task.execute', buffer=buf):
                pass
        flush_spans(api.session, buf)

        tel = api('/api/telemetry/series', {'task': task})
        assert [p['value'] for p in tel['series']['loss']] == \
            pytest.approx([1.0, 0.9, 0.8])
        assert tel['series']['mfu'][0]['step'] is None
        spans = api('/api/telemetry/spans', {'task': task})
        assert spans['spans'][0]['name'] == 'task.pipeline'
        assert [c['name'] for c in spans['spans'][0]['children']] == \
            ['task.execute']
        out = api('/api/telemetry/profile',
                  {'task': task, 'action': 'start'})
        assert out['status'] == 'requested'
        out = api('/api/telemetry/profile',
                  {'task': task, 'action': 'stop'})
        assert out['status'] == 'stop_requested'

    def test_dashboard_serves_all_tabs(self, api, seeded):
        html = api('/ui', method='GET', raw=True).decode()
        for tab_name in ('projects', 'dags', 'tasks', 'computers',
                         'models', 'logs', 'reports', 'layouts',
                         'supervisor'):
            assert f"'{tab_name}'" in html


# reuse the live-server fixture from test_api
from tests.test_api import api  # noqa: E402,F401


def test_js_structure_balanced():
    """Bracket/string/template-literal balance of the dashboard script —
    the closest thing to a parse check in an image with no JS runtime.
    Handles nested template literals (`${...}`), comments and regex
    literals."""
    html = dashboard_html()
    script = html.split('<script>')[1].split('</script>')[0]
    ctx = ['code']
    depth = [[]]
    pairs = {')': '(', '}': '{', ']': '['}
    line, i, prev_code = 1, 0, ''
    while i < len(script):
        c = script[i]
        if c == '\n':
            line += 1
        top = ctx[-1]
        if top in ('sq', 'dq'):
            if c == '\\':
                i += 2
                continue
            if (top == 'sq' and c == "'") or (top == 'dq' and c == '"'):
                ctx.pop()
            i += 1
            continue
        if top == 'tmpl':
            if c == '\\':
                i += 2
                continue
            if c == '`':
                ctx.pop()
                i += 1
                continue
            if c == '$' and script[i + 1:i + 2] == '{':
                ctx.append('expr')
                depth.append([])
                i += 2
                continue
            i += 1
            continue
        if c == "'":
            ctx.append('sq')
        elif c == '"':
            ctx.append('dq')
        elif c == '`':
            ctx.append('tmpl')
        elif c == '/' and script[i + 1:i + 2] == '/':
            while i < len(script) and script[i] != '\n':
                i += 1
            continue
        elif c == '/' and prev_code and prev_code in '=(,:;!&|?{[+':
            # regex literal: skip to the closing unescaped /
            i += 1
            in_class = False
            while i < len(script):
                r = script[i]
                if r == '\\':
                    i += 2
                    continue
                if r == '[':
                    in_class = True
                elif r == ']':
                    in_class = False
                elif r == '/' and not in_class:
                    break
                i += 1
        elif c in '({[':
            depth[-1].append((c, line))
        elif c in ')}]':
            if ctx[-1] == 'expr' and c == '}' and not depth[-1]:
                ctx.pop()
                depth.pop()
                i += 1
                continue
            assert depth[-1] and depth[-1][-1][0] == pairs[c], \
                f'bracket mismatch {c!r} at script line {line}'
            depth[-1].pop()
        if not c.isspace():
            prev_code = c
        i += 1
    assert ctx == ['code'] and not depth[0], \
        f'unclosed at EOF: ctx={ctx} open={depth[0][-5:]}'
