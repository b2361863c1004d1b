"""BENCHMARK.json against its contract's letter, and the data-driven
lookup: a cell, a configuration and a reader dropped into a temporary
tree are found with no edit to the runner."""

import json
import os
import re
import shutil

import pytest

from benchmark.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


@pytest.fixture(scope='module')
def manifest():
    return Manifest(ROOT)


def _entries(manifest, *keys):
    return [(key, e) for key in keys for e in manifest.data[key]]


def test_top_level_keys(manifest):
    assert set(manifest.data) == {
        'command', 'paths', 'run_seconds', 'configs', 'workloads',
        'end_to_end', 'per_layer'}
    assert manifest.data['paths'] == ['benchmark', 'tests/benchmark']
    assert 1 <= manifest.data['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 65536


@pytest.mark.parametrize('key', ['configs', 'workloads', 'end_to_end',
                                 'per_layer'])
def test_names_and_units(manifest, key):
    names = [e['name'] for e in manifest.data[key]]
    assert len(names) == len(set(names))
    for entry in manifest.data[key]:
        assert NAME.match(entry['name']), entry['name']
        if 'unit' in entry:
            assert UNIT.match(entry['unit']), entry['unit']
            assert entry['better'] in ('lower', 'higher')
            assert entry['source'] in ('device_trace', 'program_span',
                                       'program_counter', 'host_clock')
        for text in (entry.get('why'), entry.get('layer')):
            assert text is None or (0 < len(text) <= 200
                                    and '\n' not in text
                                    and '\t' not in text)


def test_entry_keys(manifest):
    allowed = {
        'configs': {'name', 'source', 'file', 'reduced', 'why'},
        'workloads': {'name', 'config', 'traffic', 'chips', 'why'},
        'end_to_end': {'name', 'unit', 'better', 'bound', 'source',
                       'workloads'},
        'per_layer': {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}}
    for key, entry in _entries(manifest, *allowed):
        assert set(entry) <= allowed[key], (key, entry['name'])
    for entry in manifest.data['end_to_end']:
        assert 0 < entry['bound'] <= 0.1
        assert entry['source'] in ('host_clock', 'device_trace')


def test_every_name_resolves_to_a_file(manifest):
    configs = {c['name'] for c in manifest.data['configs']}
    used = set()
    for cell in manifest.data['workloads']:
        assert cell['config'] in configs
        assert cell['chips'] == 1
        used.add(cell['config'])
        body = manifest.cell(cell['name'])
        assert body['config'] == cell['config']
        assert NAME.match(cell['traffic'])
        config = manifest.config(cell['config'])
        assert manifest.reference(config['reference']).train
    assert used == configs
    for config in manifest.data['configs']:
        assert config['file'].startswith('benchmark/')
        body = manifest.config(config['name'])
        assert body['reduced'] == config['reduced']
    for metric in manifest.data['per_layer']:
        assert callable(manifest.reader(metric['name']))


def test_every_cell_reports_what_its_metrics_move(manifest):
    cells = {c['name'] for c in manifest.data['workloads']}
    for cell in cells:
        e2e = {m['name'] for m in manifest.metrics('end_to_end', cell)}
        assert 'setup_s' in e2e and len(e2e) >= 2
        layer = manifest.metrics('per_layer', cell)
        assert layer
        for metric in layer:
            assert metric['moves'] in e2e, (cell, metric['name'])
    for metric in _entries(manifest, 'end_to_end', 'per_layer'):
        assert set(metric[1].get('workloads', cells)) <= cells


def test_peaks_unknown_device_is_an_error(manifest):
    assert manifest.peaks('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks('TPU v9 imaginary')


def test_new_cell_config_and_reader_are_found_as_files(tmp_path):
    """Adding is files plus entries: nothing that is there is edited."""
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    home = tmp_path / 'benchmark'
    shutil.copytree(os.path.join(ROOT, 'benchmark'), home,
                    ignore=shutil.ignore_patterns('__pycache__'))
    before = {p: p.read_bytes() for p in home.rglob('*') if p.is_file()}
    data = json.loads((tmp_path / 'BENCHMARK.json').read_text())
    data['configs'].append({
        'name': 'new-model', 'source': 'https://example.org/new',
        'file': 'benchmark/configs/new-model.json', 'reduced': [],
        'why': 'a later PR'})
    data['workloads'].append({
        'name': 'new-model.burst', 'config': 'new-model',
        'traffic': 'burst', 'chips': 1, 'why': 'a later PR'})
    data['per_layer'].append({
        'name': 'new_ms.tokens', 'unit': 'ms', 'better': 'lower',
        'source': 'program_span', 'layer': 'train loop, host',
        'moves': 'train_tokens_per_s',
        'workloads': ['new-model.burst']})
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(data))
    (home / 'configs' / 'new-model.json').write_text(json.dumps(
        {'reference': 'resnet', 'reduced': [], 'executor': {}}))
    (home / 'workloads' / 'new-model.burst.json').write_text(json.dumps(
        {'config': 'new-model', 'kind': 'steady', 'data': {}}))
    (home / 'layer_metrics' / 'new_ms.py').write_text(
        'def read(run, metric):\n    return 1.5\n')
    manifest = Manifest(str(tmp_path))
    assert manifest.cell('new-model.burst')['entry']['traffic'] == 'burst'
    assert manifest.config('new-model')['reference'] == 'resnet'
    assert manifest.reader('new_ms.tokens')(None, 'new_ms.tokens') == 1.5
    assert [m['name'] for m in manifest.metrics(
        'per_layer', 'new-model.burst')] == ['new_ms.tokens']
    for path, content in before.items():
        assert path.read_bytes() == content


def test_pending_cells_resolve_and_stay_out_of_the_drivers_view(manifest):
    """``pending.json`` holds cells built but not admitted: same letter,
    every name a file, and unknown to the manifest ``run.py`` loads."""
    more = Manifest(ROOT, pending=True)
    with open(os.path.join(ROOT, 'benchmark', 'pending.json')) as fh:
        pending = json.load(fh)
    for cell in pending['workloads']:
        assert NAME.match(cell['name']) and cell['chips'] == 1
        assert more.cell(cell['name'])['config'] == cell['config']
        with pytest.raises(KeyError):
            manifest.workload(cell['name'])
        e2e = {m['name'] for m in more.metrics('end_to_end', cell['name'])}
        assert 'setup_s' in e2e and len(e2e) >= 2
        for metric in more.metrics('per_layer', cell['name']):
            assert metric['moves'] in e2e
            assert callable(more.reader(metric['name']))
    for key in ('end_to_end', 'per_layer'):
        for metric in pending[key]:
            assert NAME.match(metric['name']) and UNIT.match(metric['unit'])
