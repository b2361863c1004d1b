"""BENCHMARK.json against its contract's letter, the data-driven lookup,
and the recipe of ``benchmark/README.md`` ("Adding things") executed: a
cell added to a temporary tree by files, entries and its name appended
to the lists of the metrics it reports passes every check the benchmark
makes of itself (``accepted.py``), and each way of doing more than that
is refused by name."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

import accepted
from benchmark.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope='module')
def manifest():
    return Manifest(ROOT)


def test_top_level_keys(manifest):
    assert set(manifest.data) == set(accepted.TOP_LEVEL)
    assert manifest.data['paths'] == ['benchmark', 'tests/benchmark']
    assert 1 <= manifest.data['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 65536


@pytest.mark.parametrize('key', accepted.GROUPS)
def test_names_and_units(manifest, key):
    assert accepted.names_and_units(manifest, key) == []


def test_entry_keys(manifest):
    assert accepted.entry_keys_and_bounds(manifest) == []


def test_every_name_resolves_to_a_file(manifest):
    assert accepted.every_name_resolves(manifest) == []


def test_every_cell_reports_what_its_metrics_move(manifest):
    assert accepted.every_cell_reports_what_its_metrics_move(
        manifest) == []


def test_peaks_unknown_device_is_an_error(manifest):
    assert manifest.peaks('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks('TPU v9 imaginary')


def test_pending_cells_resolve_and_stay_out_of_the_drivers_view(manifest):
    """``pending.json`` holds cells built but not admitted: same letter,
    every name a file, and unknown to the manifest ``run.py`` loads."""
    assert accepted.pending_cells(manifest) == []
    with open(os.path.join(ROOT, 'benchmark', 'pending.json')) as fh:
        pending = json.load(fh)
    assert pending['workloads']
    for cell in pending['workloads']:
        with pytest.raises(KeyError):
            manifest.workload(cell['name'])


# ------------------------------------------------- the recipe, executed
def tree_of(tmp_path):
    """``BENCHMARK.json`` and the directories of its ``paths``, copied."""
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    for path in ('benchmark', 'tests/benchmark'):
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns('__pycache__'))
    return tmp_path


def edit_manifest(tree, edit):
    path = tree / 'BENCHMARK.json'
    data = json.loads(path.read_text())
    edit(data)
    path.write_text(json.dumps(data, indent=2))


def metric(data, name):
    (entry,) = [m for group in ('end_to_end', 'per_layer')
                for m in data[group] if m['name'] == name]
    return entry


#: what a ``steady`` cell that reports ``train_tokens_per_s`` through
#: flash attention reports besides ``setup_s``: the lists the README's
#: third step appends its name to
TOKEN_LISTS = [
    'train_tokens_per_s', 'host_input_ms.tokens', 'step_device_ms.tokens',
    'step_mfu_pct.tokens', 'device_idle_pct.tokens', 'flash_attn_roofline',
    'epoch_boundary_ms.tokens', 'epoch_boundary_idle_ms.tokens',
    'setup_span_s.data', 'setup_span_s.state', 'setup_span_s.introspect',
    'setup_span_s.epoch0']
READER = 'def read(run, metric):\n    return None\n'


def add_cell(tree, cell, new_metric, config=None, cell_file=None,
             lists=TOKEN_LISTS, reader=None):
    """The README's three steps, to the letter: new files; new entries
    at the end of their groups; the cell's name at the end of the
    ``workloads`` of every metric it reports."""
    home = tree / 'benchmark'
    like = json.loads((home / 'workloads' / 'olmo-1b.steady.json')
                      .read_text())
    like.update(cell_file or {}, config=cell['config'])
    (home / 'workloads' / f'{cell["name"]}.json').write_text(
        json.dumps(like, indent=2))
    if config is not None:
        shutil.copy(home / 'configs' / 'olmo-1b.json',
                    tree / config['file'])
    name, text = reader or (f'{new_metric["name"]}.py', READER)
    (home / 'layer_metrics' / name).write_text(text)

    def edit(data):
        if config is not None:
            data['configs'].append(config)
        data['workloads'].append(cell)
        data['per_layer'].append(
            dict(new_metric, workloads=[cell['name']]))
        for name in lists:
            metric(data, name)['workloads'].append(cell['name'])
    edit_manifest(tree, edit)


def add_one_chip_cell_of_a_new_configuration(tree):
    add_cell(
        tree,
        {'name': 'recipe-lm.steady', 'config': 'recipe-lm', 'traffic': 'steady',
         'chips': 1, 'why': 'a later PR: batches of 2 x 8,192 tokens'},
        {'name': 'recipe_drop_pct', 'unit': '%', 'better': 'lower',
         'source': 'program_counter', 'layer': 'jitted step, device',
         'moves': 'train_tokens_per_s'},
        config={'name': 'recipe-lm', 'source': 'https://example.org/recipe-lm',
                'file': 'benchmark/configs/recipe-lm.json',
                'reduced': ['n_layers'], 'why': 'a later PR'})
    return 'recipe-lm.steady'


def add_four_chip_cell_of_an_accepted_configuration(tree):
    add_cell(
        tree,
        {'name': 'olmo-1b.recipe-fsdp4', 'config': 'olmo-1b', 'traffic': 'recipe-fsdp4',
         'chips': 4, 'why': 'all 16 layers sharded over four chips, '
         'global batch 16 x 2,048: exists only across chips'},
        {'name': 'recipe_comm_pct', 'unit': '%', 'better': 'lower',
         'source': 'device_trace', 'layer': 'device',
         'moves': 'train_tokens_per_s'},
        cell_file={'executor': {
            'batch_size': 16, 'checkpoint_every': 0,
            'mesh': {'fsdp': 4}, 'model': {'n_layers': 16}}})
    return 'olmo-1b.recipe-fsdp4'


RECIPES = {'one-chip-new-config': add_one_chip_cell_of_a_new_configuration,
           'four-chip-accepted-config':
               add_four_chip_cell_of_an_accepted_configuration}


@pytest.mark.parametrize('recipe', RECIPES)
def test_the_readmes_recipe_adds_a_cell(recipe, tmp_path):
    tree = tree_of(tmp_path)
    cell = RECIPES[recipe](tree)
    added = Manifest(str(tree))
    # as accepted, the letter and the bounds, every name a file, every
    # cell in its lists, the pending cells: all of them
    problems = accepted.check(added)
    if accepted.four_chip_cells(added.data['workloads']):
        # a later tree whose quarter is taken: that, and nothing else
        assert problems and all(
            f'cell {cell}: ' in p and 'ask for four chips' in p
            for p in problems), problems
    else:
        assert problems == []
    assert [m['name'] for m in added.metrics('end_to_end', cell)] == [
        'train_tokens_per_s', 'setup_s']
    reported = [m['name'] for m in added.metrics('per_layer', cell)]
    assert reported[:-1] == TOKEN_LISTS[1:]
    assert callable(added.reader(reported[-1]))
    assert added.cell(cell)['entry']['chips'] in (1, 4)
    # and the accepted cells report what they did
    before = Manifest(ROOT)
    for name in ('resnet18-cifar10.steady', 'olmo-1b.steady'):
        for group in ('end_to_end', 'per_layer'):
            assert [m['name'] for m in added.metrics(group, name)] == [
                m['name'] for m in before.metrics(group, name)]


def _loosen_a_bound(tree):
    edit_manifest(tree, lambda d: metric(d, 'train_tokens_per_s').update(
        bound=0.05))


def _remove_an_entry(tree):
    edit_manifest(tree, lambda d: d['per_layer'].remove(
        metric(d, 'flash_attn_roofline')))


def _insert_before_the_accepted_names(tree):
    add_one_chip_cell_of_a_new_configuration(tree)

    def edit(data):
        names = metric(data, 'train_tokens_per_s')['workloads']
        names.insert(0, names.pop())
    edit_manifest(tree, edit)


def _leave_the_cell_out_of_its_rate_metric(tree):
    add_one_chip_cell_of_a_new_configuration(tree)
    edit_manifest(tree, lambda d: metric(d, 'train_tokens_per_s')[
        'workloads'].remove('recipe-lm.steady'))


def _forget_a_list_and_the_configurations_file(tree):
    _leave_the_cell_out_of_its_rate_metric(tree)
    os.remove(tree / 'benchmark' / 'configs' / 'recipe-lm.json')


def _put_every_cell_on_four_chips(tree):
    def edit(data):
        for cell in data['workloads']:
            cell['chips'] = 4
    edit_manifest(tree, edit)


def _change_an_accepted_file_by_a_byte(tree):
    with open(tree / 'benchmark' / 'traffic.py', 'ab') as fh:
        fh.write(b'\n')


def _wedge_a_new_entry_among_the_accepted(tree):
    add_one_chip_cell_of_a_new_configuration(tree)

    def edit(data):
        data['per_layer'].insert(9, data['per_layer'].pop())
    edit_manifest(tree, edit)


def _give_setup_s_a_list(tree):
    add_one_chip_cell_of_a_new_configuration(tree)
    edit_manifest(tree, lambda d: metric(d, 'setup_s').update(
        workloads=['recipe-lm.steady']))


def _forget_the_readers_file(tree):
    add_one_chip_cell_of_a_new_configuration(tree)
    os.remove(tree / 'benchmark' / 'layer_metrics' / 'recipe_drop_pct.py')


def _forget_the_cells_file(tree):
    add_four_chip_cell_of_an_accepted_configuration(tree)
    os.remove(tree / 'benchmark' / 'workloads' / 'olmo-1b.recipe-fsdp4.json')


def _forget_a_pending_cells_reader(tree):
    os.remove(tree / 'benchmark' / 'layer_metrics' / 'task_fixed_s.py')


#: each way of doing more (or less) than the recipe, and the words the
#: refusal has to hold: the entry or the file at fault
REFUSED = {
    'an accepted bound loosened': (
        _loosen_a_bound,
        ['accepted end_to_end entry train_tokens_per_s', 'bound is 0.05']),
    'an accepted entry removed': (
        _remove_an_entry,
        ['accepted per_layer entry flash_attn_roofline was removed']),
    'a name inserted before the accepted ones': (
        _insert_before_the_accepted_names,
        ['accepted end_to_end entry train_tokens_per_s',
         'do not start with the accepted [\'olmo-1b.steady\']']),
    'a cell left out of its rate metric\'s list': (
        _leave_the_cell_out_of_its_rate_metric,
        ['cell recipe-lm.steady',
         'not in the workloads of its rate metric train_tokens_per_s']),
    'every cell on four chips': (
        _put_every_cell_on_four_chips,
        ['cells ask for four chips (resnet18-cifar10.steady, olmo-1b.steady',
         'accepted workloads entry olmo-1b.steady: chips is 4']),
    'an accepted file changed by a byte': (
        _change_an_accepted_file_by_a_byte,
        ['accepted file benchmark/traffic.py was changed']),
    'a new entry wedged among the accepted': (
        _wedge_a_new_entry_among_the_accepted,
        ['accepted per_layer entry epoch_boundary_ms.images stands at '
         'place 10, accepted at 9']),
    'a list given to setup_s': (
        _give_setup_s_a_list,
        ['accepted end_to_end entry setup_s: it had no workloads list']),
    'a reader\'s file forgotten': (
        _forget_the_readers_file,
        ['per_layer entry recipe_drop_pct: no reader '
         'benchmark/layer_metrics/recipe_drop_pct.py']),
    'a pending cell\'s reader gone': (
        _forget_a_pending_cells_reader,
        ['per_layer entry task_fixed_s: no reader',
         'accepted file benchmark/layer_metrics/task_fixed_s.py is gone']),
    'a cell\'s file forgotten': (
        _forget_the_cells_file,
        ['cell olmo-1b.recipe-fsdp4: its file is missing '
         '(benchmark/workloads/olmo-1b.recipe-fsdp4.json)']),
}


@pytest.mark.parametrize('case', REFUSED)
def test_more_than_the_recipe_is_refused_by_name(case, tmp_path):
    fault, words = REFUSED[case]
    tree = tree_of(tmp_path)
    fault(tree)
    problems = accepted.check(Manifest(str(tree)))
    for word in words:
        assert any(word in p for p in problems), (word, problems)


@pytest.mark.parametrize('chips, refused', [
    ([1, 1], None), ([1, 4], None), ([4], None), ([1, 4, 1, 1, 1, 1, 1, 4],
                                                 None),
    # a second four-chip cell among three; a third among eleven
    ([1, 4, 4], 'cell c2: 2 of 3 cells ask for four chips (c1, c2); at '
                'most 1 may'),
    ([4, 4] + [1] * 8 + [4], 'cell c10: 3 of 11 cells ask for four chips '
                             '(c0, c1, c10); at most 2 may')])
def test_a_quarter_of_the_cells_may_ask_for_four_chips(chips, refused):
    cells = [{'name': f'c{i}', 'chips': n} for i, n in enumerate(chips)]
    assert accepted.four_chip_cells(cells) == ([refused] if refused
                                               else [])


def run_check(tree):
    return subprocess.run(
        [sys.executable, os.path.join(str(tree), 'benchmark',
                                      'manifest.py'), '--check'],
        cwd=str(tree), env=dict(os.environ, JAX_PLATFORMS='cpu'),
        capture_output=True, text=True, timeout=120)


#: the tree the command is run in (None: the repo), its exit code, and
#: what it has to print
COMMAND = {
    'the repo': (None, 0, [
        'olmo-1b.steady  (config olmo-1b, traffic steady, chips 1)',
        'nothing lacking']),
    'a cell added': (add_one_chip_cell_of_a_new_configuration, 0, [
        'recipe-lm.steady  (config recipe-lm, traffic steady, chips 1)\n'
        '  end_to_end: train_tokens_per_s, setup_s\n',
        'nothing lacking']),
    'a list and a file forgotten': (
        _forget_a_list_and_the_configurations_file, 1, [
            '  LACKS is not in the workloads of its rate metric '
            'train_tokens_per_s',
            '  LACKS reports flash_attn_roofline, which moves '
            'train_tokens_per_s',
            'REFUSED configs entry recipe-lm: its file '
            'benchmark/configs/recipe-lm.json is missing'])}


@pytest.mark.parametrize('tree_is', COMMAND)
def test_the_check_command_says_what_a_cell_lacks(tree_is, tmp_path):
    prepare, code, prints = COMMAND[tree_is]
    tree = ROOT
    if prepare is not None:
        tree = tree_of(tmp_path)
        prepare(tree)
    proc = run_check(tree)
    assert proc.returncode == code, proc.stdout + proc.stderr
    for text in prints:
        assert text in proc.stdout, (text, proc.stdout)
    # the accepted cells lack nothing, whatever a new one lacks
    assert 'LACKS' not in proc.stdout.split('recipe-lm.steady  (')[0]


def test_the_check_command_asks_for_its_flag():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'manifest.py')],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2 and '--check' in proc.stderr


def test_the_snapshot_script_writes_the_committed_snapshot(tmp_path):
    """``make_accepted.py`` on the accepted files and the accepted
    manifest gives the committed record byte for byte — on a later tree
    too, whatever that tree has added."""
    spec = importlib.util.spec_from_file_location(
        'make_accepted', os.path.join(ROOT, 'tests', 'benchmark', 'data',
                                      'make_accepted.py'))
    make = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(make)
    with open(os.path.join(ROOT, make.RECORD)) as fh:
        committed = fh.read()
    snapshot = json.loads(committed)
    for path in snapshot['files']:
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        shutil.copy(os.path.join(ROOT, path), tmp_path / path)
    (tmp_path / 'BENCHMARK.json').write_text(
        json.dumps(snapshot['manifest']))
    assert make.record(str(tmp_path)) == committed
    # every file the guard itself is made of is under it
    for path in ('tests/benchmark/accepted.py',
                 'tests/benchmark/data/make_accepted.py',
                 'tests/benchmark/test_benchmark_manifest.py',
                 'tests/benchmark/test_benchmark_program_spans.py',
                 'tests/benchmark/data/span_trace.json',
                 'benchmark/program_spans.py', 'benchmark/README.md'):
        assert path in snapshot['files'], path
    assert make.RECORD not in snapshot['files']


def test_new_cell_config_and_reader_are_found_as_files(tmp_path):
    """Adding is files plus entries: the lookup finds each by its name,
    with nothing that is there edited."""
    tree = tree_of(tmp_path)
    home = tree / 'benchmark'
    before = {p: p.read_bytes() for p in home.rglob('*') if p.is_file()}
    add_cell(
        tree,
        {'name': 'new-model.burst', 'config': 'new-model',
         'traffic': 'burst', 'chips': 1, 'why': 'a later PR'},
        {'name': 'new_ms.tokens', 'unit': 'ms', 'better': 'lower',
         'source': 'program_span', 'layer': 'train loop, host',
         'moves': 'train_tokens_per_s'},
        config={'name': 'new-model', 'source': 'https://example.org/new',
                'file': 'benchmark/configs/new-model.json',
                'reduced': ['n_layers'], 'why': 'a later PR'},
        lists=['train_tokens_per_s'],
        # a metric split by a suffix: the file of the name before the dot
        reader=('new_ms.py', 'def read(run, metric):\n    return 1.5\n'))
    manifest = Manifest(str(tree))
    assert manifest.cell('new-model.burst')['entry']['traffic'] == 'burst'
    assert manifest.config('new-model')['reference'] == 'transformer_lm'
    assert manifest.reader('new_ms.tokens')(None, 'new_ms.tokens') == 1.5
    assert [m['name'] for m in manifest.metrics(
        'per_layer', 'new-model.burst')] == ['new_ms.tokens']
    assert accepted.check(manifest) == []
    for path, content in before.items():
        assert path.read_bytes() == content
