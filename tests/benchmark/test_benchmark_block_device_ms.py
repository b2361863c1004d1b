"""``block_device_ms.<block>``: the train step's device time by model
block, the program's row ``step.op_blocks`` joined with a trace in the
plain form of ``trace_reduce`` — a step matched in full, an eval program
left out, a share under 99% that gives ``None`` for every block, a
program without the row — and the eight entries in ``BENCHMARK.json``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import program_spans
from benchmark.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = Manifest(ROOT)
BLOCKS = ('attention', 'mixer', 'moe_routing', 'moe_experts', 'mlp',
          'embed_head', 'optimizer', 'other')
OLMO, QWEN = 'olmo-1b.steady', 'qwen3-next-80b-a3b.steady'
LFM2, KANANA = 'lfm2-8b-a1b.steady', 'kanana-2-30b-a3b.steady'
CELLS = {
    'attention': [OLMO, QWEN, LFM2, KANANA],
    'mixer': [QWEN, LFM2],
    'moe_routing': [QWEN, LFM2, KANANA],
    'moe_experts': [QWEN, LFM2, KANANA],
    'mlp': [OLMO, QWEN, LFM2, KANANA],
    'embed_head': [OLMO, QWEN, LFM2, KANANA],
    'optimizer': [OLMO, QWEN, LFM2, KANANA],
    'other': [OLMO, QWEN, LFM2, KANANA],
}

#: the program's table: instruction -> [result shape, block, backward]
TABLE = {'fusion.1': ['bf16[4,8]', 'mlp', 0],
         'fusion.2': ['bf16[4,8]', 'mlp', 1],
         'mla_attn.3': ['bf16[2,8]', 'attention', 0],
         'while.4': ['(s32[])', 'other', 0],
         'multiply_fusion.5': ['f32[8]', 'optimizer', 0],
         'gather.6': ['f32[8,2]', 'embed_head', 0]}


def op(name, shape, start, dur, kind='fusion'):
    return [f'{name} = {shape}{{1,0}} {kind}(%p.1), metadata={{}}',
            start, dur]


US = 1_000


def trace(tail_us=0):
    """Two runs of the train step (1 ms each), one of the eval step
    between them, inside the runner's two marks. ``tail_us``: an op the
    table does not know at the end of each train run."""
    ops, modules = [], []
    for at in (1_000 * US, 3_000 * US):
        modules.append(['jit_step_in_context(11)', at, 1_000 * US])
        ops += [op('fusion.1', 'bf16[4,8]', at, 200 * US),
                op('fusion.2', 'bf16[4,8]', at + 200 * US, 300 * US),
                op('while.4', '(s32[])', at + 500 * US, 400 * US, 'while'),
                op('mla_attn.3', 'bf16[2,8]', at + 500 * US, 250 * US,
                   'custom-call'),
                op('gather.6', 'f32[8,2]', at + 750 * US, 50 * US),
                op('multiply_fusion.5', 'f32[8]', at + 800 * US, 100 * US)]
        if tail_us:
            ops.append(op('copy.9', 'f32[8]', at + 900 * US, tail_us * US,
                          'copy'))
    modules.append(['jit_step_in_context(22)', 2_200 * US, 300 * US])
    ops.append(op('fusion.40', 'f32[4]', 2_200 * US, 300 * US))
    return {'planes': [
        {'name': '/host:CPU', 'lines': [{'name': 'python', 'events': [
            ['bench_window_open', 0, 10],
            ['bench_window_close', 5_000 * US, 10]]}]},
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Modules', 'events': modules},
            {'name': 'XLA Ops', 'events': ops}]}]}


class Run:
    def __init__(self, plain, rows=True):
        self.task_id, self.peaks = 7, {'bf16_flops_per_s': 1.0}
        self.extra, self.notes, self._trace = {}, [], plain
        self._rows = [{'value': float(len(TABLE)), 'tags': json.dumps(
            {'ops': TABLE, 'build_s': 0.25})}] if rows else []

    def query(self, sql, args=()):
        assert "'step.op_blocks'" in sql and args == (7,)
        return self._rows

    def note(self, text):
        self.notes.append(text)


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(program_spans, 'load_trace', lambda r: r._trace)


def read_all(run):
    return {b: MANIFEST.reader(f'block_device_ms.{b}')(
        run, f'block_device_ms.{b}') for b in BLOCKS}


def test_a_matched_step_by_block(traced):
    run = Run(trace())
    got = read_all(run)
    # ms a step: two runs; the `while` only encloses, the eval program's
    # op is no op of the train step
    assert got == pytest.approx({
        'attention': 0.25, 'mixer': 0, 'moe_routing': 0,
        'moe_experts': 0, 'mlp': 0.5, 'embed_head': 0.05,
        'optimizer': 0.1, 'other': 0})
    text = '\n'.join(run.notes)
    assert 'ran 2 times' in text and 'matched 100.000%' in text
    assert '6 instructions' in text and 'built in 0.250 s' in text
    assert ('block_device_ms.mlp: 0.500 ms = forward 0.200 + backward '
            '0.300; kernel ops 0.000, the rest 0.500') in text
    assert ('block_device_ms.attention: 0.250 ms = forward 0.250 + '
            'backward 0.000; kernel ops 0.250, the rest 0.000') in text
    assert 'block_device_ms.mlp:   fusion bf16[4,8] 0.3000 ms (backward)' \
        in text
    # the split is made once a run, whichever block is read first
    assert len([n for n in run.notes if 'ran 2 times' in n]) == 1


def test_under_99_percent_matched_every_block_is_none(traced):
    # 20 us of 920 unmatched a run: 97.8%
    run = Run(trace(tail_us=20))
    assert read_all(run) == {b: None for b in BLOCKS}
    text = '\n'.join(run.notes)
    assert 'NOT MATCHED copy f32[8]' in text
    assert 'every block reads None' in text
    # 9 us of 909: 99.0% — read
    assert read_all(Run(trace(tail_us=9)))['mlp'] == pytest.approx(0.5)


def test_a_program_without_the_row_reads_nothing(traced):
    run = Run(trace(), rows=False)
    assert read_all(run) == {b: None for b in BLOCKS}
    assert run.notes == []
    untraced = Run(None)
    assert read_all(untraced) == {b: None for b in BLOCKS}


def test_the_eight_entries_and_their_cells():
    entries = {m['name']: m for m in MANIFEST.data['per_layer']}
    names = [m['name'] for m in MANIFEST.data['per_layer']]
    at = names.index('mla_attn_roofline')
    assert names[at + 1:at + 9] == [f'block_device_ms.{b}' for b in BLOCKS]
    order = [c['name'] for c in MANIFEST.data['workloads']]
    for block, cells in CELLS.items():
        entry = entries[f'block_device_ms.{block}']
        assert entry == {
            'name': f'block_device_ms.{block}', 'unit': 'ms',
            'better': 'lower', 'source': 'device_trace',
            'layer': 'jitted step, device', 'moves': 'train_tokens_per_s',
            'workloads': sorted(cells, key=order.index)}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'manifest.py'),
         '--check'], capture_output=True, text=True, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:]
    for cell in (OLMO, QWEN, LFM2, KANANA):
        line = next(n for n in done.stdout.splitlines()
                    if n.startswith('  per_layer:') and done.stdout.index(
                        n) > done.stdout.index(cell))
        listed = {b for b in BLOCKS if f'block_device_ms.{b}' in line}
        assert listed == {b for b, c in CELLS.items() if cell in c}, cell
    resnet = MANIFEST.metrics('per_layer', 'resnet18-cifar10.steady')
    assert not any(m['name'].startswith('block_device_ms.') for m in resnet)


@pytest.mark.parametrize('cell', [QWEN, LFM2, KANANA])
def test_each_sparse_cell_reports_its_blocks_besides_the_accepted(cell):
    """The accepted ``test_what_each_sparse_cell_reports`` lists what
    each sparse cell reported before these entries (``tests/conftest.py``
    marks its three cases): the same sets, each with the cell's blocks
    added, and nothing else."""
    from tests.benchmark.test_benchmark_deepseek_v3 import REPORTS
    reports = {m['name'] for m in MANIFEST.metrics('per_layer', cell)}
    assert reports == REPORTS[cell] | {
        f'block_device_ms.{b}' for b, cells in CELLS.items() if cell in cells}
