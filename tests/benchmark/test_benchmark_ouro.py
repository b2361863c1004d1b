"""The looped cell of the benchmark: the ``ouro-2.6b`` configuration's
file against the catalog row's keys and against ``executor.model``, the
cut and its parameters met to the unit, the ``ouro`` reference's
required FLOPs against a hand count, ``loop_expected_exit`` on made-up
series (``None`` with none), ``gqa_attn_roofline`` in the new cell, the
``block_device_ms.*`` lists with the new cell (the accepted test that
fixes them is marked in ``tests/conftest.py``), the manifest's own
check, and the new cell's rehearsal on the CPU."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import rehearse
from benchmark.manifest import Manifest
from benchmark.reference import ouro as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = 'ouro-2.6b.steady'
MANIFEST = Manifest(ROOT)
CONFIG = MANIFEST.config('ouro-2.6b')
MODEL = CONFIG['executor']['model']
#: the `config` of the catalog's row Ouro-2.6B
#: (model-configs/architectures.jsonl), as published
PUBLISHED = {
    'head_dim': 128, 'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 5632, 'layer_types': ['full_attention'] * 48,
    'max_position_embeddings': 65536, 'max_window_layers': 48,
    'model_type': 'ouro', 'num_attention_heads': 16,
    'num_hidden_layers': 48, 'num_key_value_heads': 16,
    'rms_norm_eps': 1e-06, 'rope_scaling': None, 'rope_theta': 1000000,
    'sliding_window': None, 'tie_word_embeddings': False,
    'total_ut_steps': 4, 'early_exit_threshold': 1,
    'use_sliding_window': False, 'vocab_size': 49152}
#: the repo's name of each published key the model reads
NAMES = {
    'hidden_size': 'd_model', 'intermediate_size': 'd_ff',
    'num_hidden_layers': 'n_layers', 'num_attention_heads': 'n_heads',
    'num_key_value_heads': 'n_kv_heads', 'head_dim': 'head_dim',
    'rope_theta': 'rope_theta', 'rms_norm_eps': 'rms_eps',
    'total_ut_steps': 'ut_steps', 'vocab_size': 'vocab_size'}


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_the_file_holds_every_published_key(key):
    """Every key of the catalog's row is in the file under its own
    name: as published, or as run with the published value beside it
    and the key under ``reduced``."""
    if key in CONFIG['reduced']:
        assert CONFIG['published'][key] == PUBLISHED[key]
        assert CONFIG[key] != PUBLISHED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]
        assert key not in CONFIG['published']


@pytest.mark.parametrize('published', sorted(NAMES))
def test_the_model_runs_the_published_key(published):
    assert MODEL[NAMES[published]] == CONFIG[published]


def test_the_cut_is_one_pipeline_stage_and_keeps_every_width():
    assert CONFIG['reduced'] == ['num_hidden_layers']
    assert MANIFEST._by_name('configs', 'ouro-2.6b')['reduced'] \
        == CONFIG['reduced']
    # one of six stages of eight layers, run four times a step
    assert PUBLISHED['num_hidden_layers'] == 6 * MODEL['n_layers'] == 48
    assert MODEL['ut_steps'] == PUBLISHED['total_ut_steps'] == 4
    assert MODEL['vocab_size'] == PUBLISHED['vocab_size']
    assert MODEL['head_dim'] * MODEL['n_heads'] == MODEL['d_model']
    assert CONFIG['executor']['loss'] == 'looped_lm_ce'
    assert MODEL['remat'] is True
    for word in ('sandwich_norm', 'final_norm_each_step', 'exit_gate',
                 'objective', 'optimizer', 'rows'):
        assert word in CONFIG['assumed']
    assert '612,438,017' in CONFIG['deployment']
    # the kernels block counts layer APPLICATIONS
    assert CONFIG['kernels'] == {'gqa_attn': {
        'ops': ['gqa_attn'], 'q_heads': 16, 'kv_heads': 16,
        'head_dim': 128, 'attention_layers': 32}}
    cell = MANIFEST.cell(CELL)
    assert cell['data']['vocab_size'] == MODEL['vocab_size']
    assert cell['samples_per_row'] == cell['data']['seq_len'] == 4096
    assert cell['executor']['batch_size'] * cell['data']['seq_len'] == 8192
    assert cell['data']['train_rows'] // cell['executor']['batch_size'] == 8


def test_parameters_of_the_stage():
    """612,438,017 parameters: 7.35 GB of float32 weights and Adam's
    two moments + 2.45 GB of gradients; a ninth layer would be 10.62
    GB."""
    import numpy as np
    spec = ref.param_spec(MODEL)
    count = lambda prefix: sum(  # noqa: E731
        int(np.prod(s)) for p, (s, _) in spec.items()
        if p.startswith(prefix))
    layers = MODEL['n_layers']
    assert count('') == 612_438_017
    assert count('loop/layers/') == layers * 51_388_416
    assert count('loop/layers/attn/') - 2 * layers * 2048 \
        == layers * 4 * 2048 ** 2 == layers * 16_777_216
    assert count('loop/layers/mlp/ffn/') == layers * 34_603_008
    assert count('embed') + count('lm_head') == 201_326_592
    assert count('loop/exit/norm_final/') == 2048
    assert count('loop/exit/gate/') == 2049
    assert 16 * count('') == pytest.approx(9.80e9, rel=1e-3)
    nine = ref.param_spec(dict(MODEL, n_layers=9))
    assert 16 * sum(int(np.prod(s)) for s, _ in nine.values()) \
        == pytest.approx(10.62e9, rel=1e-3)


# ------------------------------------------------------------- hand counts
def test_train_flops_per_sample_against_a_hand_count():
    t, layers = 4096, 8
    weights = 4 * layers * 51_388_416 + 4 * 100_663_296 + 4 * 2048
    attention = 2 * 2 * (t * (t + 1) / 2) * 128 * 16    # QK^T and PV
    want = 6 * t * weights + 4 * layers * 3 * attention
    got = ref.train_flops_per_sample(MODEL, {'seq_len': t})
    assert got == pytest.approx(want, rel=1e-12)
    # 13.9 GFLOP a token, 114 TFLOP a step of 8,192 tokens
    assert 13.85e9 < got / t < 13.95e9
    assert 113e12 < 2 * got < 115e12
    # the four heads are about 17% of it
    assert 0.17 < 6 * t * 4 * 100_663_296 / got < 0.18


# ---------------------------------------------------------------- readers
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}


def fake_run(ops=(), series=None, peaks=PEAKS, config=CONFIG):
    """A run as the readers see it: a reduced trace whose op table holds
    ``ops`` [(HLO text, seconds)], the program's series, one step of 2
    sequences an epoch and 2 validation sequences."""
    cell = dict(MANIFEST.cell(CELL))
    cell['data'] = dict(cell['data'], seq_len=128, valid_rows=2)
    table = {f'{i}': [seconds, 1, text]
             for i, (text, seconds) in enumerate(ops)}
    run = types.SimpleNamespace(
        cell=cell, config=config, seed=1, peaks=peaks, steps_per_epoch=1,
        notes=[])
    run.reduced = lambda: {'op_table': table} if ops else None
    run.series = lambda name: (series or {}).get(name, [])
    run.note = run.notes.append
    return run


def test_loop_expected_exit_reads_the_windows_mean():
    read = MANIFEST.reader('loop_expected_exit')
    run = fake_run(series={
        'loop.expected_exit': [(0, 3.5, 0), (1, 1.8, 0), (2, 2.0, 0)],
        'loop.layer_rows': [(0, 262144.0, 0), (1, 262144.0, 0)]})
    # epoch 0 is set-up: the window's epochs are 1 and 2
    assert read(run, 'loop_expected_exit') == pytest.approx(1.9)
    assert any('262144.0' in note for note in run.notes)
    assert read(fake_run(), 'loop_expected_exit') is None


def test_gqa_attn_roofline_reads_the_new_cell():
    """32 layer applications a step of 16 heads of 128 over 16."""
    from benchmark import flops, flops_qwen3_next as more
    text = ('%gqa_attn.3 = bf16[2,128,16,128]{3,2,1,0} custom-call(%x), '
            'custom_call_target="tpu_custom_call"')
    run = fake_run([(text, 2e-3)])
    fwd = flops.causal_attention(128, 16, 128)
    by_flops = 32 * (2 * 3 * fwd + 2 * fwd) / 1e12
    by_bytes = 32 * (4 * more.gqa_attention_bytes(128, 16, 16, 128, 2)
                     + 2 * more.gqa_attention_bytes(128, 16, 16, 128, 2,
                                                    True)) / 1e11
    assert MANIFEST.reader('gqa_attn_roofline')(
        run, 'gqa_attn_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 2e-3)


# ------------------------------------------------------------ the manifest
BLOCKS = ('attention', 'mixer', 'moe_routing', 'moe_experts', 'mlp',
          'embed_head', 'optimizer', 'other')
OLMO, QWEN = 'olmo-1b.steady', 'qwen3-next-80b-a3b.steady'
LFM2, KANANA = 'lfm2-8b-a1b.steady', 'kanana-2-30b-a3b.steady'
BLOCK_CELLS = {
    'attention': [OLMO, QWEN, LFM2, KANANA, CELL],
    'mixer': [QWEN, LFM2],
    'moe_routing': [QWEN, LFM2, KANANA],
    'moe_experts': [QWEN, LFM2, KANANA],
    'mlp': [OLMO, QWEN, LFM2, KANANA, CELL],
    'embed_head': [OLMO, QWEN, LFM2, KANANA, CELL],
    'optimizer': [OLMO, QWEN, LFM2, KANANA, CELL],
    'other': [OLMO, QWEN, LFM2, KANANA, CELL],
}


def manifest_check():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'manifest.py'),
         '--check'], capture_output=True, text=True, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:]
    assert 'nothing lacking' in done.stdout
    return done.stdout


def test_the_eight_block_entries_and_their_cells_with_the_looped_cell():
    """What the accepted ``test_the_eight_entries_and_their_cells``
    asserts (``tests/conftest.py`` marks it), with the looped cell
    appended to the five blocks it reports: the entries stand after
    ``mla_attn_roofline`` as accepted, each lists its cells in the
    manifest's order, and ``manifest.py --check`` prints each cell's
    blocks and nothing else of them."""
    entries = {m['name']: m for m in MANIFEST.data['per_layer']}
    names = [m['name'] for m in MANIFEST.data['per_layer']]
    at = names.index('mla_attn_roofline')
    assert names[at + 1:at + 9] == [f'block_device_ms.{b}' for b in BLOCKS]
    order = [c['name'] for c in MANIFEST.data['workloads']]
    for block, cells in BLOCK_CELLS.items():
        assert entries[f'block_device_ms.{block}'] == {
            'name': f'block_device_ms.{block}', 'unit': 'ms',
            'better': 'lower', 'source': 'device_trace',
            'layer': 'jitted step, device', 'moves': 'train_tokens_per_s',
            'workloads': sorted(cells, key=order.index)}
    stdout = manifest_check()
    for cell in (OLMO, QWEN, LFM2, KANANA, CELL):
        line = next(n for n in stdout.splitlines()
                    if n.startswith('  per_layer:')
                    and stdout.index(n) > stdout.index(cell))
        listed = {b for b in BLOCKS if f'block_device_ms.{b}' in line}
        assert listed == {b for b, c in BLOCK_CELLS.items() if cell in c}
    resnet = MANIFEST.metrics('per_layer', 'resnet18-cifar10.steady')
    assert not any(m['name'].startswith('block_device_ms.') for m in resnet)


def test_what_the_cell_reports():
    manifest_check()
    assert MANIFEST.workload(CELL) == {
        'name': CELL, 'config': 'ouro-2.6b', 'traffic': 'steady',
        'chips': 1, 'why': MANIFEST.workload(CELL)['why']}
    assert {m['name'] for m in MANIFEST.metrics('end_to_end', CELL)} \
        == {'train_tokens_per_s', 'setup_s'}
    assert {m['name'] for m in MANIFEST.metrics('per_layer', CELL)} == {
        'host_input_ms.tokens', 'step_device_ms.tokens',
        'step_mfu_pct.tokens', 'device_idle_pct.tokens',
        'epoch_boundary_ms.tokens', 'epoch_boundary_idle_ms.tokens',
        'setup_span_s.data', 'setup_span_s.state',
        'setup_span_s.introspect', 'setup_span_s.epoch0',
        'gqa_attn_roofline', 'loop_expected_exit'} | {
        f'block_device_ms.{b}' for b, c in BLOCK_CELLS.items()
        if CELL in c}
    entry = MANIFEST.data['per_layer'][-1]
    assert entry == {
        'name': 'loop_expected_exit', 'unit': 'steps', 'better': 'lower',
        'source': 'program_counter', 'layer': 'jitted step, device',
        'moves': 'train_tokens_per_s', 'workloads': [CELL]}
    for metric in MANIFEST.data['per_layer'] + MANIFEST.data['end_to_end']:
        if CELL in metric.get('workloads', ()):
            assert metric['workloads'][-1] == CELL, metric['name']


def test_the_cell_rehearses(tmp_path):
    """The whole of a traced run at a tiny size (two layers run four
    times) through the normal path, float32 so that the CPU's numbers
    are sharp: the reference agrees and the counter comes out of the
    step and through the metric table."""
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal',
                           f'{CELL}.json')) as fh:
        tiny = json.load(fh)
    tiny['config']['executor']['model']['dtype'] = 'float32'
    tiny['config']['executor']['mesh'] = {'dp': 1}
    tiny['cell']['limits'] = {'loss_gap': 1e-5, 'grad_gap': 1e-4,
                              'delta_gap': 1e-4}
    line = rehearse.rehearse(CELL, seed=4_000_000_011, seconds=0.5,
                             trace=1, tiny=tiny, out=str(tmp_path))
    assert line['correct'] is True and line['failed'] == 0, line
    got = line['metrics']
    assert all(n.startswith('cpu_rehearsal.') for n in got)
    assert 1 < got['cpu_rehearsal.loop_expected_exit']['value'] < 4
    assert 'cpu_rehearsal.host_input_ms.tokens' in got
    # the CPU has no device trace: the roofline and the blocks are silent
    assert not any('roofline' in n or 'block_device' in n for n in got)
