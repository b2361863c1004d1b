"""What PR 33 adds to the benchmark: ``flops_lfm2`` and the ``lfm2_moe``
reference's required FLOPs against counts written by hand, the
configuration's file against the catalog row's keys and against
``executor.model``, the parameters of the share, ``short_conv_roofline``
on a made-up op table (reads by name; ``None`` with no such op), the
manifest's own check with the fourth cell, and the new cell's rehearsal
on the CPU."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops_lfm2, rehearse
from benchmark.manifest import Manifest
from benchmark.reference import lfm2_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = 'lfm2-8b-a1b.steady'
MANIFEST = Manifest(ROOT)
CONFIG = MANIFEST.config('lfm2-8b-a1b')
MODEL = CONFIG['executor']['model']
#: the `config` of the catalog's row LFM2-8B-A1B
#: (model-configs/architectures.jsonl), as published
PUBLISHED = {
    'conv_L_cache': 3, 'conv_bias': False, 'hidden_size': 2048,
    'intermediate_size': 7168,
    'layer_types': ['full_attention' if i in (2, 6, 10, 14, 18, 21)
                    else 'conv' for i in range(24)],
    'max_position_embeddings': 128000, 'model_type': 'lfm2_moe',
    'moe_intermediate_size': 1792, 'norm_eps': 1e-05,
    'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_dense_layers': 2, 'num_experts': 32, 'num_experts_per_tok': 4,
    'num_hidden_layers': 24, 'num_key_value_heads': 8,
    'rope_theta': 1000000, 'routed_scaling_factor': 1,
    'use_expert_bias': True, 'vocab_size': 65536}
#: the repo's name of each published key the model reads
NAMES = {
    'hidden_size': 'd_model', 'intermediate_size': 'd_ff',
    'layer_types': 'layer_types', 'num_dense_layers': 'n_dense_layers',
    'num_attention_heads': 'n_heads', 'num_key_value_heads': 'n_kv_heads',
    'rope_theta': 'rope_theta', 'conv_L_cache': 'conv_kernel',
    'num_experts': 'experts_held', 'num_experts_per_tok': 'top_k',
    'moe_intermediate_size': 'd_expert', 'norm_topk_prob': 'norm_topk_prob',
    'use_expert_bias': 'expert_bias',
    'routed_scaling_factor': 'routed_scaling_factor',
    'norm_eps': 'rms_eps', 'vocab_size': 'vocab_size'}


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


def test_the_cut_keeps_the_floors_and_every_width():
    assert CONFIG['reduced'] == [
        'num_hidden_layers', 'layer_types', 'num_dense_layers',
        'num_experts', 'vocab_size']
    assert MANIFEST._by_name('configs', 'lfm2-8b-a1b')['reduced'] \
        == CONFIG['reduced']
    # the layers that run are the published layers 1..5: a leading dense
    # layer (counted once) and the four that follow, 3 conv : 1 attention
    assert CONFIG['layer_types'] == PUBLISHED['layer_types'][1:6]
    assert CONFIG['num_hidden_layers'] == len(MODEL['layer_types']) == 5
    assert MODEL['layer_types'][MODEL['n_dense_layers']:].count(
        'full_attention') == 1
    # the router keeps the published width; the floors of the guide
    assert MODEL['n_experts'] == 32 and MODEL['experts_held'] == 8
    assert MODEL['vocab_size'] * 4 == 65536
    assert MODEL['head_dim'] * MODEL['n_heads'] == MODEL['d_model']
    assert MODEL['router_score'] == 'sigmoid'
    assert 0 < MODEL['expert_bias_update_rate'] <= 0.01
    assert 'tied_head' in CONFIG['assumed']
    assert 'flash_attn' not in CONFIG['kernels']
    kernels = CONFIG['kernels']
    assert kernels['short_conv']['conv_layers'] == 4
    assert kernels['gqa_attn']['attention_layers'] == 1
    assert kernels['expert_matmul']['moe_layers'] == 4
    cell = MANIFEST.cell(CELL)
    assert cell['data']['vocab_size'] == MODEL['vocab_size']
    assert cell['samples_per_row'] == cell['data']['seq_len'] == 8192
    # a held expert's rows a step, and the buffer's worst case
    tokens = cell['executor']['batch_size'] * cell['data']['seq_len']
    assert tokens * MODEL['top_k'] / MODEL['n_experts'] == 2048
    assert MODEL['moe_buffer_factor'] * MODEL['experts_held'] \
        / MODEL['n_experts'] >= 1           # nothing can drop


def test_parameters_of_the_share():
    """507,820,288 parameters: 6.09 GB of float32 arguments."""
    import numpy as np
    spec = ref.param_spec(MODEL)
    count = lambda prefix: sum(  # noqa: E731
        int(np.prod(s)) for p, (s, _) in spec.items()
        if p.startswith(prefix))
    assert count('') == 507_820_288
    assert count('layer_0/') == 60_827_648          # dense conv layer
    assert count('layer_1/') == 98_635_936          # sparse attention
    assert count('layer_2/') == 104_933_408         # sparse conv layer
    assert count('layer_2/conv/') == 16_783_360
    assert count('layer_1/attn/') == 10_485_888
    assert count('embed') == 16384 * 2048 and 'lm_head/kernel' not in spec


# ------------------------------------------------------------- hand counts
def test_train_flops_per_sample_against_a_hand_count():
    t, d = 8192, 2048
    conv = 2 * d * 6144 + 2 * d * d + (2 * 3 + 2) * d
    attn = 2 * d * 2048 + 2 * 2 * d * 512 + 2 * 2048 * d
    dense = 3 * 2 * d * 7168
    moe = 2 * d * 32 + (4 * 8 / 32) * 3 * 2 * d * 1792
    head = 2 * d * 16384
    attention = 2 * 2 * (t * (t + 1) / 2) * 64 * 32     # QK^T and PV
    want = 3 * t * (4 * conv + attn + dense + 4 * moe + head) \
        + 3 * attention
    got = ref.train_flops_per_sample(MODEL, {'seq_len': t})
    assert got == pytest.approx(want, rel=1e-12)
    # the issue's reckoning: 1.30 GFLOP a token
    assert 1.29e9 < got / t < 1.31e9
    # half as many experts held: only the routed experts' part halves
    half = ref.train_flops_per_sample(dict(MODEL, experts_held=4),
                                      {'seq_len': t})
    assert got - half == pytest.approx(
        3 * t * 4 * (4 * 4 / 32) * 3 * 2 * d * 1792)


@pytest.mark.parametrize('got,want', [
    (flops_lfm2.short_conv(100, 64, 3), (2 * 3 + 2) * 100 * 64),
    (flops_lfm2.short_conv(100, 64, 3, backward=True),
     (4 * 3 + 4) * 100 * 64),
    (flops_lfm2.short_conv(100, 64, 4), (2 * 4 + 2) * 100 * 64),
    (flops_lfm2.short_conv_bytes(100, 64, 3, 2), 4 * 100 * 64 * 2),
    (flops_lfm2.short_conv_bytes(100, 64, 3, 2, backward=True),
     7 * 100 * 64 * 2),
], ids=['conv', 'conv_bwd', 'conv_4_taps', 'conv_bytes', 'conv_bytes_bwd'])
def test_operations_and_bytes_against_hand_counts(got, want):
    assert got == want


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


def hlo(name, n=3):
    return (f'%{name}.{n} = bf16[2,8192,2048]{{2,1,0}} custom-call('
            f'%x), custom_call_target="tpu_custom_call"')


def test_short_conv_roofline_reads_its_ops_by_name():
    ops = [(hlo('short_conv_fwd'), 2e-3), (hlo('short_conv_fwd', 8), 1e-3),
           (hlo('short_conv_bwd'), 5e-3), (hlo('gqa_attn'), 3.0),
           (hlo('gmm'), 4.0),
           ('%fusion.4 = bf16[2,128,2048] fusion(%y)', 7.0)]
    run = fake_run(ops)
    # 2 train sequences (forward + backward), 2 validation (forward) of
    # 128 tokens, 4 conv layers; the bytes bound it
    by_bytes = 4 * (4 * flops_lfm2.short_conv_bytes(128, 2048, 3, 2)
                    + 2 * flops_lfm2.short_conv_bytes(128, 2048, 3, 2,
                                                      True)) / 1e11
    by_flops = 4 * (4 * flops_lfm2.short_conv(128, 2048, 3)
                    + 2 * flops_lfm2.short_conv(128, 2048, 3, True)) / 1e12
    assert by_bytes > by_flops
    assert MANIFEST.reader('short_conv_roofline')(
        run, 'short_conv_roofline') == pytest.approx(
        100 * by_bytes / 8e-3)
    assert any('bound by bandwidth' in note for note in run.notes)


@pytest.mark.parametrize('why', ['no trace', 'no such op', 'no kernels',
                                 'no peaks'])
def test_short_conv_roofline_with_nothing_to_read_is_none(why):
    """The parent of this PR has no such op: the reader says nothing,
    and never 0."""
    ops = [(hlo('short_conv_fwd'), 1e-3), (hlo('short_conv_bwd'), 1e-3)]
    run = {'no trace': lambda: fake_run(),
           'no such op': lambda: fake_run([(hlo('gqa_attn'), 1.0)]),
           'no kernels': lambda: fake_run(
               ops, config=MANIFEST.config('qwen3-next-80b-a3b')),
           'no peaks': lambda: fake_run(ops, peaks=None)}[why]()
    assert MANIFEST.reader('short_conv_roofline')(
        run, 'short_conv_roofline') is None


def test_the_accepted_readers_read_the_new_cell():
    """``gqa_attn_roofline`` and ``expert_matmul_roofline`` take their
    sizes from this configuration's ``kernels`` block."""
    from benchmark import flops, flops_qwen3_next as more
    ops = [(hlo('gqa_attn'), 2e-3), (hlo('gmm'), 1e-3), (hlo('tgmm'), 1e-3)]
    run = fake_run(ops, {'moe.local_assign_share': [(0, 0.9, 0),
                                                     (1, 0.25, 0)]})
    fwd = flops.causal_attention(128, 32, 64)
    by_flops = (2 * 3 * fwd + 2 * fwd) / 1e12
    by_bytes = (4 * more.gqa_attention_bytes(128, 32, 8, 64, 2)
                + 2 * more.gqa_attention_bytes(128, 32, 8, 64, 2, True)
                ) / 1e11
    assert MANIFEST.reader('gqa_attn_roofline')(
        run, 'gqa_attn_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 2e-3)
    pairs = 128 * 4 * 0.25
    fwd = more.expert_matmul(pairs, 2048, 1792)
    by_flops = 4 * (2 * 3 * fwd + 2 * fwd) / 1e12
    one = more.expert_matmul_bytes(8, 2 * pairs, 2048, 1792, 4, 2)
    by_bytes = 4 * (2 * one + 2 * one) / 1e11
    assert MANIFEST.reader('expert_matmul_roofline')(
        run, 'expert_matmul_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 2e-3)


# ------------------------------------------------- the manifest, rehearsed
def test_manifest_check_exits_0_with_four_cells():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'manifest.py'),
         '--check'], capture_output=True, text=True, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:]
    assert '4 cells, nothing lacking' in done.stdout
    assert MANIFEST.workload(CELL)['chips'] == 1
    assert MANIFEST.data['workloads'][-1]['name'] == CELL
    assert MANIFEST.data['configs'][-1]['name'] == 'lfm2-8b-a1b'
    assert MANIFEST.data['per_layer'][-1]['name'] == 'short_conv_roofline'
    reports = {m['name'] for m in MANIFEST.metrics('per_layer', CELL)}
    assert not reports & {'flash_attn_roofline', 'gated_delta_roofline'}
    assert {'short_conv_roofline', 'gqa_attn_roofline',
            'expert_matmul_roofline', 'moe_local_assign_pct',
            'moe_load_max_over_mean', 'step_mfu_pct.tokens',
            'step_device_ms.tokens', 'device_idle_pct.tokens',
            'host_input_ms.tokens', 'epoch_boundary_ms.tokens',
            'epoch_boundary_idle_ms.tokens', 'setup_span_s.data',
            'setup_span_s.state', 'setup_span_s.introspect',
            'setup_span_s.epoch0'} == reports
    for metric in MANIFEST.data['per_layer'] + MANIFEST.data['end_to_end']:
        if CELL in metric.get('workloads', ()):
            assert metric['workloads'][-1] == CELL, metric['name']
    # what the accepted test of the qwen cell asserts beside its pinned
    # "3 cells" (tests/conftest.py marks that test; PERF.md section 7 p)
    qwen = 'qwen3-next-80b-a3b.steady'
    assert MANIFEST.workload(qwen)['chips'] == 1
    reports = {m['name'] for m in MANIFEST.metrics('per_layer', qwen)}
    assert 'flash_attn_roofline' not in reports
    assert {'gated_delta_roofline', 'gqa_attn_roofline',
            'expert_matmul_roofline', 'moe_local_assign_pct',
            'moe_load_max_over_mean', 'step_mfu_pct.tokens'} <= reports


@pytest.mark.parametrize('trace', [0, 1], ids=['untraced', 'traced'])
def test_the_cell_rehearses(trace, tmp_path):
    """The whole of a run at a tiny size (the five layers, 8 of 16
    experts held, top-2) through the normal path, float32 so that the
    CPU's numbers are sharp."""
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal',
                           f'{CELL}.json')) as fh:
        tiny = json.load(fh)
    tiny['config']['executor']['model']['dtype'] = 'float32'
    tiny['config']['executor']['mesh'] = {'dp': 1}
    tiny['cell']['limits'] = {'loss_gap': 1e-3, 'grad_gap': 0.05,
                              'delta_gap': 0.05}
    line = rehearse.rehearse(CELL, seed=3_300_000_011, seconds=0.5,
                             trace=trace, tiny=tiny, out=str(tmp_path))
    assert line['correct'] is True and line['failed'] == 0, line
    names = set(line['metrics'])
    assert all(n.startswith('cpu_rehearsal.') for n in names)
    if not trace:
        assert names == {'cpu_rehearsal.train_tokens_per_s',
                         'cpu_rehearsal.setup_s'}
        return
    # the counters come out of the step and through the metric table;
    # the CPU has no device trace, so the rooflines stay silent
    assert 0 < line['metrics']['cpu_rehearsal.moe_local_assign_pct'][
        'value'] < 100
    assert line['metrics']['cpu_rehearsal.moe_load_max_over_mean'][
        'value'] >= 1
    assert 'cpu_rehearsal.host_input_ms.tokens' in names
    assert not any('roofline' in n for n in names)
