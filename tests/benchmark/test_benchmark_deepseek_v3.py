"""What PR 35 adds to the benchmark: ``flops_deepseek_v3`` and the
``deepseek_v3`` reference's required FLOPs against counts written by
hand, the configuration's file against the catalog row's keys and
against ``executor.model``, the parameters of the share,
``mla_attn_roofline`` on a made-up op table (reads by name; ``None`` with
no such op), the manifest's own check with the cells counted from
``BENCHMARK.json`` — every assertion that the two accepted tests with a
pinned count make, without the pin (``tests/conftest.py`` marks those
two; ``PERF.md`` section 7 p) — and the new cell's rehearsal on the
CPU."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops_deepseek_v3, rehearse
from benchmark.manifest import Manifest
from benchmark.reference import deepseek_v3 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = 'kanana-2-30b-a3b.steady'
NAME = 'kanana-2-30b-a3b'
MANIFEST = Manifest(ROOT)
CONFIG = MANIFEST.config(NAME)
MODEL = CONFIG['executor']['model']
#: the `config` of the catalog's row kanana-2-30b-a3b-instruct-2601
#: (model-configs/architectures.jsonl), as published
PUBLISHED = {
    'attention_bias': False, 'first_k_dense_replace': 1, 'head_dim': 64,
    'hidden_act': 'silu', 'hidden_size': 2048, 'intermediate_size': 6144,
    'kv_lora_rank': 512, 'max_position_embeddings': 32768,
    'model_type': 'deepseek_v3', 'moe_intermediate_size': 768,
    'moe_layer_freq': 1, 'n_group': 1, 'n_routed_experts': 128,
    'n_shared_experts': 2, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_experts_per_tok': 6,
    'num_hidden_layers': 48, 'num_key_value_heads': 32,
    'q_lora_rank': None, 'qk_head_dim': 192, 'qk_nope_head_dim': 128,
    'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06, 'rope_interleave': True,
    'rope_scaling': None, 'rope_theta': 1000000,
    'routed_scaling_factor': 2.448, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_group': 1,
    'topk_method': 'noaux_tc', 'v_head_dim': 128, 'vocab_size': 128256}
#: the repo's name of each published key the model reads
NAMES = {
    'hidden_size': 'd_model', 'num_hidden_layers': 'n_layers',
    'first_k_dense_replace': 'n_dense_layers', 'intermediate_size': 'd_ff',
    'num_attention_heads': 'n_heads', 'kv_lora_rank': 'kv_lora_rank',
    'qk_nope_head_dim': 'qk_nope_head_dim',
    'qk_rope_head_dim': 'qk_rope_head_dim', 'v_head_dim': 'v_head_dim',
    'rope_theta': 'rope_theta',
    'n_routed_experts': 'experts_held', 'num_experts_per_tok': 'top_k',
    'moe_intermediate_size': 'd_expert',
    'n_shared_experts': 'n_shared_experts',
    'routed_scaling_factor': 'routed_scaling_factor',
    'rms_norm_eps': 'rms_eps', 'vocab_size': 'vocab_size'}


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


#: published keys of which the family has ONE form: constants of the
#: model's class (no field, no key under ``executor.model``)
FIXED = {'rope_interleave': ('rope_interleave', True),
         'norm_topk_prob': ('norm_topk_prob', True),
         'scoring_func': ('router_score', 'sigmoid'),
         'topk_method': ('expert_bias', True),
         'n_shared_experts': ('shared_gate', False)}


@pytest.mark.parametrize('published', sorted(FIXED))
def test_the_class_fixes_what_the_family_has_one_form_of(published):
    import dataclasses
    from mlcomp_tpu.models.deepseek_v3 import DeepseekV3Config
    name, value = FIXED[published]
    assert CONFIG[published] == PUBLISHED[published]
    assert getattr(DeepseekV3Config, name) == value
    assert getattr(DeepseekV3Config(), name) == value
    assert name not in {f.name for f in
                        dataclasses.fields(DeepseekV3Config)}
    assert name not in MODEL


def test_the_cut_keeps_the_floors_and_every_width():
    assert CONFIG['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                                 'vocab_size']
    assert MANIFEST._by_name('configs', NAME)['reduced'] \
        == CONFIG['reduced']
    assert MANIFEST._by_name('configs', NAME)['source'] \
        == CONFIG['source']
    # the leading dense layer and four sparse layers; at least 8 routed
    # experts and an eighth of the vocabulary: the guide's floors
    assert MODEL['n_layers'] - MODEL['n_dense_layers'] == 4
    assert MODEL['n_experts'] == 128 and MODEL['experts_held'] == 16 >= 8
    assert MODEL['vocab_size'] * 8 == 128256
    assert 'one of 8 chips' in CONFIG['deployment']
    assert '575,955,968' in CONFIG['deployment']
    # the widths, as published
    assert MODEL['qk_nope_head_dim'] + MODEL['qk_rope_head_dim'] \
        == CONFIG['qk_head_dim'] == 192
    assert MODEL['n_heads'] == CONFIG['num_key_value_heads'] == 32
    assert CONFIG['topk_method'] == 'noaux_tc'
    assert CONFIG['n_group'] == CONFIG['topk_group'] == 1
    assert CONFIG['q_lora_rank'] is None
    assert 0 < MODEL['expert_bias_update_rate'] <= 0.01
    assert MODEL['norm_topk_eps'] == 1e-20
    assert {'aux_loss', 'mtp', 'norm_topk_eps', 'expert_bias',
            'optimizer', 'moe_buffer_factor'} <= set(CONFIG['assumed'])
    # the optimizer is the lfm2 configuration's, letter for letter
    assert CONFIG['executor']['optimizer'] == MANIFEST.config(
        'lfm2-8b-a1b')['executor']['optimizer']
    kernels = CONFIG['kernels']
    assert set(kernels) == {'mla_attn', 'expert_matmul'}
    assert kernels['mla_attn'] == {
        'ops': ['mla_attn'], 'heads': 32, 'qk_head_dim': 192,
        'qk_rope_head_dim': 64, 'v_head_dim': 128, 'attention_layers': 5}
    assert kernels['expert_matmul'] == {
        'ops': ['gmm', 'tgmm'], 'held': 16, 'of': 128, 'top_k': 6,
        'd_model': 2048, 'd_expert': 768, 'moe_layers': 4,
        'weight_itemsize': 4}
    cell = MANIFEST.cell(CELL)
    assert cell['data']['vocab_size'] == MODEL['vocab_size']
    assert cell['samples_per_row'] == cell['data']['seq_len'] == 8192
    # a held expert's rows a step, and the buffer against the even
    # share and the worst case
    tokens = cell['executor']['batch_size'] * cell['data']['seq_len']
    assert tokens * MODEL['top_k'] / MODEL['n_experts'] == 768
    from mlcomp_tpu.models.decoder_parts import MoeConfig, buffer_rows
    moe = MoeConfig(
        d_model=2048, d_expert=768, n_experts=128, top_k=6,
        experts_held=16, moe_buffer_factor=MODEL['moe_buffer_factor'])
    assert buffer_rows(moe, tokens) == 49152 == 4 * 12288 < 98304


def test_parameters_of_the_share():
    """575,955,968 parameters: 6.91 GB of float32 arguments."""
    import numpy as np
    spec = ref.param_spec(MODEL)
    count = lambda prefix: sum(  # noqa: E731
        int(np.prod(s)) for p, (s, _) in spec.items()
        if p.startswith(prefix))
    assert count('') == 575_955_968
    assert count('layer_0/') == 64_098_816          # the dense layer
    assert count('layer_1/') == 111_547_008         # a sparse layer
    assert count('layer_1/attn/') == 26_345_984
    assert count('layer_1/moe/wi') + count('layer_1/moe/wo') \
        == 16 * 4_718_592
    assert count('layer_1/moe/shared/') == 9_437_184
    assert count('layer_1/moe/router') == 262_144
    assert count('layer_1/moe/') == 75_497_472 + 9_437_184 + 262_144 + 128
    assert count('embed') == count('lm_head/') == 16032 * 2048
    assert not any('shared_gate' in p for p in spec)


# ------------------------------------------------------------- hand counts
def test_train_flops_per_sample_against_a_hand_count():
    t, d = 8192, 2048
    attn = 2 * d * 32 * 192 + 2 * d * 576 + 2 * 512 * 32 * 256 \
        + 2 * 32 * 128 * d
    dense = 3 * 2 * d * 6144
    moe = 2 * d * 128 + 3 * 2 * d * 1536 \
        + (6 * 16 / 128) * 3 * 2 * d * 768
    head = 2 * d * 16032
    attention = 2 * (t * (t + 1) / 2) * (192 + 128) * 32    # QK^T and PV
    want = 3 * t * (5 * attn + dense + 4 * moe + head) \
        + 3 * 5 * attention
    got = ref.train_flops_per_sample(MODEL, {'seq_len': t})
    assert got == pytest.approx(want, rel=1e-12)
    # the issue's reckoning: 2.79 GFLOP a token, attention 45% of it
    assert 2.78e9 < got / t < 2.80e9
    assert 0.45 < 3 * 5 * attention / got < 0.46
    # half as many experts held: only the routed experts' part halves
    half = ref.train_flops_per_sample(dict(MODEL, experts_held=8),
                                      {'seq_len': t})
    assert got - half == pytest.approx(
        3 * t * 4 * (6 * 8 / 128) * 3 * 2 * d * 768)


PAIRS = 100 * 101 / 2


@pytest.mark.parametrize('got,want', [
    (flops_deepseek_v3.mla_attention(100, 4, 192, 128),
     2 * PAIRS * (192 + 128) * 4),
    (flops_deepseek_v3.mla_attention(100, 4, 192, 128, backward=True),
     4 * PAIRS * (192 + 128) * 4),
    (flops_deepseek_v3.mla_attention(100, 4, 128, 128),
     2 * 2 * PAIRS * 128 * 4),               # equal heads: flops.py's
    (flops_deepseek_v3.mla_attention_bytes(100, 4, 192, 64, 128, 2),
     100 * 2 * (4 * 192 + (4 * 128 + 64) + 2 * 4 * 128)),
    (flops_deepseek_v3.mla_attention_bytes(100, 4, 192, 64, 128, 2,
                                           backward=True),
     100 * 2 * (2 * 4 * 192 + 2 * (4 * 128 + 64) + 4 * 4 * 128)),
    (flops_deepseek_v3.mla_attention_bytes(100, 4, 128, 0, 128, 2),
     4 * 100 * 4 * 128 * 2),                 # no shared part: flops.py's
], ids=['mla', 'mla_bwd', 'equal_heads', 'bytes', 'bytes_bwd',
        'bytes_no_shared_part'])
def test_operations_and_bytes_against_hand_counts(got, want):
    assert got == want


def test_the_counts_agree_with_flops_py_at_equal_heads():
    from benchmark import flops
    for backward in (False, True):
        assert flops_deepseek_v3.mla_attention(
            512, 8, 64, 64, backward=backward) \
            == flops.causal_attention(512, 8, 64, backward=backward)
        assert flops_deepseek_v3.mla_attention_bytes(
            512, 8, 64, 0, 64, 2, backward=backward) \
            == flops.attention_bytes(512, 8, 64, 2, backward=backward)


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


def hlo(name, n=3, shape='bf16[64,8192,128]'):
    return (f'%{name}.{n} = {shape}{{2,1,0}} custom-call('
            f'%x), custom_call_target="tpu_custom_call"')


def test_mla_attn_roofline_reads_its_ops_by_name():
    ops = [(hlo('mla_attn'), 2e-3),
           (hlo('mla_attn', 8, 'bf16[1,64,8192,192]'), 5e-3),
           (hlo('gqa_attn'), 3.0), (hlo('gmm'), 4.0),
           ('%fusion.4 = bf16[2,128,2048] fusion(%y)', 7.0)]
    run = fake_run(ops)
    # 2 train sequences (forward + backward), 2 validation (forward) of
    # 128 tokens, 5 attention layers
    fwd = flops_deepseek_v3.mla_attention(128, 32, 192, 128)
    by_flops = 5 * (2 * 3 * fwd + 2 * fwd) / 1e12
    size = (128, 32, 192, 64, 128, 2)
    by_bytes = 5 * (4 * flops_deepseek_v3.mla_attention_bytes(*size)
                    + 2 * flops_deepseek_v3.mla_attention_bytes(
                        *size, backward=True)) / 1e11
    assert MANIFEST.reader('mla_attn_roofline')(
        run, 'mla_attn_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 7e-3)
    assert any('ops [\'mla_attn\']' in note for note in run.notes)


@pytest.mark.parametrize('why', ['no trace', 'no such op', 'no kernels',
                                 'no peaks'])
def test_mla_attn_roofline_with_nothing_to_read_is_none(why):
    """The parent of this PR has no such op, and no other cell's
    configuration names the kernel: the reader says nothing, and never
    0."""
    ops = [(hlo('mla_attn'), 1e-3)]
    run = {'no trace': lambda: fake_run(),
           'no such op': lambda: fake_run([(hlo('gqa_attn'), 1.0)]),
           'no kernels': lambda: fake_run(
               ops, config=MANIFEST.config('lfm2-8b-a1b')),
           'no peaks': lambda: fake_run(ops, peaks=None)}[why]()
    assert MANIFEST.reader('mla_attn_roofline')(
        run, 'mla_attn_roofline') is None


def test_the_accepted_readers_read_the_new_cell():
    """``expert_matmul_roofline`` takes its sizes from this
    configuration's ``kernels`` block; ``gqa_attn_roofline`` and
    ``flash_attn_roofline`` find no block of theirs and say nothing."""
    from benchmark import flops_qwen3_next as more
    ops = [(hlo('mla_attn'), 2e-3), (hlo('gmm'), 1e-3),
           (hlo('tgmm'), 1e-3)]
    run = fake_run(ops, {'moe.local_assign_share': [(0, 0.9, 0),
                                                     (1, 0.125, 0)]})
    pairs = 128 * 6 * 0.125
    fwd = more.expert_matmul(pairs, 2048, 768)
    by_flops = 4 * (2 * 3 * fwd + 2 * fwd) / 1e12
    one = more.expert_matmul_bytes(16, 2 * pairs, 2048, 768, 4, 2)
    by_bytes = 4 * (2 * one + 2 * one) / 1e11
    assert MANIFEST.reader('expert_matmul_roofline')(
        run, 'expert_matmul_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 2e-3)
    assert MANIFEST.reader('gqa_attn_roofline')(
        run, 'gqa_attn_roofline') is None
    assert MANIFEST.reader('moe_local_assign_pct')(
        run, 'moe_local_assign_pct') == pytest.approx(12.5)


# ------------------------------------------------- the manifest, rehearsed
def names(group):
    return [entry['name'] for entry in MANIFEST.data[group]]


#: what each accepted sparse cell reports beside the `.tokens` readers
#: and the set-up spans
TOKENS = {'step_mfu_pct.tokens', 'step_device_ms.tokens',
          'device_idle_pct.tokens', 'host_input_ms.tokens',
          'epoch_boundary_ms.tokens', 'epoch_boundary_idle_ms.tokens',
          'setup_span_s.data', 'setup_span_s.state',
          'setup_span_s.introspect', 'setup_span_s.epoch0'}
ROUTED = {'expert_matmul_roofline', 'moe_local_assign_pct',
          'moe_load_max_over_mean'}
REPORTS = {
    'qwen3-next-80b-a3b.steady':
        TOKENS | ROUTED | {'gated_delta_roofline', 'gqa_attn_roofline'},
    'lfm2-8b-a1b.steady':
        TOKENS | ROUTED | {'gqa_attn_roofline', 'short_conv_roofline'},
    CELL: TOKENS | ROUTED | {'mla_attn_roofline'},
}


def test_manifest_check_exits_0_with_the_cells_it_holds():
    """``manifest.py --check`` with the count read from
    ``BENCHMARK.json``: nothing lacking, and this PR's entries after
    the accepted ones of their groups."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'manifest.py'),
         '--check'], capture_output=True, text=True, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:]
    cells = len(MANIFEST.data['workloads'])
    assert cells >= 5
    assert f'{cells} cells, nothing lacking' in done.stdout
    assert MANIFEST.workload(CELL)['chips'] == 1
    accepted = ['resnet18-cifar10', 'olmo-1b', 'qwen3-next-80b-a3b',
                'lfm2-8b-a1b']
    assert names('configs')[:5] == accepted + [NAME]
    assert names('workloads')[:5] == [c + '.steady' for c in accepted] \
        + [CELL]
    per_layer = names('per_layer')
    assert per_layer.index('mla_attn_roofline') \
        == per_layer.index('short_conv_roofline') + 1


@pytest.mark.parametrize('cell', sorted(REPORTS))
def test_what_each_sparse_cell_reports(cell):
    """The assertions of the two accepted tests that pin a cell count
    (qwen's "3 cells", lfm2's "4 cells" and last places), made so that
    the NEXT cell does not trip them: a cell's name stands after the
    accepted ones in every list it is in, in the order the cells were
    added."""
    assert MANIFEST.workload(cell)['chips'] == 1
    reports = {m['name'] for m in MANIFEST.metrics('per_layer', cell)}
    assert reports == REPORTS[cell]
    assert 'flash_attn_roofline' not in reports
    order = names('workloads')
    for metric in MANIFEST.data['per_layer'] + MANIFEST.data['end_to_end']:
        listed = metric.get('workloads', ())
        if cell in listed:
            assert [order.index(c) for c in listed] == sorted(
                order.index(c) for c in listed), metric['name']
            assert set(listed[listed.index(cell) + 1:]) <= set(
                order[order.index(cell) + 1:]), metric['name']


def test_the_two_pinned_tests_are_marked_and_only_they(request):
    """``tests/conftest.py`` marks exactly the two accepted tests that
    pin a count ``xfail``, strict: the `benchmark` PR that makes them
    count ``BENCHMARK.json``'s cells has to drop both marks."""
    marked = [item.nodeid for item in request.session.items
              if any(m.name == 'xfail' and m.kwargs.get('strict')
                     and 'pins' in m.kwargs.get('reason', '')
                     for m in item.iter_markers())]
    here = {n.split('tests/benchmark/')[-1] for n in marked}
    want = {'test_benchmark_qwen3_next.py::test_manifest_check_exits_0',
            'test_benchmark_lfm2_moe.py::'
            'test_manifest_check_exits_0_with_four_cells'}
    # a run of this file alone collects neither
    assert here <= want and (not here or here == want)


@pytest.mark.parametrize('trace', [0, 1], ids=['untraced', 'traced'])
def test_the_cell_rehearses(trace, tmp_path):
    """The whole of a run at a tiny size (the five layers, 4 of 16
    experts held, top-3, score heads of 24 and value heads of 16)
    through the normal path, float32 so that the CPU's numbers are
    sharp."""
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal',
                           f'{CELL}.json')) as fh:
        tiny = json.load(fh)
    tiny['config']['executor']['model']['dtype'] = 'float32'
    tiny['config']['executor']['mesh'] = {'dp': 1}
    tiny['cell']['limits'] = {'loss_gap': 1e-3, 'grad_gap': 0.05,
                              'delta_gap': 0.05}
    line = rehearse.rehearse(CELL, seed=3_500_000_011, seconds=0.5,
                             trace=trace, tiny=tiny, out=str(tmp_path))
    assert line['correct'] is True and line['failed'] == 0, line
    got = set(line['metrics'])
    assert all(n.startswith('cpu_rehearsal.') for n in got)
    if not trace:
        assert got == {'cpu_rehearsal.train_tokens_per_s',
                       'cpu_rehearsal.setup_s'}
        return
    # the counters come out of the step and through the metric table;
    # the CPU has no device trace, so the rooflines stay silent
    assert 0 < line['metrics']['cpu_rehearsal.moe_local_assign_pct'][
        'value'] < 100
    assert line['metrics']['cpu_rehearsal.moe_load_max_over_mean'][
        'value'] >= 1
    assert 'cpu_rehearsal.host_input_ms.tokens' in got
    assert not any('roofline' in n for n in got)
