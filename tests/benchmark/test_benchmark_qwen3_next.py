"""What PR 28 adds to the benchmark: the ``qwen3_next`` reference's
required FLOPs and the new FLOP/byte functions against hand counts, the
configuration's file against the published keys, each new reader on a
synthetic reduced trace (present, and absent -> ``None``), the manifest's
own check, and the new cell's rehearsal on the CPU with and without a
trace."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops, flops_qwen3_next as more, rehearse
from benchmark.manifest import Manifest
from benchmark.reference import qwen3_next as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = 'qwen3-next-80b-a3b.steady'
MANIFEST = Manifest(ROOT)
CONFIG = MANIFEST.config('qwen3-next-80b-a3b')
MODEL = CONFIG['executor']['model']
#: the repo's name of each published key the model reads
NAMES = {
    'hidden_size': 'd_model', 'num_hidden_layers': 'n_layers',
    'full_attention_interval': 'full_attention_interval',
    'num_attention_heads': 'n_heads', 'num_key_value_heads': 'n_kv_heads',
    'head_dim': 'head_dim', 'partial_rotary_factor':
    'partial_rotary_factor', 'rope_theta': 'rope_theta',
    'linear_num_key_heads': 'linear_key_heads',
    'linear_num_value_heads': 'linear_value_heads',
    'linear_key_head_dim': 'linear_key_dim',
    'linear_value_head_dim': 'linear_value_dim',
    'linear_conv_kernel_dim': 'linear_conv_kernel',
    'num_experts': 'experts_held', 'num_experts_per_tok': 'top_k',
    'moe_intermediate_size': 'd_expert',
    'shared_expert_intermediate_size': 'd_shared',
    'norm_topk_prob': 'norm_topk_prob', 'rms_norm_eps': 'rms_eps',
    'vocab_size': 'vocab_size'}


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize('published', sorted(NAMES))
def test_the_model_runs_the_published_key(published):
    assert MODEL[NAMES[published]] == CONFIG[published]


def test_only_depth_experts_held_and_vocabulary_are_cut():
    assert CONFIG['reduced'] == ['num_hidden_layers', 'num_experts',
                                 'vocab_size']
    assert CONFIG['published'] == dict(
        CONFIG['published'], num_hidden_layers=48, num_experts=512,
        vocab_size=151936)
    # the router keeps the published width; the floors of the guide
    assert MODEL['n_experts'] == 512 and MODEL['experts_held'] >= 8
    assert MODEL['n_layers'] % MODEL['full_attention_interval'] == 0
    assert MODEL['vocab_size'] * 8 == 151936
    assert 'flash_attn' not in CONFIG['kernels']
    cell = MANIFEST.cell(CELL)
    assert cell['data']['vocab_size'] == MODEL['vocab_size']
    assert cell['samples_per_row'] == cell['data']['seq_len'] == 8192


def test_parameters_of_the_share():
    """625,667,136 parameters: 7.51 GB of float32 arguments."""
    import numpy as np
    spec = ref.param_spec(MODEL)
    assert sum(int(np.prod(s)) for s, _ in spec.values()) == 625_667_136
    linear = sum(int(np.prod(s)) for p, (s, _) in spec.items()
                 if p.startswith('layer_0/linear_attn/'))
    full = sum(int(np.prod(s)) for p, (s, _) in spec.items()
               if p.startswith('layer_3/full_attn/'))
    assert (linear, full) == (33_718_464, 27_263_488)


# ------------------------------------------------------------- hand counts
def test_train_flops_per_sample_against_a_hand_count():
    t, d = 8192, 2048
    linear = 2 * d * 12288 + 2 * d * 64 + 2 * 4096 * d + 2 * 4 * 8192 \
        + 7 * 32 * 128 * 128            # the delta rule, recurrent
    full = 2 * d * 8192 + 2 * 2 * d * 512 + 2 * 4096 * d
    moe = 2 * d * 512 + 3 * 2 * d * 512 + 2 * d \
        + (10 * 32 / 512) * 3 * 2 * d * 512
    head = 2 * d * 18992
    attention = 2 * 2 * (t * (t + 1) / 2) * 256 * 16    # QK^T and PV
    want = 3 * t * (3 * linear + full + 4 * moe + head) + 3 * attention
    got = ref.train_flops_per_sample(MODEL, {'seq_len': t})
    assert got == pytest.approx(want, rel=1e-12)
    # the issue's reckoning: about 1.4 GFLOP a token
    assert 1.35e9 < got / t < 1.5e9
    # half as many experts held: only the routed experts' part halves
    half = ref.train_flops_per_sample(dict(MODEL, experts_held=16),
                                      {'seq_len': t})
    assert got - half == pytest.approx(
        3 * t * 4 * (10 * 16 / 512) * 3 * 2 * d * 512)


@pytest.mark.parametrize('got,want', [
    (more.gated_delta(64, 2, 8, 16), 7 * 64 * 2 * 8 * 16),
    (more.gated_delta(64, 2, 8, 16, backward=True),
     2 * 7 * 64 * 2 * 8 * 16),
    (more.gated_delta_bytes(10, 2, 8, 16, 2),
     10 * 2 * ((8 + 8 + 16) * 2 + 8) + 10 * 2 * 16 * 2),
    (more.gated_delta_bytes(10, 2, 8, 16, 2, backward=True),
     2 * 10 * 2 * ((8 + 8 + 16) * 2 + 8) + 10 * 2 * 16 * 2),
    (more.gqa_attention_bytes(10, 16, 2, 256, 2),
     2 * 10 * 16 * 256 * 2 + 2 * 10 * 2 * 256 * 2),
    (more.gqa_attention_bytes(10, 16, 2, 256, 2, backward=True),
     4 * 10 * 16 * 256 * 2 + 4 * 10 * 2 * 256 * 2),
    (more.gqa_attention_bytes(10, 4, 4, 8, 2),
     flops.attention_bytes(10, 4, 8, 2)),
    (more.expert_matmul(100, 32, 16), 3 * 2 * 100 * 32 * 16),
    (more.expert_matmul(100, 32, 16, backward=True),
     2 * 3 * 2 * 100 * 32 * 16),
    (more.expert_matmul_bytes(4, 100, 32, 16, 4, 2),
     3 * 4 * 32 * 16 * 4 + 2 * 100 * 32 * 2),
    (more.expert_matmul_bytes(4, 100, 32, 16, 4, 2, backward=True),
     2 * (3 * 4 * 32 * 16 * 4 + 2 * 100 * 32 * 2)),
], ids=['delta', 'delta_bwd', 'delta_bytes', 'delta_bytes_bwd',
        'gqa_bytes', 'gqa_bytes_bwd', 'gqa_bytes_equal_heads', 'experts',
        'experts_bwd', 'experts_bytes', 'experts_bytes_bwd'])
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
    return (f'%{name}.{n} = bf16[8,8,64,128]{{3,2,1,0}} custom-call('
            f'%x), custom_call_target="tpu_custom_call"')


def test_kernel_rooflines_read_their_ops_by_name():
    seq = 128
    ops = [(hlo('gated_delta_fwd'), 3e-3), (hlo('gated_delta_fwd', 7), 1e-3),
           (hlo('gated_delta_bwd_scan'), 4e-3), (hlo('gqa_attn'), 2e-3),
           (hlo('gqa_attn', 9), 2e-3), (hlo('gmm'), 1e-3),
           (hlo('tgmm'), 1e-3), (hlo('attn'), 5.0),
           ('%fusion.4 = bf16[2,128,2048] fusion(%y)', 7.0)]
    run = fake_run(ops, {'moe.local_assign_share': [(0, 0.5, 0),
                                                     (1, 0.0625, 0)]})
    # 2 train sequences (forward + backward), 2 validation (forward)
    shape = (seq, 32, 128, 128)
    need = 3 * (2 * 3 * more.gated_delta(*shape)
                + 2 * more.gated_delta(*shape))
    assert MANIFEST.reader('gated_delta_roofline')(
        run, 'gated_delta_roofline') == pytest.approx(
        100 * need / 1e12 / 8e-3)
    fwd = flops.causal_attention(seq, 16, 256)
    by_flops = (2 * 3 * fwd + 2 * fwd) / 1e12
    by_bytes = (4 * more.gqa_attention_bytes(seq, 16, 2, 256, 2)
                + 2 * more.gqa_attention_bytes(seq, 16, 2, 256, 2, True)
                ) / 1e11
    assert MANIFEST.reader('gqa_attn_roofline')(
        run, 'gqa_attn_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 4e-3)
    pairs = seq * 10 * 0.0625           # the window's share, not epoch 0's
    fwd = more.expert_matmul(pairs, 2048, 512)
    by_flops = 4 * (2 * 3 * fwd + 2 * fwd) / 1e12
    one = more.expert_matmul_bytes(32, 2 * pairs, 2048, 512, 4, 2)
    by_bytes = 4 * (2 * one + 2 * one) / 1e11   # 2 passes + a backward
    assert MANIFEST.reader('expert_matmul_roofline')(
        run, 'expert_matmul_roofline') == pytest.approx(
        100 * max(by_flops, by_bytes) / 2e-3)
    assert any('bound by' in note for note in run.notes)


@pytest.mark.parametrize('metric', [
    'gated_delta_roofline', 'gqa_attn_roofline', 'expert_matmul_roofline'])
@pytest.mark.parametrize('why', ['no trace', 'no such op', 'no kernels',
                                 'no peaks'])
def test_a_roofline_with_nothing_to_read_is_none(metric, why):
    ops = [(hlo('gated_delta_fwd'), 1e-3), (hlo('gqa_attn'), 1e-3),
           (hlo('gmm'), 1e-3)]
    run = {'no trace': lambda: fake_run(),
           'no such op': lambda: fake_run([(hlo('attn'), 1.0)]),
           'no kernels': lambda: fake_run(
               ops, config=MANIFEST.config('olmo-1b')),
           'no peaks': lambda: fake_run(ops, peaks=None)}[why]()
    assert MANIFEST.reader(metric)(run, metric) is None


def test_counter_readers():
    rows = {'moe.local_assign_share': [(0, 0.9, 0), (1, 0.06, 0),
                                       (2, 0.065, 0)],
            'moe.load_max_over_mean': [(0, 9.0, 0), (1, 1.5, 0),
                                       (2, 1.7, 0)],
            'moe.dropped': [(0, 0.0, 0), (1, 0.0, 0), (2, 0.0, 0)]}
    run = fake_run(series=rows)
    assert MANIFEST.reader('moe_local_assign_pct')(
        run, 'moe_local_assign_pct') == pytest.approx(6.25)
    assert MANIFEST.reader('moe_load_max_over_mean')(
        run, 'moe_load_max_over_mean') == pytest.approx(1.6)
    assert any('moe.dropped reads 0.0' in note for note in run.notes)
    # a program without the counters (the parent): nothing, not 0
    for metric in ('moe_local_assign_pct', 'moe_load_max_over_mean'):
        assert MANIFEST.reader(metric)(fake_run(), metric) is None


# ------------------------------------------------- the manifest, rehearsed
def test_manifest_check_exits_0():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'manifest.py'),
         '--check'], capture_output=True, text=True, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:]
    assert '3 cells, nothing lacking' in done.stdout
    entry = MANIFEST.workload(CELL)
    assert entry['chips'] == 1
    reports = {m['name'] for m in MANIFEST.metrics('per_layer', CELL)}
    assert 'flash_attn_roofline' not in reports
    assert {'gated_delta_roofline', 'gqa_attn_roofline',
            'expert_matmul_roofline', 'moe_local_assign_pct',
            'moe_load_max_over_mean', 'step_mfu_pct.tokens'} <= reports


@pytest.fixture(scope='module')
def _compile_once(tmp_path_factory):
    """The two rehearsals below compile the same programs: keep them for
    the length of this file, then put jax back as it was."""
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


@pytest.mark.parametrize('trace', [0, 1], ids=['untraced', 'traced'])
def test_the_cell_rehearses(trace, tmp_path, _compile_once):
    """The whole of a run at a tiny size (all four layers of a period, 8
    of 16 experts held, top-2) through the normal path, float32 so that
    the CPU's numbers are sharp."""
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal',
                           f'{CELL}.json')) as fh:
        tiny = json.load(fh)
    tiny['config']['executor']['model']['dtype'] = 'float32'
    tiny['config']['executor']['mesh'] = {'dp': 1}
    tiny['cell']['limits'] = {'loss_gap': 1e-3, 'grad_gap': 0.05,
                              'delta_gap': 0.05}
    line = rehearse.rehearse(CELL, seed=2_800_000_011, seconds=0.5,
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
    assert line['metrics']['cpu_rehearsal.moe_local_assign_pct'][
        'value'] == pytest.approx(50, abs=15)
    assert line['metrics']['cpu_rehearsal.moe_load_max_over_mean'][
        'value'] >= 1
    assert 'cpu_rehearsal.host_input_ms.tokens' in names
    assert not any('roofline' in n for n in names)
