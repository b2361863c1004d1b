"""benchmark/flops.py and the families' sums against hand counts."""

import copy
import json
import os

import pytest

from benchmark import flops, trace_reduce
from benchmark.manifest import Manifest
from benchmark.reference import resnet, transformer_lm


def test_one_resnet_block_by_hand():
    # a 64->64 basic block on 32x32: two 3x3 convolutions, each
    # 32*32 outputs x 64 channels x (3*3*64) multiply-adds
    one = 2 * 32 * 32 * 64 * 3 * 3 * 64
    assert flops.conv2d(32, 32, 3, 3, 64, 64) == one == 75_497_472
    # the first block of a stage halves the size and adds a 1x1 projection
    assert flops.conv2d(16, 16, 1, 1, 64, 128) == 2 * 256 * 64 * 128


def test_resnet18_cifar_by_hand():
    model = {'num_classes': 10}
    data = {'image_size': 32, 'channels': 3}
    stem = 2 * 32 * 32 * 27 * 64
    stage1 = 4 * 75_497_472
    later = 0
    for size, c_in, c_out in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        later += 2 * size * size * 9 * c_in * c_out        # strided 3x3
        later += 3 * 2 * size * size * 9 * c_out * c_out   # three more
        later += 2 * size * size * c_in * c_out            # projection
    head = 2 * 512 * 10
    forward = stem + stage1 + later + head
    assert resnet.train_flops_per_sample(model, data) == 3.0 * forward
    assert 1.10e9 < forward < 1.12e9      # the familiar 0.56 GMAC


def test_causal_attention_by_hand():
    # T=4, one head of 2: 10 (query, key) pairs on or under the
    # diagonal; QK^T and PV are 2*2 operations a pair each
    assert flops.causal_attention(4, 1, 2) == 2 * (2 * 10 * 2)
    assert flops.causal_attention(4, 1, 2, backward=True) \
        == 2 * flops.causal_attention(4, 1, 2)
    assert flops.attention_bytes(4, 1, 2, 2) == 4 * 4 * 2 * 2


def test_one_decoder_layer_by_hand():
    model = {'d_model': 2048, 'n_layers': 1, 'n_heads': 16, 'd_ff': 8192,
             'vocab_size': 50304}
    data = {'seq_len': 2048}
    t, d, ff, v = 2048, 2048, 8192, 50304
    dense = 2 * t * (4 * d * d + 3 * d * ff) + 2 * t * d * v
    pairs = t * (t + 1) / 2
    attention = 3 * (2 * 2 * pairs * 128 * 16)
    got = transformer_lm.train_flops_per_sample(model, data)
    assert got == pytest.approx(3 * dense + attention, rel=1e-12)
    # 8 layers: 4.04 GFLOP a token, as PERF.md reckons
    model['n_layers'] = 8
    per_token = transformer_lm.train_flops_per_sample(model, data) / t
    assert per_token == pytest.approx(4.04e9, rel=0.01)


# ----------------------------------------- flash_attn_roofline's shapes
class TracedOlmo:
    """What ``flash_attn_roofline`` needs of a traced ``olmo-1b.steady``
    run, with the recorded ``small_trace.json`` in the place of an
    epoch's trace: its three 0.1 ms ``custom-call`` events stand for the
    kernels, so the share has no meaning here, only its digits."""
    seed, steps_per_epoch = 7, 32

    def __init__(self, kernels=None):
        here = os.path.dirname(os.path.abspath(__file__))
        manifest = Manifest(os.path.dirname(os.path.dirname(here)))
        with open(os.path.join(here, 'data', 'small_trace.json')) as fh:
            self._trace = json.load(fh)
        self.cell = manifest.cell('olmo-1b.steady')
        self.config = copy.deepcopy(manifest.config('olmo-1b'))
        assert self.config['kernels'] == {'flash_attn': ['attn']}
        self.config['kernels'] = dict(kernels or {},
                                      flash_attn=['custom-call'])
        self.peaks = manifest.peaks('TPU v5 lite')
        self.read = manifest.reader('flash_attn_roofline')
        self.notes = []

    def reduced(self):
        return trace_reduce.reduce(self._trace)

    def note(self, text):
        self.notes.append(text)


def test_flash_attn_roofline_derives_the_shape_as_it_always_did():
    # the value the reader gave on this fixture before it knew of
    # ``q_heads``, ``head_dim`` and ``attention_layers``: digit for digit
    run = TracedOlmo()
    assert run.read(run, 'flash_attn_roofline') == 91205.37467971574
    assert run.notes == [
        'flash_attn_roofline: kernels 0.0003 s in the traced epoch; least '
        '0.2736 s by FLOPs, 0.1285 s by bytes -> bound by compute']


@pytest.mark.parametrize('kernels, times', [
    # olmo-1b's own shape, stated: 16 heads of 128 in all 8 layers
    ({'q_heads': 16, 'head_dim': 128, 'attention_layers': 8}, 1.0),
    # attention in one layer of four; heads twice as wide as
    # d_model / n_heads, and half as many; each key alone
    ({'attention_layers': 2}, 0.25),
    ({'q_heads': 8, 'head_dim': 256}, 1.0),
    ({'head_dim': 256}, 2.0),
    ({'q_heads': 4}, 0.25)])
def test_flash_attn_roofline_takes_the_shape_the_configuration_gives(
        kernels, times):
    run = TracedOlmo(kernels)
    assert run.read(run, 'flash_attn_roofline') == pytest.approx(
        91205.37467971574 * times, rel=1e-12)
