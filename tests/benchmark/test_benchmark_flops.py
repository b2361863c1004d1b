"""benchmark/flops.py and the families' sums against hand counts."""

import pytest

from benchmark import flops
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
