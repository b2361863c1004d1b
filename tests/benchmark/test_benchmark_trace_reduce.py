"""trace_reduce.py on a small recorded trace: idle inside a window the
host bounds is seen, which [first op, last op] would hide."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope='module')
def trace():
    with open(os.path.join(HERE, 'data', 'small_trace.json')) as fh:
        return json.load(fh)


def test_marks_bound_the_window(trace):
    assert trace_reduce.marks(trace) == (0, 3_200_000)


def test_idle_inside_a_host_bounded_window_is_seen(trace):
    out = trace_reduce.reduce(trace)
    assert out['window_s'] == pytest.approx(3.2e-3)
    assert out['busy_s'] == pytest.approx(1.2e-3)
    # [first op, last op] = 2.2 ms would read 45% idle; the host's
    # window reads 62.5%: the half millisecond at each end counts
    assert 1 - out['busy_s'] / out['window_s'] == pytest.approx(0.625)
    assert out["device_ops"][0] == ["fusion", pytest.approx(0.9e-3)]
    assert out["op_table"]["custom-call"][:2] == [pytest.approx(0.3e-3), 3]
    assert out["modules"] == {"jit_step(1)": [pytest.approx(1.2e-3), 3.0]}


def test_gaps_are_named_by_what_the_host_did(trace):
    phases = [(0, 1_300_000, 'train_steps'),
              (1_300_000, 2_300_000, 'epoch_boundary'),
              (2_300_000, 3_200_000, 'train_steps')]
    out = trace_reduce.reduce(trace, phases=phases)
    gaps = dict((name.split(' ')[0], s) for name, s in out['idle_gaps'])
    assert gaps['epoch_boundary'] == pytest.approx(1.0e-3)
    assert gaps['train_steps'] == pytest.approx(1.0e-3)


def test_interval_algebra():
    total, merged = trace_reduce.union([(0, 2), (1, 3), (5, 6)])
    assert (total, merged) == (4, [[0, 3], [5, 6]])
    assert trace_reduce.gaps(merged, 0, 8) == [(3, 5), (6, 8)]
    assert trace_reduce.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert trace_reduce.op_base_name('%fusion.123') == 'fusion'


def test_a_trace_without_marks_is_refused(trace):
    bare = {'planes': trace['planes'][:1]}
    with pytest.raises(ValueError):
        trace_reduce.marks(bare)
