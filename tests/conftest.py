"""Test bootstrap: CPU-emulated 8-device mesh + sandboxed framework root.

Must set env vars BEFORE jax or mlcomp_tpu are imported anywhere.
"""

import os

os.environ['JAX_PLATFORMS'] = 'cpu'  # tests never touch an accelerator
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()
# XLA:CPU runs every emulated device's share of a step on ONE thread
# pool of max(NPROC or the core count, device count) threads, and a
# thread waits inside an all-reduce until all 8 participants arrive.
# With 8 cores and 8 devices the pool has no thread to spare: steps
# dispatched back to back strand a participant in the queue, the
# rendezvous aborts the process after 40 s ("Fatal Python error:
# Aborted"), and under `--dist loadfile` the dead xdist worker hangs the
# whole run to its time limit. A larger pool ends that; children inherit.
os.environ.setdefault('NPROC', '32')
os.environ.setdefault('MLCOMP_TPU_TEST', '1')

import pytest  # noqa: E402


#: accepted benchmark tests that pin a count of cells or a last place
#: (node id's end -> why it is expected to fail): no PR but a
#: ``benchmark`` PR may edit their files (``tests/benchmark/accepted.py``)
PINNED = {
    'test_benchmark_qwen3_next.py::test_manifest_check_exits_0':
        'pins "3 cells"; BENCHMARK.json holds more since PR 33 and the '
        'file is an accepted one',
    'test_benchmark_lfm2_moe.py::'
    'test_manifest_check_exits_0_with_four_cells':
        'pins "4 cells" and the last places of the lfm2 entries; '
        'BENCHMARK.json holds a fifth cell since PR 35 and the file is '
        'an accepted one',
}
#: the accepted per-cell lists of the three sparse cells' per-layer
#: metrics, which the ``block_device_ms.<block>`` entries lengthen;
#: ``tests/benchmark/test_benchmark_block_device_ms.py`` makes the same
#: assertion with the blocks added
PINNED.update({
    f'test_benchmark_deepseek_v3.py::'
    f'test_what_each_sparse_cell_reports[{cell}]':
        'lists what the cell reported before the block_device_ms entries; '
        'the file is an accepted one'
    for cell in ('kanana-2-30b-a3b.steady', 'lfm2-8b-a1b.steady',
                 'qwen3-next-80b-a3b.steady')})
#: the accepted lists of the ``block_device_ms.<block>`` entries, which
#: the looped cell lengthens; ``tests/benchmark/test_benchmark_ouro.py``
#: makes the same assertions with that cell appended
PINNED['test_benchmark_block_device_ms.py::'
       'test_the_eight_entries_and_their_cells'] = (
    'asserts each block entry lists exactly the four token cells it was '
    'accepted with; BENCHMARK.json appends ouro-2.6b.steady to five of '
    'them and the file is an accepted one')


def pytest_collection_modifyitems(items):
    """Two assertions of ACCEPTED benchmark tests count the cells:
    ``tests/benchmark/test_benchmark_qwen3_next.py::
    test_manifest_check_exits_0`` (PR 28) wants ``manifest.py --check``
    to print "3 cells, nothing lacking", and
    ``tests/benchmark/test_benchmark_lfm2_moe.py::
    test_manifest_check_exits_0_with_four_cells`` (PR 33) "4 cells" with
    the lfm2 entries last in every list. PR 33 and PR 35 each add a cell
    by files and entries, and no PR but a ``benchmark`` PR may edit those
    files, so both tests are expected to fail until one makes them count
    what ``BENCHMARK.json`` holds — and, the marks being strict, that PR
    has to take them away.
    ``tests/benchmark/test_benchmark_deepseek_v3.py`` makes ALL of their
    other assertions, the qwen cell's and the lfm2 cell's, with the
    count read from ``BENCHMARK.json`` and "after the accepted entries"
    in place of "last" (``PERF.md`` section 7 p)."""
    for item in items:
        for tail, reason in PINNED.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=reason,
                                                  strict=True))


@pytest.fixture(autouse=True)
def _confine_sigterm_handler():
    """In-process worker tests run ExecuteBuilder inside the pytest
    process, which installs the worker's SIGTERM -> SystemExit(143)
    crash-flush handler (worker/tasks._install_crash_flush) — and the
    handler outlives the installing test. A CI time-budget SIGTERM
    landing after that point then raises SystemExit inside whichever
    unrelated test happens to be running, reported as a spurious
    failure. Restore the handler after each test so a budget cut
    kills the run cleanly instead."""
    import signal as _signal
    before = _signal.getsignal(_signal.SIGTERM)
    yield
    if _signal.getsignal(_signal.SIGTERM) is not before:
        try:
            _signal.signal(_signal.SIGTERM, before)
        except (ValueError, OSError):
            pass


@pytest.fixture()
def session():
    """Fresh migrated DB per test (parity: reference utils/tests.py:12-21)."""
    from mlcomp_tpu.utils.tests import fresh_session
    yield fresh_session()


@pytest.fixture(params=['sqlite', 'postgres'])
def backend_session(request):
    """Both control-plane backends behind one fixture: sqlite always
    (fresh file per test), Postgres only where ``MLCOMP_TEST_PG_DSN``
    points at a disposable database (the CI service container) — and a
    clean skip everywhere else, so tier-1 stays green on sqlite-only
    boxes. The Postgres schema is dropped and re-migrated per test for
    the same isolation the sqlite fixture gets by deleting the file."""
    if request.param == 'sqlite':
        from mlcomp_tpu.utils.tests import fresh_session
        yield fresh_session()
        return
    import os as _os
    dsn = _os.environ.get('MLCOMP_TEST_PG_DSN')
    if not dsn:
        pytest.skip('MLCOMP_TEST_PG_DSN not set — Postgres parity '
                    'leg runs only against a disposable database')
    try:
        import psycopg  # noqa: F401
    except ImportError:
        pytest.skip('psycopg not installed')
    from mlcomp_tpu.db.core import Session
    from mlcomp_tpu.db.migration import migrate
    Session.cleanup('pg_test')
    s = Session.create_session(key='pg_test', connection_string=dsn)
    s.execute('DROP SCHEMA public CASCADE')
    s.execute('CREATE SCHEMA public')
    migrate(s)
    yield s
    Session.cleanup('pg_test')
