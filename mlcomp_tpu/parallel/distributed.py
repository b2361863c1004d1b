"""Multi-host bootstrap: consume the supervisor's ``distr_info``.

The reference exports the torch.distributed env contract
(``MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK``) and lets NCCL allreduce
(reference worker/executors/catalyst/catalyst.py:195-207). The TPU-native
equivalent is ``jax.distributed.initialize``: every fanned-out service
task calls it with the coordinator address + process indices the
supervisor manufactured (server/supervisor.py), after which
``jax.devices()`` is the GLOBAL device list, meshes span hosts, and XLA
collectives ride ICI within a host / DCN across hosts.

Must run BEFORE the first jax backend use in the process (importing jax
is fine; querying devices is not).
"""

from typing import Any, Optional

_state = {'initialized': False}


#: substrings of coordination-service errors that mean "my peers never
#: arrived / the coordinator is gone", not "my own config is broken" —
#: the gang-peer-lost carve-out of the join failure space
_PEER_LOST_MARKERS = ('deadline', 'timed out', 'timeout', 'unavailable',
                      'connection refused', 'connect failed',
                      'failed to connect', 'barrier')


def _probe_coordinator(address: str, timeout_s: float, rank: int,
                       count: int, gang: dict) -> float:
    """Bounded TCP probe of the coordinator BEFORE touching
    ``jax.distributed.initialize``: the xla coordination client
    ``LOG(FATAL)``s (process abort, nothing catchable in Python) when
    its registration deadline expires, so the common gang failure —
    the coordinator HOST died at dispatch — must be diagnosed out
    here, where it can raise ``GangPeerLost`` and flow through the
    normal failure-classification path instead of a silent SIGABRT.
    Returns the seconds SPENT probing — the caller deducts them from
    the registration deadline so probe + register together honour ONE
    join budget, not two."""
    import socket
    import time as _time
    from mlcomp_tpu.recovery import GangPeerLost
    host, _, port = address.rpartition(':')
    start = _time.monotonic()
    deadline = start + float(timeout_s)
    last_err = 'unreachable'
    while _time.monotonic() < deadline:
        try:
            with socket.create_connection((host, int(port)), timeout=2):
                return _time.monotonic() - start
        except OSError as e:
            last_err = str(e) or type(e).__name__
            _time.sleep(min(1.0, max(
                0.05, deadline - _time.monotonic())))
    raise GangPeerLost(
        f'rank {rank}/{count} of gang {gang.get("id") or "?"} '
        f'(generation {gang.get("generation") or "?"}) gave up joining '
        f'coordinator {address} after {timeout_s:.0f}s: {last_err}')


def initialize_from_distr_info(distr_info: Optional[dict]) -> bool:
    """Idempotently initialize the jax distributed runtime from the
    supervisor's distr_info {coordinator_address, process_index,
    process_count}. Returns True when running multi-process.

    The join is BOUNDED: ``distr_info['join_timeout_s']`` (stamped by
    the supervisor from ``RecoveryConfig.join_timeout_s``) caps how
    long this rank waits for the gang to assemble. Without it a gang
    whose sibling died at dispatch strands every survivor at the
    coordinator forever — with it the stranded rank fails fast as
    ``GangPeerLost`` (taxonomy ``gang-peer-lost``) where the failure
    is catchable (dead-coordinator TCP probe, a join that raises)
    and as a bounded process abort where xla's coordination client
    ``LOG(FATAL)``s (a missing middle peer) — either way the rank
    dies within the bound, the gang verdict aggregates, and the whole
    gang requeues as one unit."""
    if not distr_info:
        return False
    count = int(distr_info.get('process_count') or 1)
    if count <= 1:
        return False
    if _state['initialized']:
        return True
    import jax
    timeout = distr_info.get('join_timeout_s')
    rank = int(distr_info.get('process_index') or 0)
    gang = distr_info.get('gang') or {}
    address = distr_info['coordinator_address']
    remaining = float(timeout) if timeout else None
    if timeout and rank != 0:
        # rank 0 IS the coordinator — probing itself would deadlock.
        # The probe spends part of the ONE join budget; registration
        # gets what is left, so the rank's total wait stays bounded by
        # join_timeout_s rather than paying it twice in sequence.
        spent = _probe_coordinator(address, float(timeout), rank,
                                   count, gang)
        remaining = max(1.0, float(timeout) - spent)
    kwargs = {
        'coordinator_address': address,
        'num_processes': count,
        'process_id': rank,
    }
    if remaining:
        kwargs['initialization_timeout'] = max(1, int(remaining))
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as e:
        from mlcomp_tpu.recovery import GangPeerLost
        text = f'{type(e).__name__}: {e}'.lower()
        if any(marker in text for marker in _PEER_LOST_MARKERS):
            raise GangPeerLost(
                f'rank {rank}/{count} of gang '
                f'{gang.get("id") or "?"} (generation '
                f'{gang.get("generation") or "?"}) gave up joining '
                f'coordinator {address}: '
                f'{type(e).__name__}: {e}') from e
        raise
    _state['initialized'] = True
    return True


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def is_main_process() -> bool:
    """Rank-0 check: DB reporting, checkpoint writes, and model-registry
    updates happen only here (reference suppresses checkpointing and
    reporting on rank>0, catalyst.py:298-311)."""
    return process_index() == 0


def host_replicated_copy(tree: Any, mesh=None) -> Any:
    """Pull a (possibly cross-process sharded) pytree fully to host.

    Single-process: plain ``device_get``. Multi-process: arrays sharded
    over other hosts are not addressable, so reshard to fully-replicated
    first (an all-gather every process participates in), then
    ``device_get``. Used by the checkpoint path before rank-0 writes.
    """
    import jax
    if jax.process_count() == 1:
        return jax.device_get(tree)
    leaves = [x for x in jax.tree.leaves(tree)
              if isinstance(x, jax.Array)]
    if all(x.is_fully_addressable for x in leaves):
        return jax.device_get(tree)
    if mesh is None:
        raise ValueError(
            'host_replicated_copy needs the mesh to gather '
            'cross-process shards')
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())

    def gather(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return jax.jit(lambda a: a, out_shardings=rep)(x)
        return x
    return jax.device_get(jax.tree.map(gather, tree))


__all__ = ['initialize_from_distr_info', 'process_index', 'process_count',
           'is_main_process', 'host_replicated_copy']
