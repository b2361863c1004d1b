"""Collective-communication attribution: how much of the step is the
network, not the math?

A sharded step's MFU tells you the step is slow; nothing recorded says
whether the time went to the MXU or to the gradient all-reduce. This
module closes that gap for any jit-compiled sharded step:

- ``collective_stats(compiled)`` walks the compiled executable's HLO
  text for collective ops (``all-reduce`` / ``all-gather`` /
  ``reduce-scatter`` / ``collective-permute``, plus their async
  ``-start`` halves) and tallies per-op counts and bytes. The shapes in
  a post-SPMD-partitioning module are PER-PARTICIPANT buffer shapes, so
  the byte totals are what each device actually puts on the
  interconnect per step — static truth, zero runtime cost, computed
  once per compiled stage from the same AOT lowering the FLOPs probe
  already pays for (train/executor.py).
- ``measure_collective_ms(mesh, bytes)`` MEASURES the wire: it times a
  jitted all-reduce moving the same per-device byte volume over the
  same mesh (best-of-k, value-fetch barrier). Dividing that by the
  observed step time gives the ``comm.fraction`` series the train loop
  emits per epoch — a measured number, not a bytes/bandwidth guess
  with an assumed link speed.

The train loop persists, per stage: ``comm.<op>_bytes`` and
``comm.<op>_count`` gauges plus ``comm.bytes_per_step`` /
``comm.op_count`` totals, and per epoch the measured ``comm.fraction``
series; ``GET /metrics`` re-exports the latest values per running task
(``mlcomp_comm_bytes`` / ``mlcomp_comm_fraction``), the dashboard
renders a communication card beside the phase breakdown, and bench.py
publishes ``comm_fraction`` for the sharded fsdp LM leg.
"""

import re

#: collective op kinds tallied from the HLO (async ``-start`` halves
#: count as the op; ``-done`` halves are skipped so an async pair is
#: one event, not two)
COLLECTIVE_OPS = ('all-reduce', 'all-gather', 'reduce-scatter',
                  'collective-permute')

#: HLO primitive byte widths (shape prefixes as xla prints them)
_DTYPE_BYTES = {
    'pred': 1, 's4': 1, 'u4': 1, 's8': 1, 'u8': 1, 'f8e4m3fn': 1,
    'f8e5m2': 1, 'f8e4m3b11fnuz': 1, 'f8e4m3fnuz': 1, 'f8e5m2fnuz': 1,
    's16': 2, 'u16': 2, 'f16': 2, 'bf16': 2,
    's32': 4, 'u32': 4, 'f32': 4,
    's64': 8, 'u64': 8, 'f64': 8, 'c64': 8, 'c128': 16,
}

#: one typed array shape: ``f32[64,128]`` (layout braces optional)
_SHAPE_RE = re.compile(r'([a-z]+[0-9a-z]*)\[([0-9,]*)\]')
#: an HLO instruction line: ``%name = <shape(s)> <opcode>(...)``
_INSTR_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(')
#: the wrapped computation of a generic async wrapper op
_CALLS_RE = re.compile(r'calls=%?([\w.\-]+)')


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of every typed array in a shape string — covers
    both ``f32[8,128]{1,0}`` and tuple shapes
    ``(f32[8,128]{1,0}, f32[8]{0})`` (variadic all-reduce)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue            # token[] / opaque[] move no payload
        n = 1
        if dims:
            for d in dims.split(','):
                n *= int(d)
        total += n * width
    return total


def _top_level_components(shape_text: str):
    """Split a top-level HLO tuple shape ``(a, (b, c), d)`` into its
    component texts; a non-tuple shape is its own single component."""
    text = shape_text.strip()
    if not text.startswith('('):
        return [text]
    inner = text[1:text.rfind(')')] if ')' in text else text[1:]
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(inner):
        if ch in '([{':
            depth += 1          # dims [64,64] and layouts {1,0} nest
        elif ch in ')]}':
            depth -= 1
        elif ch == ',' and depth == 0:
            parts.append(inner[start:i])
            start = i + 1
    parts.append(inner[start:])
    return [p for p in (p.strip() for p in parts) if p]


def _async_bytes(shape_text: str) -> int:
    """Payload bytes of an async collective's ``-start`` result. The
    start op's shape bundles the operand alias AND the destination
    buffer (``(f32[64,64], f32[128,64])`` for an all-gather-start,
    plus context scalars on some backends) — summing every component
    would double-count the wire. The LARGEST component is the
    destination (>= the operand for gathers, == it for reduce/permute,
    >> the context scalars), so that is the op's bytes."""
    return max((_shape_bytes(c) for c in
                _top_level_components(shape_text)), default=0)


def collective_stats(compiled_or_text) -> dict:
    """Static collective tally of one compiled executable:
    ``{'ops': {op: {'count', 'bytes'}}, 'total_bytes', 'total_count'}``.

    Accepts a jax ``Compiled`` object (``.as_text()``) or raw HLO text.
    Bytes are the op's RESULT buffer bytes per participant per step —
    the post-partitioning module carries per-device shapes. Returns the
    zero tally (not an error) for an unsharded module: "this step moves
    nothing" is a valid, publishable answer.
    """
    text = compiled_or_text
    if not isinstance(text, str):
        text = compiled_or_text.as_text()
    ops = {}
    for line in text.split('\n'):
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        shape_text, opcode = m.group(1), m.group(2)
        if opcode.endswith('-done') or opcode == 'async-update':
            continue            # the -start half already counted
        if opcode == 'async-start':
            # generic async wrapper: the collective is the WRAPPED
            # computation (``calls=%wrapped_all_gather``); its bundled
            # shape is ((operands), outputs, context) — largest
            # component is the payload
            called = _CALLS_RE.search(line)
            name = (called.group(1) if called else '').replace(
                '_', '-')
            base = next((op for op in COLLECTIVE_OPS if op in name),
                        None)
            if base is None:
                continue
            entry = ops.setdefault(base, {'count': 0, 'bytes': 0})
            entry['count'] += 1
            entry['bytes'] += _async_bytes(shape_text)
            continue
        if opcode.endswith('-start'):
            base = opcode[:-len('-start')]
            if base not in COLLECTIVE_OPS:
                continue
            # async start: shape bundles operand alias + destination —
            # count the destination only, not the sum
            entry = ops.setdefault(base, {'count': 0, 'bytes': 0})
            entry['count'] += 1
            entry['bytes'] += _async_bytes(shape_text)
            continue
        if opcode not in COLLECTIVE_OPS:
            continue
        entry = ops.setdefault(opcode, {'count': 0, 'bytes': 0})
        entry['count'] += 1
        # sync op: a tuple shape here is a VARIADIC collective (one
        # reduced buffer per operand) — summing is correct
        entry['bytes'] += _shape_bytes(shape_text)
    return {
        'ops': ops,
        'total_bytes': sum(e['bytes'] for e in ops.values()),
        'total_count': sum(e['count'] for e in ops.values()),
    }


def measure_collective_ms(mesh, bytes_per_device: int,
                          trials: int = 5) -> float:
    """Measured wall-clock of ONE all-reduce moving
    ``bytes_per_device`` over ``mesh`` (ms, best of ``trials``) — the
    wire-time basis for ``comm.fraction``. Each trial fetches a result
    value as the barrier. Returns None on a single-device mesh (no
    wire to measure) or when the probe cannot run; costs one small
    compile, so call once per stage, never per step."""
    try:
        import jax
        import numpy as np
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec

        n_dev = len(mesh.devices.flat)
        if n_dev <= 1 or not bytes_per_device:
            return None
        axes = tuple(mesh.axis_names)
        chunk = max(1, int(bytes_per_device) // 4)   # f32 lanes
        spec = PartitionSpec(axes)
        fn = jax.jit(shard_map(
            lambda x: jax.lax.psum(x, axes), mesh=mesh,
            in_specs=spec, out_specs=PartitionSpec()))
        x = jax.device_put(
            np.zeros(chunk * n_dev, np.float32),
            NamedSharding(mesh, spec))
        out = fn(x)
        float(out[0])                                # warm + barrier
        best = float('inf')
        import time
        for _ in range(max(1, int(trials))):
            t0 = time.perf_counter()
            out = fn(x)
            float(out[0])
            best = min(best, time.perf_counter() - t0)
        return best * 1e3
    except Exception:
        return None


def persist_collective_stats(session, task_id: int, stats: dict,
                             comm_ms=None, component: str = 'train'):
    """One metric row per op (``comm.<op>_bytes`` / ``comm.<op>_count``,
    dashes normalized to underscores) plus the totals
    (``comm.bytes_per_step`` / ``comm.op_count``) and, when measured,
    the probe time ``comm.probe_ms`` — the static half of the comm
    story, written once per compiled stage. Tags carry the full tally
    so the postmortem bundle picks it up as one row."""
    import json as _json

    from mlcomp_tpu.db.providers.telemetry import MetricProvider
    from mlcomp_tpu.utils.misc import now
    ts = now()
    rows = []
    for op, entry in sorted(stats.get('ops', {}).items()):
        key = op.replace('-', '_')
        rows.append((task_id, f'comm.{key}_bytes', 'gauge', None,
                     float(entry['bytes']), ts, component, None))
        rows.append((task_id, f'comm.{key}_count', 'gauge', None,
                     float(entry['count']), ts, component, None))
    rows.append((task_id, 'comm.bytes_per_step', 'gauge', None,
                 float(stats.get('total_bytes', 0)), ts, component,
                 _json.dumps(stats.get('ops', {}))))
    rows.append((task_id, 'comm.op_count', 'gauge', None,
                 float(stats.get('total_count', 0)), ts, component,
                 None))
    if comm_ms is not None:
        rows.append((task_id, 'comm.probe_ms', 'gauge', None,
                     float(comm_ms), ts, component, None))
    MetricProvider(session).add_many(rows)
    return len(rows)


__all__ = ['COLLECTIVE_OPS', 'collective_stats',
           'measure_collective_ms', 'persist_collective_stats']
