"""Device stats: TPU/HBM occupancy and compiled-step cost, from inside
the training process.

bench.py computes MFU from the outside by re-lowering the step; this
module makes the same numbers available to the loop that is actually
training, so ``mfu`` and ``hbm_used`` land in the metric table next to
the loss series they explain.

Never initializes a jax client: a chip belongs to one process at a
time, so a client made here by a process that does not train would
take it from the one that does (see worker/__main__.py:_tpu_usage).
Everything here is a no-op returning
empty data unless jax is already imported and initialized by the
caller's own training code.
"""

import sys


def device_memory_stats() -> list:
    """Per-local-device HBM stats via ``device.memory_stats()``:
    ``[{'id', 'platform', 'kind', 'bytes_in_use', 'bytes_limit',
    'peak_bytes_in_use', 'reports_memory'}]``. Empty when jax is not
    live. ``peak_bytes_in_use`` is the allocator's high-water mark
    when the backend reports one (TPU does; 0 otherwise) — the number
    an OOM postmortem wants, since the crash-time ``bytes_in_use``
    reads AFTER the failed allocation was rolled back.
    ``reports_memory`` is False on platforms without memory stats
    (CPU), so consumers can skip the device instead of rendering an
    empty 0/0 HBM row."""
    if 'jax' not in sys.modules:
        return []
    try:
        import jax
        out = []
        for d in jax.local_devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:
                stats = {}
            out.append({
                'id': d.id,
                'platform': d.platform,
                'kind': getattr(d, 'device_kind', str(d)),
                'bytes_in_use': int(stats.get('bytes_in_use', 0)),
                'bytes_limit': int(stats.get('bytes_limit', 0)),
                'peak_bytes_in_use':
                    int(stats.get('peak_bytes_in_use', 0)),
                'reports_memory': bool(stats.get('bytes_limit')),
            })
        return out
    except Exception:
        return []


def compiled_cost(jitted_fn, *args) -> dict:
    """FLOPs + bytes accessed of one compiled call from XLA's own cost
    analysis. With a persistent compilation cache this re-lowering is
    cheap; without one it costs a compile — call once per stage, not
    per step. ``{}`` when the analysis is unavailable (e.g. the cost
    lives inside a Pallas custom call XLA can't see)."""
    try:
        cost = jitted_fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return {
            'flops': float(cost.get('flops', 0.0)) or None,
            'bytes_accessed': float(cost.get('bytes accessed', 0.0))
            or None,
        }
    except Exception:
        return {}


def mfu(flops_per_step: float, steps_per_sec: float, n_devices: int,
        peak_tflops: float) -> float:
    """Model FLOPs utilization against the chip's peak."""
    return (flops_per_step * steps_per_sec /
            (peak_tflops * 1e12 * max(1, n_devices)))


def record_device_stats(recorder, step: int = None):
    """Gauge rows per local device: ``device<i>.hbm_used`` /
    ``device<i>.hbm_limit`` (+ ``hbm_peak`` when the backend reports
    a high-water mark). Cheap no-op off-TPU: devices that report no
    memory stats (``reports_memory`` False — CPU) emit nothing, so a
    CPU run never renders empty 0/0 HBM rows in the dashboard."""
    for d in device_memory_stats():
        if not d['reports_memory']:
            continue
        recorder.gauge(f'device{d["id"]}.hbm_used',
                       d['bytes_in_use'], step=step)
        recorder.gauge(f'device{d["id"]}.hbm_limit',
                       d['bytes_limit'], step=step)
        if d['peak_bytes_in_use']:
            recorder.gauge(f'device{d["id"]}.hbm_peak',
                           d['peak_bytes_in_use'], step=step)


__all__ = ['device_memory_stats', 'compiled_cost', 'mfu',
           'record_device_stats']
