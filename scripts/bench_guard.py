"""Bench regression guard: hold the freshest BENCH_r*.json to named
floor thresholds.

The bench trajectory is the repo's perf contract — every round's
headline legs (docs/performance.md) must hold while new paths land.
This guard encodes the floors (seeded from round 5's published numbers
minus noise margin) and exits nonzero when a published leg regresses
below its floor, so CI catches a perf regression the same way it
catches a failed test.

A leg ABSENT from the JSON is a warning, not a failure, by default:
the bench sheds optional legs when its budget runs out (bench.py
BENCH_BUDGET_S) and a shed leg is not a regression. ``--strict``
promotes missing tracked legs to failures (for release gating).

Usage:
    python scripts/bench_guard.py              # freshest BENCH_r*.json
    python scripts/bench_guard.py path.json    # explicit file
    python scripts/bench_guard.py --list       # print the floor table
"""
import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: leg -> (direction, floor, description). Directions: 'min' = value
#: must be >= floor, 'max' = value must be <= floor.
FLOORS = {
    # headline legs, seeded from the r05 chip record (cifar 0.5045, lm
    # 48833, serving 1.486, dag 2.42; the BENCH_r05.json file itself
    # was removed in PR 21) with room for run-to-run noise
    'mfu': ('min', 0.48, 'CIFAR bf16 headline MFU'),
    'lm_tokens_per_sec': ('min', 46000.0,
                          'flagship LM tokens/sec (bf16 flash)'),
    'serving_int8_speedup': ('min', 1.35,
                             'int8 serving-stack speedup vs bf16'),
    # tightened 6.0 -> 2.5 in round 9 (ISSUE 13 acceptance bar): the
    # event-driven control plane must hold the r05 overhead (2.42%)
    'dag_grid_sched_overhead_pct': ('max', 2.5,
                                    'grid-DAG scheduling overhead %'),
    'dag_grid_dispatch_latency_s': ('max', 0.053,
                                    'grid-DAG enqueue->claim latency '
                                    '(r05 published 0.053; the halved '
                                    'worker poll must hold it)'),
    # round-6 legs (ISSUE 8 acceptance bars)
    'cifar_fused_norm_mfu': ('min', 0.55,
                             'CIFAR fused-norm headline MFU'),
    'cifar_fused_norm_byte_reduction_pct': (
        'min', 20.0, 'fused-norm XLA-billed byte reduction vs BN %'),
    'lm_scan_compile_reduction_pct': (
        'min', 40.0, 'scan-over-layers backend compile-time cut %'),
    'lm_scan_vs_loop_tokens': (
        'min', 0.90, 'scan tokens/sec parity vs the layer loop '
                     '(4-step probe; run-to-run noise is ±5-7%)'),
    'lm_wide_int8_vs_bf16': (
        'min', 1.15, 'int8 training speedup at the wide-GEMM shape'),
    # round-7 legs (ISSUE 9: serving-fleet tier). The fleet leg is
    # jax-free (stub replicas + routing gateway on loopback), so its
    # floors gate the ROUTING tier: sustained throughput with pooled
    # connections, recovery from a replica kill absorbed by breaker +
    # hedged retry (acceptance bar: p99 back under SLO within 30 s),
    # and SLO shedding actually engaging under overload.
    'fleet_sustained_qps': ('min', 100.0,
                            'gateway sustained QPS, 3 stub replicas'),
    'fleet_recovery_s': ('max', 30.0,
                         'replica-kill to sub-SLO recovery time (s)'),
    'fleet_failed_requests': ('max', 0.0,
                              'non-429 client failures during the '
                              'replica kill'),
    'fleet_shed_rate_pct': ('min', 1.0,
                            'shed share under deliberate overload '
                            '(SLO admission control must engage)'),
    # round-9 legs (ISSUE 13: high-throughput control plane). The
    # jax-free load harness (scripts/load_smoke.py via bench.py's
    # bench_dispatch leg): 2000 queued tasks over 128 simulated worker
    # slots. dispatch_p99_ms is the event-driven same-host
    # submit->claimed p99 — the acceptance bar says it must beat the
    # old ~1.2 s tick+poll floor by holding under 250 ms; the
    # throughput floor is conservative (measured ~6800/s on the dev
    # box; CI runners are slower and share cores).
    'dispatch_p99_ms': ('max', 250.0,
                        'event-driven submit->claimed p99 (load '
                        'harness, same-host)'),
    'control_plane_tasks_per_s': ('min', 500.0,
                                  'queue claim+complete throughput '
                                  'over 128 simulated slots'),
    # round-10 leg (ISSUE 14: supervisor HA). The load harness runs
    # the failover leg with a 1 s lease window; the acceptance bar is
    # promotion within <= 2 windows of leader silence, with headroom
    # for a loaded CI runner's scheduler jitter on top.
    'supervisor_failover_s': ('max', 3.0,
                              'leader-silence to standby-promotion '
                              'latency (1 s lease window; <= 2 '
                              'windows + CI jitter)'),
    # round-11 legs (ISSUE 15: ASHA sweep scheduling). The jax-free
    # sweep_probe grid run exhaustive vs sweep-scheduled on the same
    # worker pool (bench.py bench_grid_asha). The acceptance bars:
    # the sweep reaches the same best configuration (deterministic
    # probe curve — the gap must be numerical noise only) in well
    # under half the exhaustive wallclock, with every prune recorded
    # as an auditable sweep_decision row and zero pruned cells ever
    # auto-retried (audit_ok folds both).
    'dag_grid_asha_speedup': ('min', 1.8,
                              'sweep-scheduled vs exhaustive grid '
                              'wallclock speedup (same pool)'),
    'dag_grid_asha_best_gap': ('max', 1e-6,
                               'best-score gap sweep vs exhaustive '
                               '(must agree on the winner)'),
    'dag_grid_asha_audit_ok': ('min', 1.0,
                               'every prune audited exactly once, no '
                               'pruned cell retried (1 = holds)'),
    # round-15 legs (ISSUE 20: multi-tenant scheduling). The jax-free
    # preempt leg (bench.py bench_preempt) seeds a full 8-core host of
    # preemptible cells, then times a high-class arrival through
    # decision-row + checkpoint-kill + replacement dispatch across two
    # in-process supervisor ticks — milliseconds on a dev box; the
    # floor leaves room for a loaded CI runner. The steady-state
    # passes (drained preemption scan; priority + fair-share dispatch
    # ordering over a 200-deep queue) are per-tick control-loop costs
    # held to the same budget discipline as the economy passes.
    'preempt_to_dispatch_ms': ('max', 1000.0,
                               'full-host eviction + replacement '
                               'dispatch, two in-process ticks'),
    'preempt_drained_overhead_pct': ('max', 1.0,
                                     'drained preemption pass vs the '
                                     '1 s supervisor tick %'),
    'sched_order_overhead_pct': ('max', 5.0,
                                 'priority/fair-share dispatch '
                                 'ordering, 200-deep queue, vs the '
                                 '1 s tick %'),
    # round-8 leg (ISSUE 12: deep-step observability). The per-step
    # HBM timeline must stay effectively free — the sampler is one
    # allocator-stats read per reporting device (telemetry/memory.py),
    # measured in isolation against the compute step like every other
    # telemetry overhead number.
    'memory_sampler_overhead_pct': ('max', 1.0,
                                    'per-step HBM memory sampler '
                                    'overhead vs step time %'),
    # round-12 legs (ISSUE 18: cluster-economy observability). Both
    # passes run inside the supervisor control loop (bench.py
    # bench_economy), so their budget is the loop's own cadence: the
    # steady-state usage fold per 1 s tick interval, one full SLO
    # burn-rate evaluation per 10 s evaluation period. <1% = the
    # economy layer is effectively free on the control plane.
    'usage_fold_overhead_pct': ('max', 1.0,
                                'steady-state usage-ledger fold vs '
                                'the 1 s supervisor tick interval %'),
    'slo_eval_overhead_pct': ('max', 1.0,
                              'full SLO burn-rate evaluation vs its '
                              '10 s evaluation period %'),
    # round-14 legs (ISSUE 19: device-time attribution plane). The
    # sampled profiler's loop-thread cost — one integer comparison per
    # step plus a capture window amortized over the 1000-step cadence
    # — must stay under the same <1% telemetry budget. The cross-check
    # ratio (trace-measured collective ms per device line vs the wire
    # probe of the same compiled fsdp step) is a SANITY bound, not a
    # precision bar: the two instruments measure different things
    # (sampled window incl. hidden comm vs isolated microbenchmark)
    # and agree to well within an order of magnitude on a healthy
    # build — 10x means one of them is broken.
    'devtime_overhead_pct': ('max', 1.0,
                             'sampled device-time profiler loop-'
                             'thread cost vs step time %'),
    'devtime_comm_vs_probe_pct': ('max', 1000.0,
                                  'trace-measured collective ms vs '
                                  'the wire probe, % (sanity bound: '
                                  'order-of-magnitude agreement)'),
}


def freshest_bench(root: str = REPO):
    """Highest-numbered BENCH_r*.json (falls back to newest mtime for
    unnumbered files)."""
    paths = glob.glob(os.path.join(root, 'BENCH_r*.json'))
    if not paths:
        return None

    def key(p):
        m = re.search(r'BENCH_r(\d+)\.json$', p)
        return (int(m.group(1)) if m else -1, os.path.getmtime(p))
    return max(paths, key=key)


def load_legs(path: str) -> dict:
    """The leg dict from either wire format: the driver's wrapper
    ({"parsed": {...}}) or bench.py's own raw JSON line."""
    with open(path) as fh:
        data = json.load(fh)
    if isinstance(data, dict) and isinstance(data.get('parsed'), dict):
        return data['parsed']
    if isinstance(data, dict):
        return data
    raise ValueError(f'{path}: not a bench JSON object')


def check(legs: dict, strict: bool = False):
    """Returns (failures, warnings) — lists of human-readable lines."""
    failures, warnings = [], []
    for name, (direction, floor, desc) in FLOORS.items():
        value = legs.get(name)
        if value is None:
            line = (f'MISSING {name} ({desc}): leg absent from the '
                    f'bench JSON')
            (failures if strict else warnings).append(line)
            continue
        try:
            value = float(value)
        except (TypeError, ValueError):
            failures.append(
                f'BAD     {name} ({desc}): non-numeric {value!r}')
            continue
        ok = value >= floor if direction == 'min' else value <= floor
        cmp = '>=' if direction == 'min' else '<='
        if ok:
            warnings.append(
                f'ok      {name} = {value:g} ({cmp} {floor:g})')
        else:
            failures.append(
                f'FLOOR   {name} ({desc}): {value:g} violates '
                f'{cmp} {floor:g}')
    return failures, warnings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('path', nargs='?', default=None,
                    help='bench JSON (default: freshest BENCH_r*.json)')
    ap.add_argument('--strict', action='store_true',
                    help='missing tracked legs fail instead of warn')
    ap.add_argument('--list', action='store_true',
                    help='print the floor table and exit')
    args = ap.parse_args(argv)

    if args.list:
        for name, (direction, floor, desc) in FLOORS.items():
            cmp = '>=' if direction == 'min' else '<='
            print(f'{name:40s} {cmp} {floor:<10g} {desc}')
        return 0

    path = args.path or freshest_bench()
    if path is None:
        print('bench_guard: no BENCH_r*.json found — nothing to guard')
        return 0
    legs = load_legs(path)
    failures, warnings = check(legs, strict=args.strict)
    print(f'bench_guard: {os.path.basename(path)}')
    for line in warnings:
        print(f'  {line}')
    for line in failures:
        print(f'  {line}', file=sys.stderr)
    if failures:
        print(f'bench_guard: {len(failures)} floor violation(s)',
              file=sys.stderr)
        return 1
    print('bench_guard: all published legs hold their floors')
    return 0


if __name__ == '__main__':
    sys.exit(main())
