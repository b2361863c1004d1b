#!/usr/bin/env python3
"""Readings that a cell's ``limits`` are set from, on the chip, at the
cell's own size, many seeds in one process (set-up is paid once).

For each seed: the program's gaps (a whole run of the cell at a short
window — the numbers ``correct`` compares), then, with the plain
reference put in the program's place, the control (the reference in
the nearest precision below the one the configuration states:
``float8`` operands for a bfloat16 configuration) and each fault the
family can plant (``half_batch``), each read against the float32
reference by the same measure. ``bfloat16`` operands are read too, as a
witness of what the stated precision alone costs.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--controls 3] [--seconds 2]

One JSON line per seed on standard output; the benchmark's own runs
never call this.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def as_program(ref: dict) -> dict:
    return {'loss': ref['loss'], 'moment_norm': ref['grad_norm'],
            'delta_norm': ref['delta_norm']}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', required=True)
    parser.add_argument('--controls', type=int, default=3,
                        help='seeds (the first N) that also read the '
                             'control and the faults')
    parser.add_argument('--seconds', type=float, default=2.0)
    parser.add_argument('--rehearse', action='store_true',
                        help='tiny sizes on whatever jax finds (CPU)')
    args = parser.parse_args(argv)

    from benchmark import correct, run as harness
    from benchmark.manifest import Manifest
    out = harness.place_run(ROOT, os.path.join('calibrate', args.workload))
    manifest = Manifest(ROOT, pending=True)
    if args.rehearse:
        from benchmark.rehearse import TinyManifest
        manifest = TinyManifest(manifest, args.workload)

    kept = []
    original = harness.result_line

    def keep(run):
        kept.append(run)
        return original(run)

    harness.result_line = keep
    for i, seed in enumerate(int(s) for s in args.seeds.split(',')):
        harness.run_cell(manifest, args.workload, seed, args.seconds, 0,
                         out, require_chip=not args.rehearse)
        run = kept.pop()
        row = {'seed': seed, 'program': dict(run.extra['gaps'])}
        if i < args.controls:
            family = manifest.reference(run.config['reference'])
            job = run.extra['reference_inputs'][0]
            params, feeds = harness.reference_inputs(
                family, seed, *run.extra['reference_inputs'])
            ref = family.train(job, params, feeds, steps=len(feeds))
            for name, kwargs in (
                    ('bfloat16', {'operands': 'bfloat16'}),
                    ('control_float8', {'operands': 'float8'}),
                    ('fault_half_batch', {'fault': 'half_batch'})):
                other = family.train(job, params, feeds,
                                     steps=len(feeds), **kwargs)
                gaps = correct.training_gaps(
                    as_program(other), ref, lambda m: m)
                row[name] = gaps
            del params, ref, other, feeds
        print(json.dumps(row), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == '__main__':
    sys.exit(main())
