#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a tiny size: the whole of a run — both
jobs, the window, the reference, the result line — with the sizes of
``rehearsal/<cell>.json`` laid over the cell's and the configuration's
files. It proves paths, arguments and control flow, nothing about speed:
every number is printed under ``cpu_rehearsal.<name>``, never under the
name of a device metric.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <name> \
        [--seed N] [--seconds S] [--trace 0|1]
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class TinyManifest:
    """The manifest with the rehearsal's sizes laid over one cell."""

    def __init__(self, manifest, workload, tiny=None):
        from benchmark.steady import merge
        self._m, self._merge, self._workload = manifest, merge, workload
        if tiny is None:
            with open(os.path.join(manifest.home, 'rehearsal',
                                   f'{workload}.json')) as fh:
                tiny = json.load(fh)
        self._tiny = tiny

    def __getattr__(self, name):
        return getattr(self._m, name)

    def cell(self, name):
        cell = self._m.cell(name)
        if name == self._workload:
            cell = self._merge(cell, self._tiny.get('cell'))
        return cell

    def config(self, name):
        return self._merge(self._m.config(name), self._tiny.get('config'))


def rehearse(workload, seed=1, seconds=1.0, trace=0, tiny=None, out=None):
    from benchmark import run
    from benchmark.manifest import Manifest
    manifest = TinyManifest(Manifest(ROOT, pending=True), workload, tiny)
    out = out or os.path.join(ROOT, '.bench_out', 'rehearsal', workload)
    os.makedirs(out, exist_ok=True)
    line = run.run_cell(manifest, workload, seed, seconds, trace, out,
                        require_chip=False)
    line['metrics'] = {f'cpu_rehearsal.{k}': v
                      for k, v in line['metrics'].items()}
    return line


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=1)
    parser.add_argument('--seconds', type=float, default=1.0)
    parser.add_argument('--trace', type=int, default=0)
    args = parser.parse_args(argv)
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    from benchmark import run
    run.place_run(ROOT, os.path.join('rehearsal', args.workload))
    print(json.dumps(rehearse(args.workload, args.seed, args.seconds,
                              args.trace)))
    return 0


if __name__ == '__main__':
    sys.exit(main())
