#!/usr/bin/env python3
"""The plain reference of a ``task`` cell, as a child of the runner (the
runner stays off the chip): follows every step of one short task from
the benchmark's seeded weights, on the benchmark's rows in the order the
configuration's seed gives, and prints the losses, the leaf norms of
the momentum after the last step and of the parameters' change.

    python3 benchmark/task_reference.py <reference_job.json> \
        [operands=float8] [fault=half_batch]

The two optional words put the control or a fault in the reference's
place, for the readings a cell's limits are set from.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def step_order(job_seed: int, rows: int, steps: int, batch: int):
    """The rows of each step of epoch 0: the permutation numpy's
    ``RandomState(seed * 1000 + epoch)`` gives, cut to whole batches —
    what the configuration's ``seed`` means to ``jax_train``."""
    import numpy as np
    perm = np.random.RandomState(job_seed * 1000).permutation(rows)
    return perm[:steps * batch].astype(np.int32).reshape(steps, batch)


def main(argv=None):
    argv = argv or sys.argv[1:]
    path, more = argv[0], dict(a.split('=', 1) for a in argv[1:])
    with open(path) as fh:
        spec = json.load(fh)
    from benchmark import weights
    from benchmark.manifest import Manifest
    manifest = Manifest(ROOT, pending=True)
    if spec.get('tiny'):
        from benchmark.rehearse import TinyManifest
        manifest = TinyManifest(manifest, spec['workload'], spec['tiny'])
    config = manifest.config(manifest.cell(spec['workload'])['config'])
    family = manifest.reference(config['reference'])
    job, steps = spec['job'], int(spec['steps'])
    resident = family.resident(spec['dataset'])
    order = step_order(job['seed'], int(resident['x_all'].shape[0]),
                       steps, job['batch_size'])
    params = weights.make_params(
        spec['seed'], family.param_spec(job['model']))
    out = family.train(job, params,
                       [dict(resident, feed=order[i]) for i in range(steps)],
                       steps=steps, **more)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
