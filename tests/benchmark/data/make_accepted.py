#!/usr/bin/env python3
"""Write ``benchmark_as_accepted.json``, the record the benchmark's tests
hold every later PR against (``../accepted.py: as_accepted``).

    python3 tests/benchmark/data/make_accepted.py

Only a ``benchmark`` PR runs it, as its last edit: it takes the tree as
it stands — ``BENCHMARK.json`` and the sha256 of every file under its
``paths``, this script among them, the record itself excepted — as what
was accepted.
"""

import hashlib
import json
import os

RECORD = 'tests/benchmark/data/benchmark_as_accepted.json'
ABOUT = (
    'The benchmark as its last `benchmark` PR left it: BENCHMARK.json, and '
    'the sha256 of every file under its paths but this one. Written by '
    'make_accepted.py and read by ../accepted.py. A PR that is not a '
    '`benchmark` PR adds files, adds entries after these, appends its '
    "cell's name to the workloads of the metrics it reports, and changes "
    'nothing else; a `benchmark` PR that edits an accepted file or entry '
    'runs make_accepted.py again in the same change.')


def files_under(root: str, paths) -> list:
    """Every file a checkout holds under ``paths``, as git would commit
    it: no byte-code, not the record."""
    found = []
    for top in paths:
        for folder, dirs, names in os.walk(os.path.join(root, top)):
            dirs[:] = [d for d in dirs if d != '__pycache__']
            for name in names:
                path = os.path.relpath(os.path.join(folder, name), root)
                if not name.endswith('.pyc') and path != RECORD:
                    found.append(path.replace(os.sep, '/'))
    return sorted(found)


def record(root: str) -> str:
    """The record's text for the tree at ``root``."""
    with open(os.path.join(root, 'BENCHMARK.json')) as fh:
        manifest = json.load(fh)
    digests = {}
    for path in files_under(root, manifest['paths']):
        with open(os.path.join(root, path), 'rb') as fh:
            digests[path] = hashlib.sha256(fh.read()).hexdigest()
    return json.dumps({'about': ABOUT, 'files': digests,
                       'manifest': manifest}, indent=1) + '\n'


if __name__ == '__main__':
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    text = record(root)
    with open(os.path.join(root, RECORD), 'w') as fh:
        fh.write(text)
    print(f'{RECORD}: {len(json.loads(text)["files"])} files')
