"""``BENCHMARK.json`` and the files its names lead to.

Everything that belongs to one configuration, one cell or one per-layer
metric sits in a file of its own, found by name — a later PR adds files
and entries, and edits none that is there:

- a cell      -> ``<paths[0]>/workloads/<workload name>.json``
- a config    -> the ``file`` of its ``configs`` entry
- a reference -> ``<paths[0]>/reference/<config file's "reference">.py``
- a reader    -> ``<paths[0]>/layer_metrics/<metric name>.py``, or, for
  a metric split by a suffix (``host_input_ms.tokens``), the file of
  the name before the last dot (``host_input_ms.py``)
"""

import importlib.util
import json
import os


class Manifest:
    def __init__(self, root: str, pending: bool = False):
        """``pending``: also know the cells of ``pending.json`` (built,
        not admitted); the driver's entry never asks for them."""
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, 'BENCHMARK.json')) as fh:
            self.data = json.load(fh)
        self.home = os.path.join(self.root, self.data['paths'][0])
        more = os.path.join(self.home, 'pending.json')
        if pending and os.path.exists(more):
            with open(more) as fh:
                extra = json.load(fh)
            for key in ('workloads', 'end_to_end', 'per_layer'):
                self.data[key] = self.data[key] + extra.get(key, [])

    def _by_name(self, key, name):
        for entry in self.data[key]:
            if entry['name'] == name:
                return entry
        raise KeyError(f'no {key} entry named {name!r}')

    def workload(self, name: str) -> dict:
        return self._by_name('workloads', name)

    def cell(self, name: str) -> dict:
        """The cell's own file, with the manifest's entry under
        ``entry``."""
        entry = self.workload(name)
        with open(os.path.join(self.home, 'workloads',
                               f'{name}.json')) as fh:
            cell = json.load(fh)
        cell['entry'] = entry
        return cell

    def config(self, name: str) -> dict:
        entry = self._by_name('configs', name)
        with open(os.path.join(self.root, entry['file'])) as fh:
            return json.load(fh)

    def metrics(self, group: str, workload: str):
        """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
        return [m for m in self.data[group]
                if 'workloads' not in m or workload in m['workloads']]

    def _load(self, folder, name):
        path = os.path.join(self.home, folder, f'{name}.py')
        if not os.path.exists(path):
            return None
        spec = importlib.util.spec_from_file_location(
            f'benchmark.{folder}.{name.replace(".", "_")}', path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def reader(self, metric: str):
        """``read(run, metric) -> value or None`` of a per-layer metric."""
        module = self._load('layer_metrics', metric)
        if module is None and '.' in metric:
            module = self._load('layer_metrics', metric.rsplit('.', 1)[0])
        if module is None:
            raise FileNotFoundError(
                f'no reader for per-layer metric {metric!r}')
        return module.read

    def reference(self, name: str):
        module = self._load('reference', name)
        if module is None:
            raise FileNotFoundError(f'no reference named {name!r}')
        return module

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.home, 'peaks.json')) as fh:
            table = json.load(fh)['devices']
        if device_kind not in table:
            raise KeyError(
                f'device kind {device_kind!r} is not in peaks.json '
                f'(has {sorted(table)}): add it with its source')
        return table[device_kind]


if __name__ == '__main__':
    # ``python3 benchmark/manifest.py --check``: for every cell the
    # metrics it reports and what it still lacks, by the checks the
    # benchmark's tests make (``tests/benchmark/accepted.py``); exit 1
    # where anything is lacking or an accepted file or entry changed.
    import argparse
    import sys
    parser = argparse.ArgumentParser(
        description='BENCHMARK.json of this checkout against its own '
                    'rules and the record of what was accepted')
    parser.add_argument('--check', action='store_true', required=True)
    parser.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)    # the references import ``benchmark``
    spec = importlib.util.spec_from_file_location(
        'accepted', os.path.join(root, 'tests', 'benchmark', 'accepted.py'))
    accepted = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(accepted)
    lines, problems = accepted.report(Manifest(root))
    print('\n'.join(lines))
    sys.exit(1 if problems else 0)
