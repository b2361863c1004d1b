"""What the benchmark's guard of itself checks, as functions of a tree or
of a manifest: the tests call them on the repo, the recipe test on a
temporary tree with a cell added, and ``python3 benchmark/manifest.py
--check`` prints what they find.

Every check returns a list of problems, each a line that names the
entry or the file at fault; an empty list is a pass.

``as_accepted`` is what "as accepted" means. A PR that is not a
``benchmark`` PR adds files, adds entries after the accepted ones of
their group, and appends its cell's name to the ``workloads`` lists of
the metrics the cell reports. Nothing else of an accepted entry, and no
byte of an accepted file, changes. The record it is held against is
``data/benchmark_as_accepted.json``, which ``data/make_accepted.py``
writes and only a ``benchmark`` PR brings up to date.
"""

import hashlib
import json
import os
import re

SNAPSHOT = os.path.join('data', 'benchmark_as_accepted.json')

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
TOP_LEVEL = ('command', 'paths', 'run_seconds', 'configs', 'workloads',
             'end_to_end', 'per_layer')
GROUPS = TOP_LEVEL[3:]
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')
ALLOWED = {
    'configs': {'name', 'source', 'file', 'reduced', 'why'},
    'workloads': {'name', 'config', 'traffic', 'chips', 'why'},
    'end_to_end': {'name', 'unit', 'better', 'bound', 'source',
                   'workloads'},
    'per_layer': {'name', 'unit', 'better', 'source', 'layer', 'moves',
                  'workloads'}}


def load_snapshot(root: str) -> dict:
    """The record beside this file in the tree at ``root``."""
    with open(os.path.join(root, 'tests', 'benchmark', SNAPSHOT)) as fh:
        return json.load(fh)


# ------------------------------------------------------------ as accepted
def as_accepted(root: str, snapshot: dict) -> list:
    """The tree at ``root`` against the record of what was accepted."""
    problems = []
    for path, digest in snapshot['files'].items():
        try:
            with open(os.path.join(root, path), 'rb') as fh:
                now = hashlib.sha256(fh.read()).hexdigest()
        except FileNotFoundError:
            problems.append(f'accepted file {path} is gone')
            continue
        if now != digest:
            problems.append(
                f'accepted file {path} was changed (sha256 {now[:12]}, '
                f'accepted {digest[:12]}): add a file, edit none')
    with open(os.path.join(root, 'BENCHMARK.json')) as fh:
        today = json.load(fh)
    was = snapshot['manifest']
    if set(today) != set(was):
        problems.append(f'BENCHMARK.json has the keys {sorted(today)}, '
                        f'accepted {sorted(was)}')
    for key in TOP_LEVEL[:3]:
        if today.get(key) != was[key]:
            problems.append(f'{key} is {today.get(key)!r}, accepted '
                            f'{was[key]!r}')
    cells = [c['name'] for c in today.get('workloads', [])]
    for group in GROUPS:
        problems += _group_as_accepted(
            group, was[group], today.get(group, []), cells)
    return problems


def _group_as_accepted(group, was, now, cells):
    problems = []
    at = {entry['name']: i for i, entry in enumerate(now)}
    for i, old in enumerate(was):
        name = old['name']
        what = f'accepted {group} entry {name}'
        if name not in at:
            problems.append(f'{what} was removed')
            continue
        if at[name] != i:
            problems.append(
                f'{what} stands at place {at[name]}, accepted at {i}: '
                f'new entries go after the {len(was)} accepted ones, '
                f'which keep their order')
        new = now[at[name]]
        for key in sorted((set(old) | set(new)) - {'workloads'}):
            if old.get(key) != new.get(key):
                problems.append(f'{what}: {key} is {new.get(key)!r}, '
                                f'accepted {old.get(key)!r}')
        problems += _list_as_accepted(
            what, old.get('workloads'), new.get('workloads'), cells)
    return problems


def _list_as_accepted(what, old, new, cells):
    """The one thing of an accepted entry that grows: names are
    appended to its ``workloads``; a metric that had no list (every
    cell reports it) keeps none."""
    if old is None and new is None:
        return []
    if old is None or new is None:
        return [f'{what}: it had {"no" if old is None else "a"} '
                f'workloads list and has {"one" if old is None else "none"}']
    if new[:len(old)] != old:
        return [f'{what}: its workloads {new} do not start with the '
                f'accepted {old}: a cell\'s name is appended']
    problems = []
    for name in new[len(old):]:
        if name not in cells:
            problems.append(f'{what}: {name!r} in its workloads is no cell')
        elif new.count(name) > 1:
            problems.append(f'{what}: {name!r} is in its workloads twice')
    return problems


# ------------------------------------------------------------- the letter
def names_and_units(manifest, group: str) -> list:
    problems = []
    names = [e['name'] for e in manifest.data[group]]
    for name in sorted({n for n in names if names.count(n) > 1}):
        problems.append(f'{group} holds two entries named {name}')
    for entry in manifest.data[group]:
        what = f'{group} entry {entry["name"]}'
        if not NAME.match(entry['name']):
            problems.append(f'{what}: not a name')
        if 'unit' in entry:
            if not UNIT.match(entry['unit']):
                problems.append(f'{what}: unit {entry["unit"]!r}')
            if entry.get('better') not in ('lower', 'higher'):
                problems.append(f'{what}: better {entry.get("better")!r}')
            if entry.get('source') not in SOURCES:
                problems.append(f'{what}: source {entry.get("source")!r}')
        for key in ('why', 'layer'):
            text = entry.get(key)
            if text is not None and not (
                    0 < len(text) <= 200 and '\n' not in text
                    and '\t' not in text):
                problems.append(f'{what}: {key} is not one line of 1 to '
                                f'200 characters')
    return problems


def entry_keys_and_bounds(manifest) -> list:
    problems = []
    for group, allowed in ALLOWED.items():
        for entry in manifest.data[group]:
            if not set(entry) <= allowed:
                problems.append(f'{group} entry {entry["name"]}: keys '
                                f'{sorted(set(entry) - allowed)}')
    for entry in manifest.data['end_to_end']:
        what = f'end_to_end entry {entry["name"]}'
        if not 0 < entry.get('bound', 0) <= 0.1:
            problems.append(f'{what}: bound {entry.get("bound")!r} is '
                            f'not in (0, 0.1]')
        if entry.get('source') not in ('host_clock', 'device_trace'):
            problems.append(f'{what}: source {entry.get("source")!r}')
    return problems


# ------------------------------------------------- names, files and lists
def _cell_files(manifest, cell, configs):
    """What a cell's entry leads to: its file, its configuration's, the
    reference."""
    what = f'cell {cell["name"]}'
    problems = []
    if not NAME.match(cell['traffic']):
        problems.append(f'{what}: traffic {cell["traffic"]!r}')
    if cell['chips'] not in (1, 4):
        problems.append(f'{what}: chips {cell["chips"]!r} is not 1 or 4')
    if cell['config'] not in configs:
        return problems + [f'{what}: no configs entry named '
                           f'{cell["config"]!r}']
    try:
        body = manifest.cell(cell['name'])
    except FileNotFoundError as exc:
        return problems + [f'{what}: its file is missing '
                           f'({os.path.relpath(exc.filename, manifest.root)})']
    if body['config'] != cell['config']:
        problems.append(f'{what}: its file says config '
                        f'{body["config"]!r}, its entry {cell["config"]!r}')
    try:
        config = manifest.config(cell['config'])
        if not callable(manifest.reference(config['reference']).train):
            problems.append(f'{what}: reference {config["reference"]!r} '
                            f'has no train()')
    except FileNotFoundError as exc:
        problems.append(f'{what}: {exc.filename or exc}')
    return problems


def four_chip_cells(cells):
    """Of the cells a quarter, rounded down, may ask for four chips, and
    one always may."""
    four = [c['name'] for c in cells if c['chips'] == 4]
    room = max(1, len(cells) // 4)
    if len(four) <= room:
        return []
    return [f'cell {four[-1]}: {len(four)} of {len(cells)} cells ask for '
            f'four chips ({", ".join(four)}); at most {room} may']


def every_name_resolves(manifest) -> list:
    configs = {c['name'] for c in manifest.data['configs']}
    cells = manifest.data['workloads']
    problems = []
    for cell in cells:
        problems += _cell_files(manifest, cell, configs)
    problems += four_chip_cells(cells)
    for name in sorted(configs - {c['config'] for c in cells}):
        problems.append(f'configs entry {name}: no cell uses it')
    home = os.path.relpath(manifest.home, manifest.root)
    for config in manifest.data['configs']:
        what = f'configs entry {config["name"]}'
        if not config['file'].startswith(home + '/'):
            problems.append(f'{what}: file {config["file"]!r} is not '
                            f'under {home}/')
            continue
        try:
            body = manifest.config(config['name'])
        except FileNotFoundError:
            problems.append(f'{what}: its file {config["file"]} is '
                            f'missing')
            continue
        if body.get('reduced') != config['reduced']:
            problems.append(f'{what}: reduced {config["reduced"]}, its '
                            f'file {body.get("reduced")}')
    for metric in manifest.data['per_layer']:
        try:
            manifest.reader(metric['name'])
        except FileNotFoundError:
            problems.append(
                f'per_layer entry {metric["name"]}: no reader '
                f'{home}/layer_metrics/{metric["name"]}.py (nor of the '
                f'name before its last dot)')
    return problems


def _cell_lists(manifest, name):
    """The lists a cell has to stand in: it reports ``setup_s`` and one
    more end-to-end metric (its file's ``rate_metric`` where it names
    one), at least one per-layer metric, and every per-layer metric it
    reports moves an end-to-end metric it reports."""
    what = f'cell {name}'
    e2e = {m['name'] for m in manifest.metrics('end_to_end', name)}
    problems = []
    try:
        rate_metric = manifest.cell(name).get('rate_metric')
    except FileNotFoundError:
        rate_metric = None      # every_name_resolves says so
    if rate_metric is not None and rate_metric not in e2e:
        problems.append(f'{what}: is not in the workloads of its rate '
                        f'metric {rate_metric}')
    if 'setup_s' not in e2e:
        problems.append(f'{what}: does not report setup_s')
    if len(e2e - {'setup_s'}) < 1:
        problems.append(f'{what}: reports no end-to-end metric besides '
                        f'setup_s: append its name to one\'s workloads')
    layer = manifest.metrics('per_layer', name)
    if not layer:
        problems.append(f'{what}: reports no per-layer metric')
    for metric in layer:
        if metric['moves'] not in e2e:
            problems.append(
                f'{what}: reports {metric["name"]}, which moves '
                f'{metric["moves"]}, and is not in the workloads of '
                f'{metric["moves"]}')
    return problems


def every_cell_reports_what_its_metrics_move(manifest) -> list:
    cells = [c['name'] for c in manifest.data['workloads']]
    problems = []
    for name in cells:
        problems += _cell_lists(manifest, name)
    for group in ('end_to_end', 'per_layer'):
        for metric in manifest.data[group]:
            for name in metric.get('workloads', ()):
                if name not in cells:
                    problems.append(f'{group} entry {metric["name"]}: '
                                    f'{name!r} in its workloads is no '
                                    f'cell')
    return problems


def pending_cells(manifest) -> list:
    """``pending.json`` holds cells built but not admitted: the same
    letter, every name a file and in its lists — the checks above on the
    manifest that knows them too —, and unknown to the manifest the
    driver's entry loads."""
    path = os.path.join(manifest.home, 'pending.json')
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        pending = json.load(fh)
    admitted = {c['name'] for c in manifest.data['workloads']}
    problems = []
    for cell in pending.get('workloads', []):
        if not NAME.match(cell['name']):
            problems.append(f'pending cell {cell["name"]}: not a name')
        if cell['name'] in admitted:
            problems.append(f'pending cell {cell["name"]}: is admitted '
                            f'too')
    for group in ('end_to_end', 'per_layer'):
        for metric in pending.get(group, []):
            if not (NAME.match(metric['name'])
                    and UNIT.match(metric['unit'])):
                problems.append(f'pending {group} entry '
                                f'{metric["name"]}: name or unit')
    more = type(manifest)(manifest.root, pending=True)
    said = set(every_name_resolves(manifest)
               + every_cell_reports_what_its_metrics_move(manifest))
    return problems + [
        p for p in every_name_resolves(more)
        + every_cell_reports_what_its_metrics_move(more) if p not in said]


# ------------------------------------------------------------ all of them
def check(manifest) -> list:
    """Every check on the tree the manifest was read from."""
    problems = as_accepted(manifest.root, load_snapshot(manifest.root))
    for group in GROUPS:
        problems += names_and_units(manifest, group)
    problems += entry_keys_and_bounds(manifest)
    problems += every_name_resolves(manifest)
    problems += every_cell_reports_what_its_metrics_move(manifest)
    problems += pending_cells(manifest)
    return problems


def report(manifest) -> tuple:
    """(lines to print, problems): for every cell the metrics it reports
    and what it still lacks, then what is wrong elsewhere."""
    problems = check(manifest)
    lines = []
    for cell in manifest.data['workloads']:
        name = cell['name']
        lines.append(f'{name}  (config {cell["config"]}, traffic '
                     f'{cell["traffic"]}, chips {cell["chips"]})')
        for group in ('end_to_end', 'per_layer'):
            names = [m['name'] for m in manifest.metrics(group, name)]
            lines.append(f'  {group}: {", ".join(names) or "none"}')
        own = [p for p in problems if p.startswith(f'cell {name}: ')]
        lines += [f'  LACKS {p.split(": ", 1)[1]}' for p in own]
    rest = [p for p in problems if not any(
        p.startswith(f'cell {c["name"]}: ')
        for c in manifest.data['workloads'])]
    lines += [f'REFUSED {p}' for p in rest]
    lines.append(f'{len(problems)} problems' if problems else
                 f'{len(manifest.data["workloads"])} cells, nothing '
                 f'lacking, every accepted file and entry as accepted')
    return lines, problems
