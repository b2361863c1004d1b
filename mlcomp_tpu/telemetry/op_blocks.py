"""The train step's device time by model block.

A compiled step's text names every instruction beside the ``op_name``
its ops were traced under, and that name runs through the flax modules'
own scopes (``layer_1/attn/q_norm/mul``) and the two scopes the update
rule adds (``loss``, ``optimizer``, ``train/loop.py``). A device trace
names each op by its instruction name only. So the program reads the
table once, from the text it compiles anyway (``JaxTrain._introspect``,
the row ``step.op_blocks``), and whoever holds a trace joins the two:

- ``block_of(op_name)``: the rule, the one place the vocabulary lives;
- ``op_table(hlo_text)``: every top-level instruction of a compiled
  program (the entry computation's and every ``while`` / ``call`` /
  ``conditional`` body's, not the insides of fused computations) ->
  ``[result shape, block, backward]``;
- ``block_split(modules, ops, table)``: a device's ``XLA Modules`` and
  ``XLA Ops`` events + the table -> ms a step per block;
- ``row_scatters(hlo_text)``: the scatters of whole rows a block's
  top-level instructions hold (the gauge ``step.wide_scatters``).

The rule, the table and the join are plain text and numbers: no jax.
"""

import bisect
import json
import re

#: the metric table's row that holds a task's train step's table
ROW = 'step.op_blocks'

#: the blocks, in the order a split is printed
BLOCKS = ('attention', 'mixer', 'moe_routing', 'moe_experts', 'mlp',
          'embed_head', 'optimizer', 'other')

#: scope names -> block, by rule (``block_of``); a model added later
#: extends these sets. ``moe`` is split three ways by what it holds.
MOE, MOE_EXPERTS, MOE_SHARED = 'moe', 'expert_matmul', 'shared'
ATTENTION = {'attn', 'full_attn'}
MIXER = {'linear_attn', 'conv'}
MLP = {'mlp'}
OPTIMIZER = {'optimizer'}
#: the loss (``train/loop.py``'s scope) and the modules round the layer
#: stack: the final norm and the untied head of ``models/transformer.py``,
#: ``qwen3_next.py``, ``deepseek_v3.py`` (``lfm2_moe.py``'s head is its
#: embedding table), and ``ouro.py``'s exits (final norm, gate, head and
#: loss, each exit's inside the scope ``exit``)
EMBED_HEAD = {'loss', 'norm_final', 'lm_head', 'exit'}
#: the families' language models: their own ops outside the layer stack
#: are the embedding's lookup and its gradient, the learned positions
#: (``transformer_lm``) and a tied head (``lfm2_moe``) — ``embed_head``
MODELS = {'TransformerLM', 'Qwen3NextLM', 'Lfm2MoeLM', 'DeepseekV3LM',
          'OuroLM'}
#: what marks an op of the stack: a layer's scope, a scanned stack's, and
#: ``remat``'s own (its recomputation and the gradients it hands back);
#: the buffers a scan stacks its layers' values in are a bare
#: ``broadcast_in_dim`` at the model's own level
STACK = re.compile(r'layer_\d+|layers|periods|while|checkpoint|remat2?')
STACK_BUFFER = ['broadcast_in_dim']

#: ops a trace shows that only enclose other ops of the step
WRAPPERS = ('while', 'conditional', 'call')
#: the largest ops a split lists for each block
TOP = 10

_NAME_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$')
_OPCODE_RE = re.compile(r'(?:^|[\s)])([a-z][a-z0-9_\-]*)\(')
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED_RE = re.compile(
    r'\b(?:body|condition|to_apply|true_computation|false_computation)'
    r'=%?([\w.\-]+)|branch_computations=\{([^}]*)\}')
_CALLS_RE = re.compile(r'\bcalls=%?([\w.\-]+)')
_SUFFIX_RE = re.compile(r'\.\d+$')
_WRAPPED_RE = re.compile(r'[\w\-]*\((.*)\)')


def scopes(op_name: str) -> list:
    """The scope names of an ``op_name`` with the transforms' wrappers
    taken off (``transpose(jvp(loss))`` -> ``loss``), so that an op's
    backward and its ``remat`` recomputation name the op's own scopes."""
    out, depth, part = [], 0, []
    for ch in op_name + '/':
        if ch == '/' and depth == 0:
            name = ''.join(part)
            inner = _WRAPPED_RE.fullmatch(name)
            while inner:
                name = inner.group(1)
                inner = _WRAPPED_RE.fullmatch(name)
            if '/' in name:
                out += scopes(name)
            elif name:
                out.append(name)
            part = []
            continue
        depth += (ch == '(') - (ch == ')')
        part.append(ch)
    return out


def block_of(op_name: str) -> str:
    """The block an op belongs to, by the first rule that matches."""
    path = scopes(op_name)
    names = set(path)
    if MOE in names:
        if MOE_EXPERTS in names:
            return 'moe_experts'
        return 'mlp' if MOE_SHARED in names else 'moe_routing'
    if names & ATTENTION:
        return 'attention'
    if names & MIXER:
        return 'mixer'
    if names & MLP:
        return 'mlp'
    if names & OPTIMIZER:
        return 'optimizer'
    if names & EMBED_HEAD or _in_model(path):
        return 'embed_head'
    return 'other'


def _in_model(path: list) -> bool:
    """An op of the language model's own body, outside its stack."""
    at = [i for i, name in enumerate(path) if name in MODELS]
    return bool(at) and path[at[-1] + 1:] != STACK_BUFFER and not any(
        STACK.fullmatch(name) for name in path)


def is_backward(op_name: str) -> bool:
    return 'transpose(' in op_name


def result_shape(rhs: str) -> str:
    """The first result's shape of an instruction's right-hand side
    (``bf16[4,2048]{1,0} fusion(..)`` -> ``bf16[4,2048]``), as a trace
    event's name carries it too."""
    return rhs.lstrip('(').split('{', 1)[0].split(' ', 1)[0].rstrip(',')


def _computations(hlo_text: str):
    """{computation: [(name, rhs)]} and the entry's name."""
    comps, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        if not line or line.startswith(('HloModule', '}')):
            continue
        if not line[0].isspace():               # a computation's header
            head = line.split(' (', 1)[0].split()
            current = head[-1].lstrip('%')
            comps[current] = []
            if head[0] == 'ENTRY':
                entry = current
            continue
        found = _NAME_RE.match(line)
        if found and current is not None:
            comps[current].append(found.groups())
    return comps, entry


def _top_level(comps: dict, entry: str):
    """(name, rhs, op_name) of every top-level instruction: the entry's
    and every ``while`` / ``call`` / ``conditional`` body's."""
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, rhs in comps[comp]:
            op = _OPCODE_RE.search(rhs)
            if op and op.group(1) in WRAPPERS:
                for one, many in _CALLED_RE.findall(rhs):
                    todo += [one] if one else [
                        c.strip().lstrip('%') for c in many.split(',')]
            found = _OP_NAME_RE.search(rhs)
            yield name, rhs, found.group(1) if found else ''


def op_table(hlo_text: str) -> dict:
    """{instruction name: [result shape, block, backward]} of every
    top-level instruction of a compiled program's text."""
    comps, entry = _computations(hlo_text)
    return {name: [result_shape(rhs), block_of(op_name),
                   int(is_backward(op_name))]
            for name, rhs, op_name in _top_level(comps, entry)}


def _scatters_rows(rhs: str, comps: dict) -> bool:
    """Whether an instruction is, or fuses, a scatter of whole rows into
    a matrix: its result [rows, width], its indices picking rows alone."""
    op = _OPCODE_RE.search(rhs)
    if op and op.group(1) == 'scatter':
        return bool(re.fullmatch(r'\w+\[\d+,\d+\]', result_shape(rhs))) \
            and 'scatter_dims_to_operand_dims={0}' in rhs
    called = _CALLS_RE.search(rhs)
    return bool(called) and any(_scatters_rows(inner, comps) for _, inner
                                in comps.get(called.group(1), ()))


def row_scatters(hlo_text: str, block: str = 'moe_routing') -> int:
    """The top-level instructions of ``block`` that scatter whole rows
    (``_scatters_rows``): in ``moe_routing`` a sparse layer's scatter-adds
    of buffer rows into the tokens [tokens, d_model], the gauge
    ``step.wide_scatters``. The router's top-k gradient scatters into
    (token, expert) cells, a count into a vector: neither is counted."""
    comps, entry = _computations(hlo_text)
    return sum(block_of(op_name) == block and _scatters_rows(rhs, comps)
               for _, rhs, op_name in _top_level(comps, entry))


def _event(name: str):
    """(instruction name, result shape or None) of a trace event named
    by its HLO text (``fusion.12 = bf16[..]{..} fusion(..)``) or by the
    instruction's name alone."""
    text = str(name)
    head, _, rhs = text.partition(' = ')
    return head.strip().lstrip('%'), (result_shape(rhs) if rhs else None)


def _base(name: str) -> str:
    return _SUFFIX_RE.sub('', name)


def block_split(modules, ops, table, window=None) -> dict:
    """One device's ms a step by block for the program that took most
    of its time (the train step: ``step_device_ms``'s choice).

    ``modules``, ``ops``: that device's ``XLA Modules`` and ``XLA Ops``
    events, ``[name, start_ns, duration_ns]``; ``window``: (lo, hi) ns,
    the runs that lie wholly inside it (default: all). Each op that
    starts inside a run of the program is matched by its instruction
    name, its result shape checked against ``table`` (``op_table``);
    the ops that only enclose others (``WRAPPERS``) are skipped.
    Returns ``{'module', 'runs', 'step_ms', 'ops_ms', 'matched': the
    share of the ops' time matched, 'ms': {block: ms a step},
    'backward_ms', 'kernel_ms' (custom calls): {block: ms a step},
    'top': {block: [[label, ms a step, backward]]}, 'unmatched':
    [[label, ms a step]]}``; None where no program ran."""
    lo, hi = window or (float('-inf'), float('inf'))
    runs = {}
    for name, start, dur in modules:
        if start >= lo and start + dur <= hi:
            runs.setdefault(name, []).append((start, start + dur))
    if not runs:
        return None
    module, spans = max(runs.items(),
                        key=lambda kv: sum(b - a for a, b in kv[1]))
    spans.sort()
    starts = [a for a, _ in spans]
    ms, back, kernel = ({b: 0 for b in BLOCKS} for _ in range(3))
    labels, unmatched, total = {}, {}, 0
    for name, start, dur in ops:
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= spans[at][1]:
            continue                            # not inside a run
        inst, shape = _event(name)
        if _base(inst) in WRAPPERS:
            continue
        total += dur
        row = table.get(inst)
        if row is None or (shape is not None and shape != row[0]):
            label = f'{_base(inst)} {shape or ""}'.strip()
            unmatched[label] = unmatched.get(label, 0) + dur
            continue
        shape, block, backward = row
        ms[block] += dur
        back[block] += dur * backward
        kernel[block] += dur * ('custom-call(' in str(name))
        key = (block, f'{_base(inst)} {shape}', backward)
        labels[key] = labels.get(key, 0) + dur
    n = len(spans)

    def per_step(ns):
        return ns / 1e6 / n

    top_ops = {b: [] for b in BLOCKS}
    for (block, label, backward), ns in sorted(
            labels.items(), key=lambda kv: -kv[1]):
        if len(top_ops[block]) < TOP:
            top_ops[block].append([label, per_step(ns), backward])
    missed = sum(unmatched.values())
    return {
        'module': module, 'runs': n,
        'step_ms': per_step(sum(b - a for a, b in spans)),
        'ops_ms': per_step(total),
        'matched': (total - missed) / total if total else 0.0,
        'ms': {b: per_step(v) for b, v in ms.items()},
        'backward_ms': {b: per_step(v) for b, v in back.items()},
        'kernel_ms': {b: per_step(v) for b, v in kernel.items()},
        'top': top_ops,
        'unmatched': sorted(([k, per_step(v)] for k, v in
                             unmatched.items()), key=lambda r: -r[1])[:TOP],
    }


def persist_op_table(session, task_id: int, table: dict,
                     build_s: float) -> int:
    """The table as ONE row, ``step.op_blocks``: its value the number of
    instructions, its tags ``{'ops': table, 'build_s'}`` (the seconds
    ``op_table`` took). Returns the bytes of the tags."""
    from mlcomp_tpu.db.providers.telemetry import MetricProvider
    from mlcomp_tpu.utils.misc import now
    tags = json.dumps({'ops': table, 'build_s': build_s},
                      separators=(',', ':'))
    MetricProvider(session).add_many([(
        task_id, ROW, 'gauge', None, float(len(table)), now(), 'train',
        tags)])
    return len(tags)


def load_op_table(tags) -> dict:
    """The table of a ``step.op_blocks`` row's tags (a JSON string or
    the dict it decodes to)."""
    if isinstance(tags, str):
        tags = json.loads(tags)
    return (tags or {}).get('ops') or {}


__all__ = ['BLOCKS', 'ROW', 'block_of', 'scopes', 'is_backward',
           'op_table', 'row_scatters', 'block_split', 'persist_op_table',
           'load_op_table', 'result_shape']
