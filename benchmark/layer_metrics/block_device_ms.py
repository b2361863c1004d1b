"""Device time of the train step by model block, in ms a step
(``block_device_ms.<block>``), from the device trace of one whole epoch.

The program writes once a task, in set-up, which block each op of its
compiled train step belongs to: the row ``step.op_blocks`` of its metric
table (``mlcomp_tpu/telemetry/op_blocks.py``; the blocks come from the
flax modules' own scopes and the update rule's ``loss`` and
``optimizer``). This reader takes the train step's runs on the ``XLA
Modules`` line between the runner's two marks — the program that took
most of the time, as ``step_device_ms`` picks it —, sums every ``XLA
Ops`` event inside them onto its block by instruction name, its result
shape checked against the row, skips the ops that only enclose others,
and divides by the runs: the program's own ``op_blocks.block_split``.

Where less than 99% of the ops' time is matched every block reads
``None``, never a partial number, and standard error says what did not
match. ``None`` too where the program wrote no such row (a program
without it) or there is no trace. Standard error also carries, a block
at a time, the forward / backward split (``transpose(`` in the op's
name), the kernel ops (custom calls) apart from the rest, and the ops
that took most time."""

import json

#: the share of the step's op time the row has to account for
MATCHED = 0.99
NAME = 'block_device_ms'


def read(run, metric):
    if NAME not in run.extra:           # one split a run, for all blocks
        run.extra[NAME] = split(run)
    blocks = run.extra[NAME]
    return None if blocks is None else blocks.get(metric.rsplit('.', 1)[1])


def split(run):
    """{block: ms a step} of the traced epoch, or None."""
    rows = run.query('select value, tags from metric where task = ? and '
                     "name = 'step.op_blocks'", (run.task_id,))
    if not rows or run.peaks is None:
        return None
    from benchmark import program_spans, trace_reduce
    trace = program_spans.load_trace(run)
    if trace is None:
        return None
    from mlcomp_tpu.telemetry import op_blocks
    tags = rows[-1]['tags']
    decoded = json.loads(tags)
    table = op_blocks.load_op_table(decoded)
    window = trace_reduce.marks(trace)
    parts = [op_blocks.block_split(
        trace_reduce.line_events(plane, trace_reduce.MODULE_LINE),
        trace_reduce.line_events(plane, trace_reduce.OP_LINE),
        table, window) for plane in trace_reduce.device_planes(trace)]
    parts = [p for p in parts if p]
    if not parts:
        return None
    one = parts[0]

    def mean(key):
        return {b: sum(p[key][b] for p in parts) / len(parts)
                for b in op_blocks.BLOCKS}

    ms, back, kernel = mean('ms'), mean('backward_ms'), mean('kernel_ms')
    matched = min(p['matched'] for p in parts)
    build = decoded.get('build_s') or 0.0
    run.note(f'{NAME}: row step.op_blocks: {int(rows[-1]["value"])} '
             f'instructions, {len(tags)} bytes, built in {build:.3f} s')
    run.note(f'{NAME}: module {one["module"]} ran {one["runs"]} times, '
             f'{one["step_ms"]:.3f} ms a run, ops {one["ops_ms"]:.3f} ms; '
             f'matched {100 * matched:.3f}% of the ops\' time; blocks sum '
             f'to {sum(ms.values()):.3f} ms')
    for label, ms_ in one['unmatched']:
        run.note(f'{NAME}: NOT MATCHED {label} {ms_:.4f} ms')
    for block in op_blocks.BLOCKS:
        if not ms[block]:
            continue
        run.note(f'{NAME}.{block}: {ms[block]:.3f} ms = forward '
                 f'{ms[block] - back[block]:.3f} + backward '
                 f'{back[block]:.3f}; kernel ops {kernel[block]:.3f}, '
                 f'the rest {ms[block] - kernel[block]:.3f}')
        for label, ms_, backward in one['top'][block]:
            run.note(f'{NAME}.{block}:   {label} {ms_:.4f} ms'
                     f'{" (backward)" if backward else ""}')
    if matched < MATCHED:
        run.note(f'{NAME}: every block reads None: under '
                 f'{100 * MATCHED:.0f}% of the ops\' time matched')
        return {b: None for b in op_blocks.BLOCKS}
    return ms
