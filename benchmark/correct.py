"""The comparison that decides ``correct`` for a training cell.

Three numbers, each with a limit of its own in the cell's file
(``limits``), each from what the timed job's own first three steps
produced against the plain reference following the same three steps
from the same seeded weights and the same feed:

- ``loss_gap``: the widest relative gap of a step's loss;
- ``grad_gap``: the first gradient as the optimizer got it (from its
  first moment after one step), by the worst leaf: the gap between the
  program's norm and the reference's — not the norm of a difference —
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger;
- ``delta_gap``: the same measure on the norm of each leaf's change
  after the three steps. Leaves whose reference gradient is under a
  thousandth of the median leaf's are left out of this one (they move
  by round-off alone under Adam);
- ``grad_gap_all_leaves`` / ``delta_gap_all_leaves``: the same gap on
  the norm over all leaves together — steady from seed to seed where a
  single small leaf is not (``PERF.md`` §6 says when that matters).
"""

import statistics


def leaf_gaps(program: dict, reference: dict, skip=()) -> dict:
    """{leaf: gap} by the measure above."""
    median = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - ref) / max(ref, median, 1e-30)
            for leaf, ref in reference.items() if leaf not in skip}


def worst_leaf(program: dict, reference: dict, skip=()):
    """(gap, leaf) of the worst leaf by the measure above."""
    worst, where = 0.0, None
    for leaf, gap in leaf_gaps(program, reference, skip).items():
        if not gap <= worst:        # a NaN is the worst there is
            worst, where = gap, leaf
    return worst, where


def total_gap(program: dict, reference: dict) -> float:
    """The same gap on the norm over all leaves together."""
    tot_p = sum(v * v for v in program.values()) ** 0.5
    tot_r = sum(v * v for v in reference.values()) ** 0.5
    return abs(tot_p - tot_r) / tot_r


def training_gaps(program: dict, reference: dict, first_gradient) -> dict:
    """``program``: loss list, ``moment_norm``, ``delta_norm`` of the
    timed job; ``reference``: loss list, ``grad_norm``, ``delta_norm``.
    ``first_gradient`` turns first-moment norms into gradient norms."""
    grad_prog = first_gradient(program['moment_norm'])
    grad_ref = reference['grad_norm']
    if set(grad_prog) != set(grad_ref):
        raise ValueError('the program and the reference have '
                         'different leaves')
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program['loss'], reference['loss']))
    if len(program['loss']) != len(reference['loss']):
        loss_gap = float('nan')
    median = statistics.median(grad_ref.values())
    still = {leaf for leaf, g in grad_ref.items() if g < 1e-3 * median}
    grad_gap, grad_leaf = worst_leaf(grad_prog, grad_ref)
    delta_gap, delta_leaf = worst_leaf(
        program['delta_norm'], reference['delta_norm'], skip=still)
    return {'loss_gap': loss_gap, 'grad_gap': grad_gap,
            'delta_gap': delta_gap,
            'grad_gap_all_leaves': total_gap(grad_prog, grad_ref),
            'delta_gap_all_leaves': total_gap(
                program['delta_norm'], reference['delta_norm']),
            'where': {'grad_gap': grad_leaf, 'delta_gap': delta_leaf,
                      'left_out': sorted(still)}}


def judge(gaps: dict, limits: dict):
    """(correct, [[name, number, limit], ...]): every number named in
    ``limits`` has to be there, finite and within its limit."""
    compared, ok = [], True
    for name, limit in limits.items():
        value = gaps.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        compared.append([name, value, limit])
    return ok and bool(compared), compared
