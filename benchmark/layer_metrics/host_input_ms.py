"""Mean per step, over the window, of the host's input phases: the
program's ``step.phase.data_wait_ms`` + ``step.phase.h2d_ms`` rows
(host clock around host work: the permutation slice or the next host
batch, and the ``device_put`` of the index vector or the batch). The
rows of epoch 0, which is set-up, are left out."""


def read(run, metric):
    total, steps = 0.0, 0
    for phase in ('data_wait', 'h2d'):
        rows = [v for _, v, _ in run.series(f'step.phase.{phase}_ms')]
        rows = rows[run.steps_per_epoch:]
        if not rows:
            return None
        total += sum(rows)
        steps = len(rows)
    return total / steps
