"""Seconds the first measured task spent in backend compiles: the sum
of its ``compile.backend_ms`` rows (cache loads included as the program
books them)."""


def read(run, metric):
    tasks = [t for t in run.extra.get('tasks', ()) if t['ok']]
    if not tasks:
        return None
    rows = run.series('compile.backend_ms', tasks[0]['task_id'])
    return sum(v for _, v, _ in rows) / 1e3
