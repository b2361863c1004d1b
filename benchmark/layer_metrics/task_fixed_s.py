"""What a short task costs besides training: the runner's clock around
the first measured child, less the task's own training seconds (its
rows over the ``images_per_sec`` of its epoch row)."""


def read(run, metric):
    tasks = [t for t in run.extra.get('tasks', ()) if t['ok']]
    if not tasks:
        return None
    rows = run.query(
        "select value from report_series where task = ? and "
        "name = 'images_per_sec' order by id", (tasks[0]['task_id'],))
    if not rows or not rows[0]['value']:
        return None
    training = sum(run.extra['train_rows'] / r['value'] for r in rows)
    return tasks[0]['end'] - tasks[0]['start'] - training
