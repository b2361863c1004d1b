"""Set-up by the program's own spans: the seconds of one span name,
summed over BOTH jobs of a run (the warm job and the measured one:
``setup_s`` holds both). The metric's suffix names the span:

- ``.data``       ``train.setup.data``: the dataset's load, quantisation
  and placement;
- ``.state``      ``train.setup.state``: model, optimizer, train state
  (fresh, restored or pretrained);
- ``.introspect`` ``train.setup.introspect``: the AOT lower + compile of
  the train step for cost, memory and collective analysis;
- ``.epoch0``     the ``train.epoch`` spans tagged ``epoch`` 0: the
  epoch that traces, compiles or loads the step programs.
"""


def read(run, metric):
    from benchmark import program_spans
    part = metric.rsplit('.', 1)[1]
    name = program_spans.EPOCH if part == 'epoch0' \
        else f'train.setup.{part}'
    picked = [r['duration'] for r in program_spans.rows(run)
              if r['name'] == name
              and (part != 'epoch0' or r['tags'].get('epoch') == 0)]
    return sum(picked) if picked else None
