"""Device time of one train step: inside the traced epoch, the summed
device time of the program (``XLA Modules`` line, one name and fingerprint a
program) that took most of it — the train step — over the number of
times it ran."""


def read(run, metric):
    reduced = run.reduced()
    if not reduced or not reduced['modules']:
        return None
    name, (seconds, count) = max(reduced['modules'].items(),
                                 key=lambda kv: kv[1][0])
    run.note(f'{metric}: module {name} ran {count:.0f} times')
    return seconds / count * 1e3
