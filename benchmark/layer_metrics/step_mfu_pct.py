"""The whole step's share of the chip's peak: FLOPs the forward and
backward passes require per sample (from shapes, the configuration
family's ``train_flops_per_sample``; recomputation does not count)
times the rate of the window's epochs in which no profiler was open,
over chips times the table's bf16 peak."""


def read(run, metric):
    if run.peaks is None or not run.quiet_rate:
        return None
    family = run.manifest.reference(run.config['reference'])
    from benchmark.steady import job_spec
    job = job_spec(run.cell, run.config, run.seed)
    per_sample = family.train_flops_per_sample(job['model'],
                                               run.cell['data'])
    per_sample /= float(run.cell.get('samples_per_row', 1))
    chips = int(run.cell['entry']['chips'])
    return 100.0 * per_sample * run.quiet_rate / (
        chips * run.peaks['bf16_flops_per_s'])
