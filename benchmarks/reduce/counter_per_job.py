"""A counter's rise over the window for each job that ran its `stage`
in it: delta of `series` over the rise of
theia_job_stage_seconds_count{kind, stage}. A manager that does not
export the counter, or a window with no job, gives nothing."""

from benchmarks import prom


def reduce(data, p):
    before, after = data["metrics_before"], data["metrics_after"]
    jobs = 'theia_job_stage_seconds_count{kind="%s",stage="%s"}' % (
        p["kind"], p["stage"])
    try:
        rise = prom.delta(before, after, p["series"])
        n = prom.delta(before, after, jobs)
    except KeyError:
        return None
    return rise / n if n > 0 else None
