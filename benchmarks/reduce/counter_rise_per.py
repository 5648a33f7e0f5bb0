"""A counter's rise over the window per rise of another series:
`series` over `per`, times `scale`. `per` is what the rise is counted
against: the acked blocks (theia_ingest_batches_total), a histogram's
`_count` with its labels (the answers a phase sent, the jobs a stage
ran), a `_count` without labels for all of a family's children (the
panel requests). A name given without labels stands for every child
of it, summed (a fault counter over all stages, the collector's pause
over all generations). A manager that does not export `series`, or a
window in which `per` did not rise, gives nothing."""

from benchmarks import prom


def rise(before, after, name):
    """after - before of `name{labels}` as given, or of every child
    of a bare `name`; KeyError where neither side has one."""
    if "{" in name:
        return prom.delta(before, after, name)
    keys = {k for side in (before, after) for k in side
            if k == name or k.startswith(name + "{")}
    if not keys:
        raise KeyError(name)
    return sum(prom.delta(before, after, k) for k in keys)


def reduce(data, p):
    before, after = data["metrics_before"], data["metrics_after"]
    try:
        up = rise(before, after, p["series"])
        n = rise(before, after, p["per"])
    except KeyError:
        return None
    return up / n * p.get("scale", 1.0) if n > 0 else None
