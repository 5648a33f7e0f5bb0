"""A counter family's rise over the window, summed over the children
that carry every label of `where`: `series` is the family's bare name,
`where` {label: value}. For a family with one label more than the
reader wants to tell apart (the parts a read met, by table and by what
became of them: all tables' `read`). A manager that exports no such
child gives nothing."""

from benchmarks import prom


def reduce(data, p):
    before, after = data["metrics_before"], data["metrics_after"]
    want = ['%s="%s"' % kv for kv in p["where"].items()]
    keys = {k for side in (before, after) for k in side
            if k.startswith(p["series"] + "{")
            and all(w in k for w in want)}
    if not keys:
        return None
    return sum(prom.delta(before, after, k) for k in keys)
