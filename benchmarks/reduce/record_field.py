"""One number off a role's record of the window: `field` is a key of
the record, or `a.b` for a key of a dictionary on it; `scale`
multiplies it. The first record of the role that has the field is
read, wherever in the window it ended: a traced run's `clean` part is
not applied, so an answer that arrived under the profiler still
reports what it carried (the program measured it, not this clock)."""


def reduce(data, p):
    for spec, res in zip(data["specs"], data["results"]):
        if spec["role"] != p["role"]:
            continue
        for rec in res["records"]:
            value = rec
            for key in p["field"].split("."):
                value = value.get(key) if isinstance(value, dict) else None
            if value is not None:
                return float(value) * p.get("scale", 1.0)
    return None
