"""The longest stretch, in ms, in which no worker of `role` got an
ack: between the window's open, successive acks (whichever worker's)
and the window's close. In a traced run only the part of the window
before the profiler was asked for (`clean`): under it every request
stretches."""


def reduce(data, p):
    lo = data["t_open"]
    hi = min(data["t_open"] + data["seconds"], data["clean"][1])
    acks = sorted(r["ack"] for spec, res in zip(data["specs"],
                                                data["results"])
                  if spec["role"] == p["role"] for r in res["records"]
                  if r.get("status") == 200 and lo <= r["ack"] <= hi)
    if not acks:
        return None
    edges = [lo] + acks + [hi]
    return max(b - a for a, b in zip(edges, edges[1:])) * 1e3
