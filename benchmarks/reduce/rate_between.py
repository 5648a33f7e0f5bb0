"""Sum of `field` over the records of `role` acked between the send
and the answer of the first request of `between` (another role's), a
second: the rate while that request ran. In a traced run the stretch
ends where the profiler was asked for at the latest."""


def reduce(data, p):
    span = None
    for spec, res in zip(data["specs"], data["results"]):
        if spec["role"] == p["between"] and res["records"]:
            rec = res["records"][0]
            span = (rec["send"], min(rec["ack"], data["clean"][1]))
            break
    if span is None or span[1] <= span[0]:
        return None
    total = sum(r.get(p["field"]) or 0
                for spec, res in zip(data["specs"], data["results"])
                if spec["role"] == p["role"] for r in res["records"]
                if r.get("status") == 200 and not r.get("degraded")
                and span[0] <= r["ack"] <= span[1])
    return total / (span[1] - span[0])
