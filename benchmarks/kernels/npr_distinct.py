"""Roofline function of the NetworkPolicy Recommendation job's DISTINCT
(theia_tpu/analytics/npr_device.py `distinct_rows`: the rows under the
job's WHERE clause in, the distinct 9-tuples and their multiplicities
out).

`least()` counts what the query cannot avoid moving if everything in
between stays on the chip, from the job's rows and the nine columns'
widths and not from how a program packs them: every selected row's
nine codes of 4 bytes in, every distinct row's nine codes and its
count out. So it reads the same work whatever implements it. The rows
and the distinct flows are the reference's over the generator's own
rows of the preloaded blocks (checks/npr_policies.py), not the
program's counters. No arithmetic of the matrix unit: `flops` is 0
(README.md, "A kernel function") and the share is of the memory's peak
alone.

A sort is not traffic: a call compares and moves each row many times
over, so the share reads well under 1 %, as `dbscan_noise_roofline`
does; the distance to 100 % is what a hash or a radix pass that touches
a row once would close, not headroom of a sort."""

from benchmarks import gen
from benchmarks.checks import npr_policies

CODE_BYTES = 4
COLUMNS = 9


def distinct_bytes(rows: int, distinct: int) -> int:
    return (rows * COLUMNS + distinct * (COLUMNS + 1)) * CODE_BYTES


def least(data):
    traffic = data["traffic"]
    spec = npr_policies.job_spec(traffic)
    producers = [s for s in data["specs"] if s["role"] == "producer"]
    want = npr_policies.reference(
        (rec for s in producers
         for rec in npr_policies.block_records(
             gen.stream(traffic, s["seed"], s["producer"]),
             int(s.get("preload_blocks", 0)) + int(s.get("warm_blocks", 0)))),
        spec)
    return {"bytes": distinct_bytes(want["rows_sorted"],
                                    want["distinct_flows"]),
            "flops": 0}
