"""Roofline function of theia_tpu/ops/dbscan.py `dbscan_noise` where a
series is not a connection: the `--agg-flow pod` job's padded [S, T]
batch, a series a (podNamespace, podLabels, direction) key.

What a later cell of series that are not connections needs to know
(benchmarks/README.md may not be edited by the PR that brings one):
`roofline.series_shape`, which `kernels/dbscan_noise.py` and the
built-in `ewma_scores` take their [S, T] from, assumes that a series is
a connection: S = `connections_per_producer` x producers, T = visits of
a slice x `points_per_conn`. Under an aggregated query neither holds: S
is how many keys the population's connections fall on (1,985 for the
4,000 connections of `Population(0, 4000)`: 1,001 outbound and 984
inbound pods, where `series_shape` would say 4,000 and count the bytes
twice over), and T is the seconds on which any connection of a key has
a row. Both are taken here from the reference's own grouping of the
population (references/tad_agg_pod.py `connection_keys`) and from the
blocks the producers preload, as the key law lays them out
(`conn_index(b)`; a block's seconds are its own): a cell for another
aggregated mode brings a file like this one with that mode's keys.

The bytes are `kernels/dbscan_noise.py`'s: float32 values and a bool
mask in, bool flags and a float32 deviation a series out; `flops` 0,
the arithmetic is float32 comparisons on the vector unit. At the
cell's [1,985, 864]: 10,298,180 B, 12.6 us at a v5e's 819 GB/s."""

from benchmarks import gen
from benchmarks.kernels.dbscan_noise import dbscan_noise_bytes
from benchmarks.references import tad_agg_pod as reference


def pod_series_shape(data):
    """{"series", "steps"} of the pod job's tensor over what the
    producers preload: the keys that have a row, and the most seconds
    one of them has."""
    seconds = {}
    producers = [s for s in data["specs"] if s["role"] == "producer"]
    for i, spec in enumerate(producers):
        stream = gen.stream(data["traffic"], spec.get("seed", 0),
                            spec.get("producer", i))
        pop = gen.Population(stream.producer, stream.n_conn, stream.start)
        arms = reference.connection_keys(pop)
        for b in range(int(spec.get("preload_blocks", 0))):
            keys = {arm[int(j)] for arm in arms
                    for j in stream.conn_index(b)} - {None}
            for key in keys:
                seconds[key] = seconds.get(key, 0) + stream.points
    return {"series": len(seconds),
            "steps": max(seconds.values(), default=0)}


def least(data):
    shape = pod_series_shape(data)
    return {"bytes": dbscan_noise_bytes(shape["series"], shape["steps"]),
            "flops": 0}
