"""Key law `spread_spikes`: the built-in law `slices` with spikes of many
heights in place of one.

Under `slices` every spike of a connection is `base x spike_magnitude`,
one value: over a retained day the spikes of a series are each other's
neighbours, a density-based job (DBSCAN, eps 2.5e8, min_samples 4)
finds a second cluster and flags nothing. Here a point is a spike with
`spike_rate`, and a spike is `base x m` with m log-uniform in
[`spike_magnitude_low`, `spike_magnitude_high`]: the low ones lie
within eps of the series' bulk and are core points, some higher ones
lie within eps of a core spike and are border points, the rest are
alone and are noise, so that a check sees all three classes.

Base, noise, connection identity, slice order and the block's own
generator are `slices`' (the noise and the spike positions are drawn
first and in its order, the heights after them), so producer,
reference and control draw the same rows. What the seed changes: each
connection's base throughput, the noise, where the spikes are and how
high, the order of the slices. What it does not: which connections
exist, hence the job's tensor shape and the compile cache's key.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import gen


class Stream(gen.ProducerStream):
    def __init__(self, traffic: Dict, seed: int, producer: int) -> None:
        super().__init__(traffic, seed, producer)
        g = traffic["generator"]
        self.log_low = np.log(float(g["spike_magnitude_low"]))
        self.log_high = np.log(float(g["spike_magnitude_high"]))

    def values(self, b: int) -> Dict[str, np.ndarray]:
        conn = self.conn_index(b)
        rng = np.random.default_rng([self.seed, self.producer, 1, b])
        shape = (self.cpb, self.points)
        noise = np.clip(rng.normal(1.0, 0.05, shape), 0.1, None)
        spike = rng.random(shape) < self.spike_rate
        height = np.exp(rng.uniform(self.log_low, self.log_high, shape))
        base = self.base[conn][:, None]
        thr = base * np.where(spike, height, noise)
        t0 = self.start + b * self.points * self.interval
        flow_end = t0 + np.arange(self.points, dtype=np.int64) \
            * self.interval
        return {"conn": conn, "thr": thr.astype(np.int64),
                "flow_end": flow_end}
