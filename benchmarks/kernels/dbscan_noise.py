"""Roofline function of theia_tpu/ops/dbscan.py `dbscan_noise` (and the
deviation `dbscan_scores` returns beside it) over a padded [S, T] batch.

`least()` counts what the algorithm cannot avoid moving if everything
in between stays on the chip: it reads float32 values and a bool mask,
and writes bool flags and a float32 deviation a series. Its arithmetic
is float32 comparisons on the vector unit, for which no peak is
published, so `flops` is 0 (README.md, "A kernel function") and the
share is of the memory's peak alone.

That ceiling is not within reach of this formulation and the share
says so: it reads about 4e-3 % (0.00436 % on a v5e; my chip runs, PR
37). A call moves 20.7 MB (25 us at 819 GB/s) and tests every pair of a
series' points twice, once for the neighbour counts and once for the
reachability: at 80 x 43,200 that is 1.49299e11 pair tests a pass, 0.28
and 0.30 s of the vector unit's time, which is its arithmetic limit
(about 7.5 lane-operations a test). The distance between the share and
100 % is what a formulation that sorts (O(T log T) a series,
references/dbscan.py) would close; it is not headroom of the pairwise
one. `pair_tests` gives the count for PERF.md and is what
`theia_job_dbscan_pair_tests_total` rises by a job; no reduction reads
it, because a pair test is neither a byte nor an operation of the matrix unit."""

from benchmarks import roofline


def dbscan_noise_bytes(n_series: int, n_steps: int) -> int:
    cells = n_series * n_steps
    return cells * 4 + cells * 1 + cells * 1 + n_series * 4


def pair_tests(n_series: int, n_steps: int) -> int:
    """Pairs one pass of the definition tests over full series."""
    return n_series * n_steps * n_steps


def least(data):
    shape = roofline.series_shape(data)
    return {"bytes": dbscan_noise_bytes(shape["series"], shape["steps"]),
            "flops": 0}
