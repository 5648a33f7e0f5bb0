"""Roofline function of theia_tpu/ops/arima.py `arima_scores` over a
padded [S, T] batch.

`least()` counts what the algorithm cannot avoid moving if everything
in between stays on the chip: it reads float32 values and a bool mask,
and writes float32 forecasts, a float32 deviation a series and bool
flags. Its arithmetic is float32 on the vector unit, for which no peak
is published, so `flops` is 0 (README.md, "A kernel function") and the
share is of the memory's peak alone.

That ceiling is not within reach and the share says so: a call is S x
ceil(T / k) prefix fits, each followed by a residual recursion of T - 1
sequential steps (at 20 x 43,200 and k = 21: 41,160 fits, 1.78 G
recursion steps, 1.17 M loop iterations as the program chunks them),
over 8.6 MB. `recursion_steps` gives that count for PERF.md; no
reduction reads it, because a step is neither a byte nor an operation
of the matrix unit."""

from benchmarks import roofline


def arima_scores_bytes(n_series: int, n_steps: int) -> int:
    cells = n_series * n_steps
    return cells * 4 + cells * 1 + cells * 4 + n_series * 4 + cells * 1


def recursion_steps(n_series: int, n_steps: int, refit_every: int) -> int:
    return n_series * -(-n_steps // refit_every) * (n_steps - 1)


def least(data):
    shape = roofline.series_shape(data)
    return {"bytes": arima_scores_bytes(shape["series"], shape["steps"]),
            "flops": 0}
