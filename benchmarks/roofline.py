"""Bytes and operations a kernel needs for one call, from its shapes,
and the chip's published peaks (peaks.json, keyed by `device_kind`; a
device that is not in the table is an error, not a default).

A kernel's function `least(data) -> {"bytes": int, "flops": int}`
counts what the algorithm needs if everything in between stays on the
chip; either may be 0. Float32 arithmetic runs on the vector unit, for
which no peak is published (peaks.json `_source`): such a kernel states
`flops: 0` and is held to its bytes. `KERNELS` has the built-in ones;
any other name is benchmarks/kernels/<name>.py (extend.py)."""

from __future__ import annotations

import json
import os
from typing import Callable, Dict

from . import extend as _extend

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]


def ewma_scores_bytes(n_series: int, n_steps: int) -> int:
    """theia_tpu/ops/ewma.py `ewma_scores` over a padded [S, T] batch:
    reads float32 values and a bool mask, writes float32 EWMA, float32
    stddev [S] and bool flags — the least traffic the algorithm needs
    if everything in between stays on chip."""
    cells = n_series * n_steps
    return cells * 4 + cells * 1 + cells * 4 + n_series * 4 + cells * 1


def series_shape(data: Dict) -> Dict[str, int]:
    """[S, T] of the TAD job's tensor: the retained window's
    connections × points per connection, from the traffic file."""
    g = data["traffic"]["generator"]
    producers = [s for s in data["specs"] if s["role"] == "producer"]
    blocks = int(producers[0].get("preload_blocks", 0))
    n_slices = g["connections_per_producer"] // g["conns_per_block"]
    visits = -(-blocks // n_slices)
    return {"series": g["connections_per_producer"] * len(producers),
            "steps": visits * g["points_per_conn"]}


def least_ewma_scores(data: Dict) -> Dict[str, int]:
    shape = series_shape(data)
    return {"bytes": ewma_scores_bytes(shape["series"], shape["steps"]),
            "flops": 0}


KERNELS: Dict[str, Callable[[Dict], Dict[str, int]]] = {
    "ewma_scores": least_ewma_scores,
}


def least_seconds(kernel: str, data: Dict, device: Dict) -> float:
    """The larger of bytes over the memory's peak and operations over
    the matrix unit's."""
    pk = peaks(device["kind"])
    need = _extend.resolve("kernel", kernel)(data)
    return max(need["bytes"] / pk["hbm_bytes_per_s"],
               need["flops"] / pk["bf16_flops_per_s"])
