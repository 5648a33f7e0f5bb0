"""Analytics jobs: throughput anomaly detection, policy recommendation,
and abnormal traffic-drop detection."""

from .drop_detection import run_drop_detection
from .heavy_hitters import HeavyHitterAlert, HeavyHitterDetector
from .itemsets import mine_frequent_patterns, run_pattern_mining
from .npr import (NAMESPACE_ALLOW_LIST, read_distinct_flows, run_npr)
from .series import (SeriesBatch, TadQuerySpec, build_series,
                     read_columns)
from .spatial import flow_embeddings, run_spatial, spatial_outliers
from .streaming import StreamingDetector, stream_update
from .tad import ALGORITHMS, detect_anomalies, run_tad, score_series

__all__ = [
    "SeriesBatch", "TadQuerySpec", "build_series", "read_columns",
    "ALGORITHMS", "detect_anomalies", "run_tad", "score_series",
    "NAMESPACE_ALLOW_LIST", "read_distinct_flows", "run_npr",
    "StreamingDetector", "stream_update",
    "run_drop_detection",
    "HeavyHitterAlert", "HeavyHitterDetector",
    "mine_frequent_patterns", "run_pattern_mining",
    "flow_embeddings", "run_spatial", "spatial_outliers",
]
