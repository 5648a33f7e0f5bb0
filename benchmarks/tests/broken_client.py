"""A load worker whose producer sends something else than the
generator says (`python3 -m benchmarks.tests.broken_client <spec>`):
the timed path broken underneath, for test_sharpness.py. The fault is
named in BENCH_TEST_FAULT (JSON); the harness's own process keeps the
sound generator, so the reference still speaks of what should have
been sent.

  {"bf16_throughput": true}  every throughput rounded to bfloat16, as a
                             lower-precision scoring path would see it
  {"octet_block": b}         one row of block b carries one octet more
"""

import json
import os
import sys

import numpy as np

from benchmarks import client, gen
from benchmarks.reference import to_bf16

FAULT = json.loads(os.environ["BENCH_TEST_FAULT"])
_values = gen.ProducerStream.values


def values(self, b):
    v = _values(self, b)
    if FAULT.get("bf16_throughput"):
        v["thr"] = to_bf16(v["thr"].astype(np.float32)).astype(np.int64)
    if FAULT.get("octet_block") == b:
        v["thr"][0, 0] += 1
    return v


gen.ProducerStream.values = values

if __name__ == "__main__":
    sys.exit(client.main(sys.argv))
