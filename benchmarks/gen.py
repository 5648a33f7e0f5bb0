"""The one traffic generator: flow-record blocks from a traffic file.

A traffic mix is a JSON file of parameters (benchmarks/traffic/); this
module turns it and `--seed` into TBLK blocks of the 52-column `flows`
row. Modelled on theia_tpu/data/synth.py (pod-to-pod / pod-to-service /
pod-to-external connections, per-connection throughput series with
spikes) and on the TBLK encoder of theia_tpu/store/wire.py, rewritten
vectorized and without the program's classes: later PRs may change the
program, not the yardstick.

What the seed changes and what it does not: the *identity* of the
connections (addresses, ports, pods, the split over detector shards)
is fixed by the traffic file, so every seed drives the same device
shapes and the compile cache of the first run serves every later one.
The seed draws each connection's base throughput, the noise and the
spikes of every point, and the order in which a producer visits its
slices of connections.

A producer owns `connections` connections, cut into slices of
`conns_per_block`; block b carries `points_per_conn` successive
points of every connection of one slice. No connection belongs to two
producers, so the detector's per-connection recurrence sees every
connection's points in its own producer's order however the producers'
requests interleave: alert decisions do not depend on timing.
"""

from __future__ import annotations

import json
import struct
from typing import Dict, List, Tuple

import numpy as np

from . import extend as _extend
from .schema import FLOW_SCHEMA, host_dtype

BLOCK_MAGIC = b"TBLK"
#: 2021-01-01 00:00:00 UTC, as the program's own generator
DEFAULT_START = 1609459200

N_NAMESPACES = 16
PODS_PER_NAMESPACE = 64
N_NODES = 8
N_SERVICES = 16
N_EXTERNAL = 250
PORT_SPAN = 28000


def _mix(j: np.ndarray, producer: int) -> np.ndarray:
    """A fixed integer hash of the connection index: spreads pods,
    nodes and destinations without a random generator (identity must
    not depend on the seed)."""
    h = (j.astype(np.uint64) * np.uint64(2654435761)
         + np.uint64(producer * 40503 + 12345)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(15)
    h = (h * np.uint64(2246822519)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(13)
    return h.astype(np.int64)


def cluster_uuid(producer: int) -> str:
    return f"8a6a2e0e-0000-4000-8000-{producer + 1:012d}"


class Population:
    """The fixed identity of one producer's connections: for every
    string column a table of strings and an index per connection, for
    every static numeric column a value per connection."""

    def __init__(self, producer: int, n_conn: int,
                 start_time: int = DEFAULT_START) -> None:
        p = producer
        j = np.arange(n_conn, dtype=np.int64)
        h = _mix(j, p)
        src_ns = h % N_NAMESPACES
        src_pod = (h >> 4) % PODS_PER_NAMESPACE
        dst_ns = (h >> 10) % N_NAMESPACES
        dst_pod = (h >> 14) % PODS_PER_NAMESPACE
        src_node = (h >> 20) % N_NODES
        dst_node = (h >> 23) % N_NODES
        u = (h >> 7) % 1000
        external = u < 100
        service = (~external) & (u < 400)
        protected = ((h >> 9) % 10) == 0
        svc = (h >> 5) % N_SERVICES
        ext = (h >> 12) % N_EXTERNAL

        pods = [(a, b) for a in range(N_NAMESPACES)
                for b in range(PODS_PER_NAMESPACE)]
        src_pod_i = src_ns * PODS_PER_NAMESPACE + src_pod
        dst_pod_i = dst_ns * PODS_PER_NAMESPACE + dst_pod
        pod_names = [f"pod-{a}-{b}" for a, b in pods]
        pod_ips = [f"10.{p}.{a}.{b}" for a, b in pods]
        pod_labels = [json.dumps({"app": f"app-{a}-{b}"}, sort_keys=True)
                      for a, b in pods]
        ns_names = [f"ns-{a}" for a in range(N_NAMESPACES)]
        nodes = [f"node-{p}-{i}" for i in range(N_NODES)]
        n_pods = len(pods)

        def opt(table: List[str], idx: np.ndarray, blank: np.ndarray
                ) -> Tuple[List[str], np.ndarray]:
            """Table with '' appended; `blank` rows point at it."""
            return table + [""], np.where(blank, len(table), idx)

        ing = protected & ~external
        policies_in = [f"allow-ingress-{i}" for i in range(5)]
        policies_eg = [f"allow-egress-{i}" for i in range(5)]
        self.strings: Dict[str, Tuple[List[str], np.ndarray]] = {
            "sourceIP": (pod_ips, src_pod_i),
            "destinationIP": (
                pod_ips + [f"203.0.113.{i}" for i in range(N_EXTERNAL)],
                np.where(external, n_pods + ext, dst_pod_i)),
            "sourcePodName": (pod_names, src_pod_i),
            "sourcePodNamespace": (ns_names, src_ns),
            "sourceNodeName": (nodes, src_node),
            "destinationPodName": opt(pod_names, dst_pod_i, external),
            "destinationPodNamespace": opt(ns_names, dst_ns, external),
            "destinationNodeName": opt(nodes, dst_node, external),
            "destinationClusterIP": opt(
                [f"10.96.{p}.{i + 1}" for i in range(N_SERVICES)],
                svc, ~service),
            "destinationServicePortName": opt(
                [f"ns-{a}/svc-{i}:http" for a in range(N_NAMESPACES)
                 for i in range(N_SERVICES)],
                dst_ns * N_SERVICES + svc, ~service),
            "ingressNetworkPolicyName": opt(policies_in, h % 5, ~ing),
            "ingressNetworkPolicyNamespace": opt(ns_names, dst_ns, ~ing),
            "ingressNetworkPolicyRuleName": opt(
                ["rule-0"], np.zeros(n_conn, np.int64), ~ing),
            "egressNetworkPolicyName": opt(policies_eg, h % 5,
                                           ~protected),
            "egressNetworkPolicyNamespace": opt(ns_names, src_ns,
                                                ~protected),
            "egressNetworkPolicyRuleName": opt(
                ["rule-0"], np.zeros(n_conn, np.int64), ~protected),
            "tcpState": (["ESTABLISHED"], np.zeros(n_conn, np.int64)),
            "sourcePodLabels": (pod_labels, src_pod_i),
            "destinationPodLabels": opt(pod_labels, dst_pod_i, external),
            "clusterUUID": ([cluster_uuid(p)],
                            np.zeros(n_conn, np.int64)),
            "egressName": ([""], np.zeros(n_conn, np.int64)),
            "egressIP": ([""], np.zeros(n_conn, np.int64)),
        }
        flow_type = np.where(external, 3,
                             np.where(src_node == dst_node, 1, 2))
        self.static: Dict[str, np.ndarray] = {
            "flowStartSeconds": start_time - 10 - j // PORT_SPAN,
            "flowEndReason": np.full(n_conn, 3),
            "sourceTransportPort": 32768 + j % PORT_SPAN,
            "destinationTransportPort": np.where(
                external, 443,
                np.where(service, 80, 5201 + (h >> 26) % 9)),
            "protocolIdentifier": np.full(n_conn, 6),
            "destinationServicePort": np.where(service, 80, 0),
            "ingressNetworkPolicyRuleAction": ing.astype(np.int64),
            "ingressNetworkPolicyType": ing.astype(np.int64),
            "egressNetworkPolicyRuleAction": protected.astype(np.int64),
            "egressNetworkPolicyType": protected.astype(np.int64),
            "flowType": flow_type,
            "trusted": np.zeros(n_conn, np.int64),
        }
        self.n_conn = n_conn


# -- TBLK encoding (layout: theia_tpu/store/wire.py) ----------------------

def _width_reduce(a: np.ndarray) -> Tuple[np.ndarray, int]:
    if a.dtype.kind in "iu" and a.itemsize > 1 and len(a):
        mn, mx = int(a.min()), int(a.max())
        for cand in ("<u1", "<u2", "<u4"):
            cdt = np.dtype(cand)
            if cdt.itemsize >= a.itemsize:
                break
            if mx - mn <= int(np.iinfo(cdt).max):
                return (a - mn).astype(cand), mn
    return a, 0


def encode_numeric(name: str, values: np.ndarray, dtype: str) -> bytes:
    a = np.ascontiguousarray(values, dtype=dtype)
    stored, base = _width_reduce(a)
    bname = name.encode()
    dt = a.dtype.str.encode("ascii")
    sdt = stored.dtype.str.encode("ascii")
    return b"".join((
        struct.pack("<H", len(bname)), bname,
        struct.pack("<BH", 0, len(dt)), dt,
        struct.pack("<H", len(sdt)), sdt,
        struct.pack("<qI", base, stored.nbytes), stored.tobytes()))


def encode_string(name: str, table: List[str], idx: np.ndarray) -> bytes:
    """`idx` indexes `table` per row; the block carries only the
    strings it uses, in table order, and local codes."""
    uniq, local = np.unique(idx, return_inverse=True)
    code_dt = ("<u1" if len(uniq) <= 0xFF
               else "<u2" if len(uniq) <= 0xFFFF else "<i4")
    encoded = [table[int(i)].encode() for i in uniq]
    lens = np.fromiter(map(len, encoded), "<i4", count=len(encoded))
    blob = b"".join(encoded)
    codes = np.ascontiguousarray(local.astype(code_dt))
    bname = name.encode()
    return b"".join((
        struct.pack("<H", len(bname)), bname,
        struct.pack("<BIIB", 1, len(uniq), len(blob), codes.itemsize),
        lens.tobytes(), blob, codes.tobytes()))


def block_header(n_rows: int) -> bytes:
    return BLOCK_MAGIC + struct.pack("<IH", n_rows, len(FLOW_SCHEMA))


# -- one producer's stream -------------------------------------------------

class ProducerStream:
    """Blocks of one producer, in order. `values(b)` gives what the
    reference needs of block b (no encoding); `block(b)` the payload.
    Blocks must be encoded in order 0, 1, 2, … because the cumulative
    counters of a connection carry from one block to the next.

    This is the key law `slices`, and its surface is what every key
    law has (`stream` below): `producer`, `n_conn`, `cpb`, `points`,
    `rows`, `interval`, `start`, `conn_index(b)`, `values(b)`,
    `block(b)`. A block carries `points` successive points of `cpb`
    distinct connections. What the seed changes here: the values and
    the order of the slices, never a connection's identity."""

    def __init__(self, traffic: Dict, seed: int, producer: int) -> None:
        g = traffic["generator"]
        self.producer = producer
        self.seed = int(seed)
        self.n_conn = int(g["connections_per_producer"])
        self.cpb = int(g["conns_per_block"])
        self.points = int(g["points_per_conn"])
        self.interval = int(g.get("interval_seconds", 1))
        self.start = int(g.get("start_time", DEFAULT_START))
        self.base_throughput = float(g.get("base_throughput", 1e7))
        self.spike_rate = float(g.get("spike_rate", 0.001))
        self.spike_magnitude = float(g.get("spike_magnitude", 50.0))
        if self.n_conn % self.cpb:
            raise ValueError("connections_per_producer must be a "
                             "multiple of conns_per_block")
        self.n_slices = self.n_conn // self.cpb
        self.rows = self.cpb * self.points
        rng = np.random.default_rng([self.seed, producer, 0x5EED])
        self.base = self.base_throughput * (0.5 + rng.random(self.n_conn))
        #: a seed visits the same slices as every other, in its own order
        self.order = rng.permutation(self.n_slices)
        self._pop = None
        self._static: Dict[int, Dict[str, bytes]] = {}
        self._carry = None
        self._next = 0

    def slice_of(self, b: int) -> int:
        return int(self.order[b % self.n_slices])

    def conn_index(self, b: int) -> np.ndarray:
        s = self.slice_of(b)
        return np.arange(s * self.cpb, (s + 1) * self.cpb)

    def values(self, b: int) -> Dict[str, np.ndarray]:
        """conn [cpb] (indices into this producer's population), thr
        [cpb, T] int64 throughput, flow_end [T] int64 seconds."""
        conn = self.conn_index(b)
        rng = np.random.default_rng([self.seed, self.producer, 1, b])
        noise = np.clip(rng.normal(1.0, 0.05, (self.cpb, self.points)),
                        0.1, None)
        spike = rng.random((self.cpb, self.points)) < self.spike_rate
        base = self.base[conn][:, None]
        thr = np.where(spike, base * self.spike_magnitude, base * noise)
        t0 = self.start + b * self.points * self.interval
        flow_end = t0 + np.arange(self.points, dtype=np.int64) \
            * self.interval
        return {"conn": conn, "thr": thr.astype(np.int64),
                "flow_end": flow_end}

    def _static_columns(self, s: int) -> Dict[str, bytes]:
        cols = self._static.get(s)
        if cols is None:
            if self._pop is None:
                self._pop = Population(self.producer, self.n_conn,
                                       self.start)
            conn = np.arange(s * self.cpb, (s + 1) * self.cpb)
            cols = {}
            for name, (table, idx) in self._pop.strings.items():
                cols[name] = encode_string(
                    name, table, np.repeat(idx[conn], self.points))
            kinds = dict(FLOW_SCHEMA)
            for name, per_conn in self._pop.static.items():
                cols[name] = encode_numeric(
                    name, np.repeat(per_conn[conn], self.points),
                    host_dtype(kinds[name]))
            self._static[s] = cols
        return cols

    def block(self, b: int) -> Tuple[bytes, Dict[str, int]]:
        if b != self._next:
            raise ValueError(f"blocks are encoded in order: expected "
                             f"{self._next}, got {b}")
        self._next += 1
        if self._carry is None:
            self._carry = {k: np.zeros(self.n_conn, np.int64) for k in
                           ("octet", "packet", "roctet", "rpacket")}
        v = self.values(b)
        conn, thr = v["conn"], v["thr"]
        octet = thr * self.interval
        packet = np.maximum(octet // 1400, 1)
        roctet = octet // 20
        rpacket = np.maximum(octet // 28000, 1)
        totals = {}
        for key, delta in (("octet", octet), ("packet", packet),
                           ("roctet", roctet), ("rpacket", rpacket)):
            tot = self._carry[key][conn][:, None] + np.cumsum(delta, 1)
            self._carry[key][conn] = tot[:, -1]
            totals[key] = tot.ravel()
        end = np.tile(v["flow_end"], self.cpb)
        thr_r = thr.ravel()
        dynamic = {
            "timeInserted": end, "flowEndSeconds": end,
            "flowEndSecondsFromSourceNode": end,
            "flowEndSecondsFromDestinationNode": end,
            "packetTotalCount": totals["packet"],
            "octetTotalCount": totals["octet"],
            "packetDeltaCount": packet.ravel(),
            "octetDeltaCount": octet.ravel(),
            "reversePacketTotalCount": totals["rpacket"],
            "reverseOctetTotalCount": totals["roctet"],
            "reversePacketDeltaCount": rpacket.ravel(),
            "reverseOctetDeltaCount": roctet.ravel(),
            "throughput": thr_r, "reverseThroughput": thr_r // 20,
            "throughputFromSourceNode": thr_r,
            "throughputFromDestinationNode": thr_r,
            "reverseThroughputFromSourceNode": thr_r // 20,
            "reverseThroughputFromDestinationNode": thr_r // 20,
        }
        static = self._static_columns(self.slice_of(b))
        parts = [block_header(self.rows)]
        for name, kind in FLOW_SCHEMA:
            if name in dynamic:
                parts.append(encode_numeric(name, dynamic[name],
                                            host_dtype(kind)))
            else:
                parts.append(static[name])
        return b"".join(parts), {"rows": self.rows,
                                 "octets": int(octet.sum())}


#: the built-in key laws; any other name is benchmarks/laws/<name>.py
LAWS = {"slices": ProducerStream}


def law(traffic: Dict):
    """The class of the key law the traffic file names
    (`generator.law`, default `slices`)."""
    return _extend.resolve("law", traffic["generator"].get("law", "slices"))


def stream(traffic: Dict, seed: int, producer: int):
    """One producer's stream under the traffic file's key law. The
    producer, the reference and the control all get theirs here, so
    they draw the same rows."""
    return law(traffic)(traffic, seed, producer)
