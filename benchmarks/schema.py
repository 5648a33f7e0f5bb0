"""The 52-column `flows` row as the benchmark sends it.

A copy of theia_tpu/schema/flow_schema.py (names, order and host
dtypes; reference create_table.sh:31-84), kept here so that a change
to the program cannot change what the yardstick sends."""

from __future__ import annotations

# kind → host dtype on the wire before width reduction
_DT = {"datetime": "<i8", "u8": "<i4", "u16": "<i4", "u64": "<i8",
       "string": None}

FLOW_SCHEMA = (
    ("timeInserted", "datetime"),
    ("flowStartSeconds", "datetime"),
    ("flowEndSeconds", "datetime"),
    ("flowEndSecondsFromSourceNode", "datetime"),
    ("flowEndSecondsFromDestinationNode", "datetime"),
    ("flowEndReason", "u8"),
    ("sourceIP", "string"),
    ("destinationIP", "string"),
    ("sourceTransportPort", "u16"),
    ("destinationTransportPort", "u16"),
    ("protocolIdentifier", "u8"),
    ("packetTotalCount", "u64"),
    ("octetTotalCount", "u64"),
    ("packetDeltaCount", "u64"),
    ("octetDeltaCount", "u64"),
    ("reversePacketTotalCount", "u64"),
    ("reverseOctetTotalCount", "u64"),
    ("reversePacketDeltaCount", "u64"),
    ("reverseOctetDeltaCount", "u64"),
    ("sourcePodName", "string"),
    ("sourcePodNamespace", "string"),
    ("sourceNodeName", "string"),
    ("destinationPodName", "string"),
    ("destinationPodNamespace", "string"),
    ("destinationNodeName", "string"),
    ("destinationClusterIP", "string"),
    ("destinationServicePort", "u16"),
    ("destinationServicePortName", "string"),
    ("ingressNetworkPolicyName", "string"),
    ("ingressNetworkPolicyNamespace", "string"),
    ("ingressNetworkPolicyRuleName", "string"),
    ("ingressNetworkPolicyRuleAction", "u8"),
    ("ingressNetworkPolicyType", "u8"),
    ("egressNetworkPolicyName", "string"),
    ("egressNetworkPolicyNamespace", "string"),
    ("egressNetworkPolicyRuleName", "string"),
    ("egressNetworkPolicyRuleAction", "u8"),
    ("egressNetworkPolicyType", "u8"),
    ("tcpState", "string"),
    ("flowType", "u8"),
    ("sourcePodLabels", "string"),
    ("destinationPodLabels", "string"),
    ("throughput", "u64"),
    ("reverseThroughput", "u64"),
    ("throughputFromSourceNode", "u64"),
    ("throughputFromDestinationNode", "u64"),
    ("reverseThroughputFromSourceNode", "u64"),
    ("reverseThroughputFromDestinationNode", "u64"),
    ("clusterUUID", "string"),
    ("egressName", "string"),
    ("egressIP", "string"),
    ("trusted", "u8"),
)

assert len(FLOW_SCHEMA) == 52


def host_dtype(kind: str):
    return _DT[kind]
