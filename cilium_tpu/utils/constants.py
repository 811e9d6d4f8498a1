"""Shared constants: reserved identities, protocols, verdicts, drop reasons, CT.

Numbering follows upstream Cilium's public, documented values where those are
well-known (reserved identities, local-identity scope bit). Drop-reason numbers
are this framework's own enum — the *names* mirror upstream's
``bpf/lib/drop.h`` reason names, but the reference mount was empty (SURVEY.md
§0) so no numeric values are claimed as read-from-source.
"""

from __future__ import annotations

import enum

# --------------------------------------------------------------------------- #
# Reserved security identities (upstream: pkg/identity/reserved, numericidentity)
# --------------------------------------------------------------------------- #
IDENTITY_UNKNOWN = 0
IDENTITY_HOST = 1
IDENTITY_WORLD = 2
IDENTITY_UNMANAGED = 3
IDENTITY_HEALTH = 4
IDENTITY_INIT = 5
IDENTITY_REMOTE_NODE = 6
IDENTITY_KUBE_APISERVER = 7
IDENTITY_INGRESS = 8

RESERVED_IDENTITIES = {
    "unknown": IDENTITY_UNKNOWN,
    "host": IDENTITY_HOST,
    "world": IDENTITY_WORLD,
    "unmanaged": IDENTITY_UNMANAGED,
    "health": IDENTITY_HEALTH,
    "init": IDENTITY_INIT,
    "remote-node": IDENTITY_REMOTE_NODE,
    "kube-apiserver": IDENTITY_KUBE_APISERVER,
    "ingress": IDENTITY_INGRESS,
}
RESERVED_IDENTITY_NAMES = {v: k for k, v in RESERVED_IDENTITIES.items()}

# First identity id available for cluster-scope (label-derived) identities.
CLUSTER_IDENTITY_BASE = 256
# Cluster-scope identities fit in 16 bits upstream.
CLUSTER_IDENTITY_MAX = 65535

# Node-local identities (CIDR-derived) carry the local scope bit
# (upstream: identity.IdentityScopeLocal == 1 << 24).
LOCAL_IDENTITY_SCOPE = 1 << 24

# Wildcard identity in MapState / policymap keys (matches any remote identity).
IDENTITY_ANY = 0

# --------------------------------------------------------------------------- #
# Protocols
# --------------------------------------------------------------------------- #
PROTO_ANY = 0
PROTO_ICMP = 1
PROTO_TCP = 6
PROTO_UDP = 17
PROTO_ICMP6 = 58
PROTO_SCTP = 132

PROTO_NAMES = {
    PROTO_ANY: "ANY",
    PROTO_ICMP: "ICMP",
    PROTO_TCP: "TCP",
    PROTO_UDP: "UDP",
    PROTO_ICMP6: "ICMPv6",
    PROTO_SCTP: "SCTP",
}
PROTO_BY_NAME = {v: k for k, v in PROTO_NAMES.items()}

# Protocols that carry L4 ports.
PORT_PROTOS = (PROTO_TCP, PROTO_UDP, PROTO_SCTP)

# Dense "proto family" index used by the compiled tensors: ports only make
# sense for TCP/UDP/SCTP; ICMP type is carried in the port field (upstream CT
# does the same trick with ICMP type/code in the port slots). ICMP and ICMPv6
# are distinct families so their entries never shadow each other's cells.
PROTO_FAMILY_TCP = 0
PROTO_FAMILY_UDP = 1
PROTO_FAMILY_SCTP = 2
PROTO_FAMILY_ICMP = 3
PROTO_FAMILY_ICMP6 = 4
PROTO_FAMILY_OTHER = 5
N_PROTO_FAMILIES = 6


def proto_family(proto: int) -> int:
    if proto == PROTO_TCP:
        return PROTO_FAMILY_TCP
    if proto == PROTO_UDP:
        return PROTO_FAMILY_UDP
    if proto == PROTO_SCTP:
        return PROTO_FAMILY_SCTP
    if proto == PROTO_ICMP:
        return PROTO_FAMILY_ICMP
    if proto == PROTO_ICMP6:
        return PROTO_FAMILY_ICMP6
    return PROTO_FAMILY_OTHER


# --------------------------------------------------------------------------- #
# Directions (relative to the local endpoint, as in per-endpoint policymaps)
# --------------------------------------------------------------------------- #
DIR_EGRESS = 0   # traffic leaving the endpoint
DIR_INGRESS = 1  # traffic entering the endpoint
N_DIRECTIONS = 2

DIR_NAMES = {DIR_EGRESS: "egress", DIR_INGRESS: "ingress"}

# --------------------------------------------------------------------------- #
# Verdict codes (dense tensor cell values; low 2 bits = decision)
# --------------------------------------------------------------------------- #
VERDICT_MISS = 0       # no matching entry: default-deny if enforced else allow
VERDICT_ALLOW = 1
VERDICT_DENY = 2
VERDICT_REDIRECT = 3   # L7 redirect; upper bits carry the L7 ruleset id

VERDICT_DECISION_MASK = 0x3
VERDICT_L7_SHIFT = 2   # l7 ruleset id stored in bits [2..15] of the uint16 cell


def verdict_cell(decision: int, l7_id: int = 0) -> int:
    return (l7_id << VERDICT_L7_SHIFT) | decision


# --------------------------------------------------------------------------- #
# Final per-packet forward decision + drop reasons.
# Names mirror upstream bpf/lib/drop.h; numbers are ours (see module docstring).
# --------------------------------------------------------------------------- #
class DropReason(enum.IntEnum):
    OK = 0                    # forwarded
    POLICY = 130              # default deny: enforced direction, no matching rule
    POLICY_DENY = 133         # explicit deny rule matched
    POLICY_L7 = 180           # L7-lite rules matched none of the request tokens
    CT_INVALID = 134          # malformed / untrackable (e.g. bad header record)
    INVALID_IDENTITY = 135    # ipcache produced no usable identity
    UNSUPPORTED_PROTO = 136
    CT_FULL = 137             # new flow: CT probe window saturated with
    #                           unevictable entries (adversarial-load fail
    #                           closed; upstream analog: CT map insert failed)
    NO_SERVICE = 140          # dst matched a service frontend with no backends


# Geometry of the per-batch verdict counters tensor (kernels/classify.py
# accumulates drops by reason x direction in-kernel; runtime/metrics.py
# aggregates the same shape on the host). Reason ids are an 8-bit field.
DROP_REASON_BINS = 256
COUNTER_CELLS = DROP_REASON_BINS * N_DIRECTIONS
# ... and of the LPM walk's rows-by-matched-length counter: a bin for each
# prefix length 0..128, then the miss bin (no prefix held the address: the
# world fallback)
LPM_PLEN_BINS = 130
LPM_MISS_BIN = LPM_PLEN_BINS - 1

if int(max(DropReason)) >= DROP_REASON_BINS:
    raise AssertionError(
        "DropReason value exceeds DROP_REASON_BINS — widen the counter "
        "tensor geometry before adding reasons past the 8-bit field")


# --------------------------------------------------------------------------- #
# Conntrack (upstream: bpf/lib/conntrack.h, pkg/maps/ctmap)
# --------------------------------------------------------------------------- #
class CTStatus(enum.IntEnum):
    NEW = 0
    ESTABLISHED = 1
    REPLY = 2
    # RELATED (ICMP errors referencing an inner tuple) is deliberately not
    # implemented in v1; ICMP echo is tracked as its own flow instead.


# Lifetimes in seconds (upstream defaults: CT_SYN_TIMEOUT 60s,
# CT_ESTABLISHED_LIFETIME_TCP 21600s, nonTCP 60s, CT_CLOSE_TIMEOUT 10s).
CT_LIFETIME_SYN = 60
CT_LIFETIME_TCP = 21600
CT_LIFETIME_NONTCP = 60
CT_LIFETIME_CLOSE = 10

# CT entry flag bits.
CT_FLAG_SEEN_NON_SYN = 1 << 0
CT_FLAG_TX_CLOSING = 1 << 1
CT_FLAG_RX_CLOSING = 1 << 2

# TCP header flag bits (standard wire format, low byte).
TCP_FIN = 0x01
TCP_SYN = 0x02
TCP_RST = 0x04
TCP_PSH = 0x08
TCP_ACK = 0x10

# --------------------------------------------------------------------------- #
# Policy enforcement modes (upstream: option.Config.EnablePolicy —
# "default" | "always" | "never"; these change verdicts, so they are part of
# the parity contract)
# --------------------------------------------------------------------------- #
ENFORCEMENT_DEFAULT = "default"
ENFORCEMENT_ALWAYS = "always"
ENFORCEMENT_NEVER = "never"
ENFORCEMENT_MODES = (ENFORCEMENT_DEFAULT, ENFORCEMENT_ALWAYS, ENFORCEMENT_NEVER)

# --------------------------------------------------------------------------- #
# Health probing (cilium-health analog): the node's health prober sources
# probes from this link-local address, mapped to the reserved health
# identity in the ipcache at engine startup.
# --------------------------------------------------------------------------- #
HEALTH_PROBE_IP = "169.254.254.254"
ICMP_ECHO_REQUEST = 8

# Engine health states (supervised degradation — runtime/engine.health()):
# OK = serving the current compiled snapshot; DEGRADED = regeneration
# failing, serving the last-good snapshot (still semantically current);
# STALE = regeneration failing with committed policy changes pending.
HEALTH_OK = "OK"
HEALTH_DEGRADED = "DEGRADED"
HEALTH_STALE = "STALE"
HEALTH_STATES = (HEALTH_OK, HEALTH_DEGRADED, HEALTH_STALE)
# health() detail key: a registered bounded structure is past its warn
# pressure fraction (observe/pressure.py resource ledger, ISSUE 13)
RESOURCE_PRESSURE = "RESOURCE_PRESSURE"
# Clustermesh staleness detail (runtime/clustermesh.status()): the store
# has been unreachable past the staleness budget — remote state still
# serves last-good (never fail closed on established remote flows), but
# the view may be behind the mesh; folds Engine.health() to DEGRADED.
MESH_STALE = "MESH_STALE"
# CT-archive staleness detail (ISSUE 19): the ct-snapshot controller's
# newest archive is older than checkpoint_max_age_s — the salvage floor a
# device-loss re-mesh would fall back to no longer reflects recent flows;
# folds Engine.health() to DEGRADED until a snapshot lands.
CHECKPOINT_STALE = "CHECKPOINT_STALE"
# Device-loss detail (ISSUE 19): an accelerator in the configured mesh is
# latched dead (runtime/datapath.device_health) — serving continues on the
# survivor mesh, but the cluster is one fault from losing redundancy.
DEVICE_LOST = "DEVICE_LOST"

# --------------------------------------------------------------------------- #
# L7-lite (config 4): tokenized HTTP method/path-prefix matching
# --------------------------------------------------------------------------- #
HTTP_METHODS = (
    "GET", "POST", "PUT", "DELETE", "HEAD", "OPTIONS", "PATCH", "TRACE", "CONNECT",
)
HTTP_METHOD_IDS = {m: i for i, m in enumerate(HTTP_METHODS)}
HTTP_METHOD_ANY = 255
L7_PATH_MAXLEN = 64
