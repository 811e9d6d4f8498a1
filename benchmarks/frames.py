"""Flows → Ethernet frames (for the rx ring) and → column batches (for
``Engine.submit``), in bulk with numpy.

A set of flows is a dict of equal-length arrays:

    src     [n, 4] uint32   source address, four big-endian words
                            (IPv4 as ::ffff:a.b.c.d)
    sport   [n] int32
    dport   [n] int32
    proto   [n] int32       6 TCP, 17 UDP
    is_v6   [n] bool

and, where the world states them, any of:

    egress       [n] bool        the flow leaves the local endpoint
    payload      [n, k] uint8    bytes after the TCP header, and
    payload_len  [n] int32       how many of the k each flow carries
    http_method  [n] int32       what the shim's request-line tokenizer
    http_path    [n, 64] uint8   makes of that payload (columns only)

``src`` is the **peer's** address. The one local endpoint's two addresses
(v4 and v6) come from the world. A flow is ingress to it, peer → endpoint,
unless ``egress`` says it leaves: then the frame carries the endpoint's
address as its source and the peer's as its destination, which is how the
shim tells the direction (``flowshim.cc``: source match → egress). ``sport``
and ``dport`` are the frame's own either way.

Without ``payload`` TCP segments carry ACK and nothing else, UDP datagrams
nothing: the smallest frames, where per-packet cost is all there is, in a
table of ``FRAME_STRIDE`` bytes a row. With it a row is as long as the
payload column's width needs.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

PROTO_TCP = 6
PROTO_UDP = 17
TCP_ACK = 0x10
DIR_EGRESS = 0                  # cilium_tpu/utils/constants.py
DIR_INGRESS = 1
FRAME_STRIDE = 80               # 74 bytes is the longest frame with no payload

Flows = Dict[str, np.ndarray]

_ETH = bytes.fromhex("020000000001" "020000000002")


def v4_words(addr: np.ndarray) -> np.ndarray:
    """[n] uint32 IPv4 addresses → [n, 4] v4-mapped words."""
    w = np.zeros((addr.shape[0], 4), dtype=np.uint32)
    w[:, 2] = 0xFFFF
    w[:, 3] = addr
    return w


def concat(parts) -> Flows:
    parts = list(parts)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def take(flows: Flows, idx) -> Flows:
    return {k: v[idx] for k, v in flows.items()}


def _be(words: np.ndarray, n: int) -> np.ndarray:
    """[n, w] uint32 → [n, 4w] bytes, big-endian."""
    return np.ascontiguousarray(words.astype(">u4")).view(np.uint8) \
        .reshape(n, -1)


def _addresses(flows: Flows, ep_v4: int, ep_v6_words) -> Tuple[np.ndarray,
                                                               np.ndarray]:
    """→ (source, destination) [n, 4] words of each flow's frame: peer →
    endpoint, or endpoint → peer where the flow set says ``egress``."""
    n = flows["sport"].shape[0]
    peer = flows["src"]
    ep = np.where(flows["is_v6"].astype(bool)[:, None],
                  np.asarray(ep_v6_words, np.uint32)[None, :],
                  v4_words(np.full((n,), ep_v4, np.uint32)))
    if "egress" not in flows:
        return peer, ep
    out = flows["egress"].astype(bool)[:, None]
    return np.where(out, ep, peer), np.where(out, peer, ep)


def _payload_len(flows: Flows) -> np.ndarray:
    """[n] int64 payload bytes of each frame (TCP segments only)."""
    n = flows["sport"].shape[0]
    if "payload" not in flows:
        return np.zeros((n,), np.int64)
    plen = flows["payload_len"].astype(np.int64)
    if ((plen < 0) | (plen > flows["payload"].shape[1])).any():
        raise ValueError("payload_len outside the payload column")
    if (plen[flows["proto"] != PROTO_TCP] != 0).any():
        raise ValueError("only a TCP segment carries a payload here")
    return plen


def frames_of(flows: Flows, ep_v4: int, ep_v6_words) -> Tuple[np.ndarray,
                                                               np.ndarray]:
    """→ (table [n, stride] uint8, length [n] uint16): Ethernet II, IPv4
    (20-byte header) or IPv6 (40), then TCP (20, and the payload where the
    flow set has one) or UDP (8). The stride is ``FRAME_STRIDE``, or the
    next multiple of 16 that holds the payload column's width."""
    n = flows["sport"].shape[0]
    v6 = flows["is_v6"].astype(bool)
    udp = flows["proto"] == PROTO_UDP
    src, dst = _addresses(flows, ep_v4, ep_v6_words)
    plen = _payload_len(flows)
    width = flows["payload"].shape[1] if "payload" in flows else 0
    stride = max(FRAME_STRIDE, -(-(74 + width) // 16) * 16)
    tab = np.zeros((n, stride), dtype=np.uint8)
    tab[:, :12] = np.frombuffer(_ETH, dtype=np.uint8)
    l4_len = np.where(udp, 8, 20).astype(np.int64) + plen
    l3_len = np.where(v6, 40, 20).astype(np.int64)
    length = (14 + l3_len + l4_len).astype(np.uint16)
    sp = flows["sport"].astype(">u2").view(np.uint8).reshape(n, 2)
    dp = flows["dport"].astype(">u2").view(np.uint8).reshape(n, 2)

    i4 = np.nonzero(~v6)[0]
    if i4.size:
        m = i4.size
        tab[i4, 12:14] = (0x08, 0x00)
        tab[i4, 14] = 0x45
        tab[i4, 16:18] = (20 + l4_len[i4]).astype(">u2").view(np.uint8) \
            .reshape(m, 2)
        tab[i4, 22] = 64                                   # ttl
        tab[i4, 23] = flows["proto"][i4]
        tab[i4, 26:30] = _be(src[i4, 3:4], m)
        tab[i4, 30:34] = _be(dst[i4, 3:4], m)
    i6 = np.nonzero(v6)[0]
    if i6.size:
        m = i6.size
        tab[i6, 12:14] = (0x86, 0xDD)
        tab[i6, 14] = 0x60
        tab[i6, 18:20] = l4_len[i6].astype(">u2").view(np.uint8) \
            .reshape(m, 2)
        tab[i6, 20] = flows["proto"][i6]                   # next header
        tab[i6, 21] = 64                                   # hop limit
        tab[i6, 22:38] = _be(src[i6], m)
        tab[i6, 38:54] = _be(dst[i6], m)
    l4 = 14 + l3_len
    rows = np.arange(n)
    for j in range(2):
        tab[rows, l4 + j] = sp[:, j]
        tab[rows, l4 + 2 + j] = dp[:, j]
    t = np.nonzero(~udp)[0]
    tab[t, l4[t] + 12] = 5 << 4                            # data offset
    tab[t, l4[t] + 13] = TCP_ACK
    tab[t, l4[t] + 14] = 0xFF                              # window
    tab[t, l4[t] + 15] = 0xFF
    u = np.nonzero(udp)[0]
    tab[u, l4[u] + 5] = 8                                  # udp length
    if width:
        keep = np.arange(width)[None, :] < plen[:, None]
        for family, at in ((~v6, 54), (v6, 74)):           # past the TCP header
            r = np.nonzero(family & (plen > 0))[0]
            tab[r, at:at + width] = np.where(keep[r], flows["payload"][r], 0)
    return tab, length


def columns_of(flows: Flows, ep_v4: int, ep_v6_words,
               ep_slot: int) -> Dict[str, np.ndarray]:
    """→ the records-layout column batch ``Engine.submit`` takes (what the
    shim would parse the frames above into)."""
    from cilium_tpu.kernels.records import empty_batch
    n = flows["sport"].shape[0]
    b = empty_batch(n)
    b["src"][:], b["dst"][:] = _addresses(flows, ep_v4, ep_v6_words)
    b["sport"][:] = flows["sport"]
    b["dport"][:] = flows["dport"]
    b["proto"][:] = flows["proto"]
    b["tcp_flags"][:] = np.where(flows["proto"] == PROTO_TCP, TCP_ACK, 0)
    b["is_v6"][:] = flows["is_v6"].astype(bool)
    b["direction"][:] = np.where(flows["egress"], DIR_EGRESS, DIR_INGRESS) \
        if "egress" in flows else DIR_INGRESS
    for k in ("http_method", "http_path"):
        if k in flows:
            b[k][:] = flows[k]
    b["ep_slot"][:] = ep_slot
    b["valid"][:] = True
    return b
