"""IP address / prefix helpers.

All addresses are normalized to 16 bytes: IPv6 verbatim, IPv4 as the
v4-mapped form ``::ffff:a.b.c.d``. This lets a single 16-level stride-8 LPM
trie serve both families (SURVEY.md §5 "long-context" analog: LPM over 100k
prefixes as multi-level stride tables), with a precomputed 4-level fast path
for pure-IPv4 batches.
"""

from __future__ import annotations

import ipaddress
import socket
from typing import Optional, Tuple

V4_MAPPED_PREFIX = b"\x00" * 10 + b"\xff\xff"


def _plain_prefix(text: str) -> Optional[Tuple[bytes, int, bool]]:
    """``a.b.c.d/p`` or ``x:y::/p`` in the plain form a routing table is
    written in → (packed network address, host bits cleared; prefix length;
    is_ipv6), through the C library's parser: a tenth of what
    ``ipaddress`` costs, which a table of a million prefixes pays a
    million times. None for every other form (no length, a netmask, a zone,
    an address the parser refuses, an IPv6 address whose first group is 0,
    which has forms ``ipaddress`` writes its own way): the caller then asks
    ``ipaddress``, whose answers and errors stay what they were."""
    addr, sep, plen = text.partition("/")
    if not sep or not plen.isascii() or not plen.isdigit():
        return None
    is_v6 = ":" in addr
    try:
        packed = socket.inet_pton(
            socket.AF_INET6 if is_v6 else socket.AF_INET, addr)
    except (OSError, ValueError):
        return None
    bits, plen = len(packed) * 8, int(plen)
    if plen > bits or (is_v6 and packed[:2] == b"\x00\x00"):
        return None
    host = bits - plen
    net = (int.from_bytes(packed, "big") >> host) << host
    return net.to_bytes(bits // 8, "big"), plen, is_v6


def parse_addr(text: str) -> Tuple[bytes, bool]:
    """Parse an address string → (16-byte normalized form, is_ipv6)."""
    addr = ipaddress.ip_address(text)
    if addr.version == 4:
        return V4_MAPPED_PREFIX + addr.packed, False
    return addr.packed, True


def parse_prefix(text: str) -> Tuple[bytes, int, bool]:
    """Parse a CIDR string → (16-byte normalized network address, normalized
    prefix length in the 128-bit space, is_ipv6).

    IPv4 ``/p`` becomes ``/(96+p)`` in the v4-mapped space.
    """
    plain = _plain_prefix(text)
    if plain is not None:
        packed, plen, is_v6 = plain
        return (packed, plen, True) if is_v6 \
            else (V4_MAPPED_PREFIX + packed, 96 + plen, False)
    net = ipaddress.ip_network(text, strict=False)
    if net.version == 4:
        return V4_MAPPED_PREFIX + net.network_address.packed, 96 + net.prefixlen, False
    return net.network_address.packed, net.prefixlen, True


def normalize_prefix(text: str) -> str:
    """Canonical string form of a CIDR (host bits cleared)."""
    plain = _plain_prefix(text)
    if plain is not None:
        packed, plen, is_v6 = plain
        return socket.inet_ntop(
            socket.AF_INET6 if is_v6 else socket.AF_INET, packed) \
            + f"/{plen}"
    return str(ipaddress.ip_network(text, strict=False))


def addr_to_words(addr16: bytes) -> Tuple[int, int, int, int]:
    """16-byte address → four big-endian uint32 words (device layout)."""
    return (
        int.from_bytes(addr16[0:4], "big"),
        int.from_bytes(addr16[4:8], "big"),
        int.from_bytes(addr16[8:12], "big"),
        int.from_bytes(addr16[12:16], "big"),
    )


def words_to_addr(words) -> bytes:
    return b"".join(int(w).to_bytes(4, "big") for w in words)


def addr_to_str(addr16: bytes) -> str:
    """Render a normalized 16-byte address, un-mapping v4."""
    if addr16[:12] == V4_MAPPED_PREFIX:
        return str(ipaddress.IPv4Address(addr16[12:]))
    return str(ipaddress.IPv6Address(addr16))


def is_v4_mapped(addr16: bytes) -> bool:
    return addr16[:12] == V4_MAPPED_PREFIX
