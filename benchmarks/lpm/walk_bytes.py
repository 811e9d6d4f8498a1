"""Bytes the LPM walk has to read, from the trie's node layout alone.

The ipcache is compiled into a stride-8 trie, one for each family: a node
is 256 entries, one for each value of the address's next byte, and an
entry is three int32 (child node, identity index of a prefix that ends
here, that prefix's slot and length): 12 bytes. A lookup reads one entry
a level, each chosen by the entry before it: 4 dependent reads for a v4
address (bytes 12..15 of the v4-mapped form), 16 for a v6 one. Nothing
else of the walk touches memory; the node, the best match and its
provenance stay in registers.

This counts what the mechanism needs, not what the program does: a
program that walks both tries for every row and selects by family (the
default; ``v4_only`` elides the v6 chain) reads more, and the share of the
roofline is the lower for it.

This file imports nothing of the program: it is the yardstick's count,
kept beside the benchmark so that a PR which changes the node layout is
seen to change the share. ``tests/test_lpm100k_config.py`` holds it equal
to the shapes ``cilium_tpu.compile.lpm`` builds.
"""

ENTRY_WORDS = 3
WORD_BYTES = 4
STRIDE_BITS = 8
V4_LEVELS = 32 // STRIDE_BITS
V6_LEVELS = 128 // STRIDE_BITS


def walk_bytes(rows_v4: int, rows_v6: int = 0) -> int:
    """Bytes the walks of ``rows_v4`` v4 and ``rows_v6`` v6 addresses have
    to read: one 12-byte entry a level a row."""
    return (rows_v4 * V4_LEVELS + rows_v6 * V6_LEVELS) \
        * ENTRY_WORDS * WORD_BYTES


def node_bytes() -> int:
    """Bytes of one trie node: 256 entries."""
    return (1 << STRIDE_BITS) * ENTRY_WORDS * WORD_BYTES
