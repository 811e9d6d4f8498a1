"""Bytes of the device-RSS conntrack exchange, from its row layouts alone.

A batch of ``rows`` rows on an ``n``-chip ring is ``L = rows / n`` rows a
chip. Each chip packs its rows' conntrack requests into ``[L, 13]`` uint32
(ten key words, tcp flags, three meta bits, the rev-NAT id) and the ring
gathers them in ``n - 1`` hops, every chip sending one ``[L, 13]`` buffer
to its neighbour in each. The owners' replies, ``[n, L, 2]`` uint32 on
each chip (state bits, rev-NAT id), go home in ``n - 1`` more hops of one
``[L, 2]`` chunk a chip. So a batch costs a chip ``2 (n - 1)`` sends.

This file imports nothing of the program: it is the yardstick's count of
what the mechanism has to move, kept beside the benchmark so that a PR
which changes the program's layouts is seen to change the share.
``tests/test_mesh4_config.py`` holds it equal to
``cilium_tpu.parallel.exchange.exchange_bytes``.
"""

REQUEST_WORDS = 13
REPLY_WORDS = 2
WORD_BYTES = 4


def hop_bytes(rows: int, n_chips: int):
    """→ (request, reply) bytes one chip sends in one hop of each phase."""
    per_chip = rows // n_chips
    return (per_chip * REQUEST_WORDS * WORD_BYTES,
            per_chip * REPLY_WORDS * WORD_BYTES)


def sent_bytes_per_chip(rows: int, n_chips: int) -> int:
    """Bytes one chip puts on the interconnect for one ``rows``-row batch:
    ``n - 1`` request hops and ``n - 1`` reply hops."""
    request, reply = hop_bytes(rows, n_chips)
    return (n_chips - 1) * (request + reply)


def materialized_bytes(rows: int, n_chips: int) -> int:
    """Bytes the exchange holds over the whole mesh for one batch: on each
    of the ``n`` chips the gathered requests ``[n, L, 13]`` and the reply
    chunks ``[n, L, 2]``. The program's own ledger counts this number."""
    return n_chips * rows * (REQUEST_WORDS + REPLY_WORDS) * WORD_BYTES
