"""Bytes the LB step has to read, from the tables' row layouts alone.

A row of a batch looks its destination up in the frontend table, an
open-addressed hash table probed over a fixed window: ``PROBE_DEPTH`` slots,
each a key of six words (address, port, protocol) and a value of one (the
frontend's index). A hit reads the frontend's service (its Maglev row) and
its rev-NAT id, a word each; then one entry of that row, chosen by the
flow's hash; then the backend's address (four words) and port (one). Nothing
else of the step touches memory: the hashes and the selects stay in
registers. A row that hits no frontend reads the window alone; this counts
the whole chain for every row, which is what a row of a cell whose flows go
through a frontend nine times in ten has to read.

This counts what the mechanism needs, not what the program does (a gather
of one word from a tiled table moves more than a word). The tables' own
sizes follow from the configuration's counts (``table_bytes``): the Maglev
rows are all of it, which is why a batch's 1,024 four-byte reads out of
655 MB are bound by the latency of a read and not by bandwidth.

This file imports nothing of the program: it is the yardstick's count, kept
beside the benchmark so that a PR which changes a layout is seen to change
the share. ``tests/test_svc10k_config.py`` holds it equal to the shapes
``cilium_tpu.compile.lb`` builds.
"""

WORD_BYTES = 4
PROBE_DEPTH = 8
KEY_WORDS = 6               # address (4), port, protocol
VALUE_WORDS = 1             # the frontend's index
FRONTEND_WORDS = 2          # its service (Maglev row) and its rev-NAT id
MAGLEV_WORDS = 1            # one entry: a backend's index
BACKEND_WORDS = 5           # address (4) and port


def row_bytes() -> int:
    """Bytes one row's step has to read."""
    return (PROBE_DEPTH * (KEY_WORDS + VALUE_WORDS) + FRONTEND_WORDS
            + MAGLEV_WORDS + BACKEND_WORDS) * WORD_BYTES


def step_bytes(rows: float) -> float:
    """Bytes the steps of ``rows`` rows have to read."""
    return rows * row_bytes()


def table_bytes(n_services: int, maglev_m: int, n_frontends: int,
                n_backends: int) -> dict:
    """Bytes of each table the step reads, at the deployment's counts. The
    frontend table has the first power of two of slots that is at least
    twice the frontends (it doubles again where a probe window overflows)."""
    slots = 8
    while slots < 2 * max(n_frontends, 1):
        slots *= 2
    return {
        "maglev": n_services * maglev_m * MAGLEV_WORDS * WORD_BYTES,
        "frontend_table": slots * (KEY_WORDS + VALUE_WORDS) * WORD_BYTES,
        "frontends": n_frontends * FRONTEND_WORDS * WORD_BYTES,
        "backends": n_backends * BACKEND_WORDS * WORD_BYTES,
    }
