"""Device time under the program's ``l7.unpack`` and ``l7.match`` scopes in
a run's profiler trace: what ``kernels.l7_us_per_batch`` and
``kernels.l7_hbm_share`` read.

The program opens ``l7.unpack`` around the path dictionary's unpacking on
the device (``kernels/records.py:_unpack_dict_paths_jnp``: one gather of a
row's packed words from the batch's dictionary and their cut into 64
bytes) and ``l7.match`` around the match of every row's request against
its cell's rule set (``kernels/classify.py:interior_pre_core``), in a
program whose snapshot holds an L7 set. How a device event is tied to a
scope is ``benchmarks/lpm/trace.py``'s rule, which takes any pair of scope
names: an event stands under the scopes its instruction, or any
instruction of the computations it calls, names; an instruction the
compiler put in without a name stands under its users'. One scope → that
kernel's seconds, both → ``mixed`` (a fusion that unpacks and matches),
none → ``unnamed``. The L7 lane's device time is the three named kinds
together.

**One exception to that rule, found in the compiler's text of this cell's
program** (the 1,024-row ``l7-http`` program compiled for a described v5e,
PERF.md PR 37): a name that ends in ``broadcast_in_dim`` names no scope.
The match ends in ``jnp.where(set_id <= 0, True, any_rule)``, whose
``True`` is a broadcast of a constant to ``pred[1024]`` under ``l7.match``.
XLA merges equal broadcasts of constants across the whole program and
keeps one's name: conntrack's ``.at[...].set(True)`` updates are the same
``pred[1024]`` of ``True``, so the twelve ``pred[65536]`` scatter fusions
of its claim rounds each hold a reshape and a transpose of that one
broadcast under the name ``jit(fn)/l7.match/jit(_where)/
broadcast_in_dim``, and the rule as it stands would count 0.4 ms of
conntrack a batch as the match's. A broadcast moves no data of its own,
and a fusion that does the lane's work holds other instructions under the
scope (the gathers, ``eq``, ``or``, the reductions), so nothing of the
lane is lost by it. ``lpm/trace.py`` is not edited: the traced programs
are handed to it with those names blanked (``-``: named, under no scope).

No scope named in any traced program (a program before PR 37, a
deployment without an L7 set on a wire without tokens) → None, never 0.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from benchmarks.lpm.trace import fields, read_trace, seconds_by_scope
from benchmarks.mesh.trace import BATCH_SPAN, trace_file
from benchmarks.reduce import xplane

SCOPE_UNPACK = "l7.unpack"
SCOPE_MATCH = "l7.match"
SCOPES = (SCOPE_UNPACK, SCOPE_MATCH)
KEPT = "l7_scoped"
#: an ``op_name`` that ends so says nothing of whose work a fusion does
MERGED_CONSTANT = "/broadcast_in_dim"
NO_SCOPE = b"-"


# -- the wire format, written --------------------------------------------------
def _put_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, b = n >> 7, n & 0x7F
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _put(number: int, wire: int, value) -> bytes:
    key = _put_varint((number << 3) | wire)
    if wire == 0:
        return key + _put_varint(value)
    if wire == 2:
        return key + _put_varint(len(value)) + bytes(value)
    return key + value.to_bytes(8 if wire == 1 else 4, "little")


def _rebuilt(buf, path: Tuple[int, ...], leaf: Callable) -> bytes:
    """``buf`` again, field for field, with the sub-messages along ``path``
    (field numbers, outermost first) rebuilt and the innermost handed to
    ``leaf`` (bytes → bytes)."""
    out = bytearray()
    for number, wire, value in fields(buf):
        if wire == 2 and number == path[0]:
            value = leaf(value) if len(path) == 1 \
                else _rebuilt(value, path[1:], leaf)
        out += _put(number, wire, value)
    return bytes(out)


def _instruction(inst) -> bytes:
    """An ``HloInstructionProto`` (metadata 7, its op_name 2) as it stands,
    or with its name blanked where that ends in ``broadcast_in_dim``."""
    for number, wire, value in fields(inst):
        if number == 7 and wire == 2:
            op_name = next((bytes(v) for n, _w, v in fields(value)
                            if n == 2), b"")
            if op_name.decode("utf-8", "replace").endswith(MERGED_CONSTANT):
                return _rebuilt(inst, (7,),
                                lambda _m: _put(2, 2, NO_SCOPE))
    return bytes(inst)


def without_merged_constants(hlo_proto) -> bytes:
    """An ``HloProto`` (hlo_module 1, computations 3, instructions 2) with
    the names of broadcasts blanked: the exception at the head of this
    file."""
    return _rebuilt(hlo_proto, (1, 3, 2), _instruction)


def scoped(run) -> Optional[Dict]:
    """→ {"batches": batches dispatched in the traced interval, "chips",
    "unpack_s", "match_s", "mixed_s", "unnamed_s": seconds a chip (mean
    over them), "l7_s": the three named kinds together} for this run, read
    once and kept on ``run.info``; None where there is nothing to read: no
    trace, or no program in it that names either scope."""
    if KEPT in run.info:
        return run.info[KEPT]
    out = None
    path = trace_file(run) if run.trace is not None else None
    if path is not None:
        marks = xplane.read_planes(path)["marks"]
        trace = read_trace(path)
        trace["programs"] = {pid: without_merged_constants(proto)
                             for pid, proto in trace["programs"].items()}
        by = seconds_by_scope(trace, marks[xplane.MARK_START][0],
                              marks[xplane.MARK_END][0], scopes=SCOPES)
        m0, m1 = run.trace["window_mono_s"]
        batches = sum(1 for name, t0, _d in run.spans
                      if name == BATCH_SPAN and m0 <= t0 < m1)
        if by and batches:
            chips = list(by["chips"].values())
            out = {"batches": batches, "chips": len(chips)}
            for key, kind in (("unpack_s", "first"), ("match_s", "second"),
                              ("mixed_s", "mixed"), ("unnamed_s", "unnamed")):
                out[key] = sum(c[kind] for c in chips) / len(chips)
            out["l7_s"] = out["unpack_s"] + out["match_s"] + out["mixed_s"]
    run.info[KEPT] = out
    return out
