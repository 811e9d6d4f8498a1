"""Device time under the program's ``lpm.walk`` and ``lb.step`` scopes in a
run's profiler trace: what the ``kernels.lpm_*`` and ``kernels.lb_*``
readers read.

How an event is tied to a scope (looked at by hand in a TPU v5e trace of
``lpm100k-zipf.saturate-longflows``, PERF.md PR 34). ``jax.named_scope``
puts its name into the ``op_name`` of every HLO instruction traced under
it (``jit(fn)/jit(main)/lpm.walk/gather``). An event on a chip's ``XLA
Ops`` line is one executed instruction of the optimized program; its name
is the instruction's whole HLO line (``%fusion.61 = s32[1024]… fusion(…),
calls=%fused_computation.61``) and its metadata names the program
(``program_id``) but carries ``op_name`` (as ``tf_op``) of the fusion's
root alone. A fusion that XLA built from instructions of both scopes, or
of a scope and of none, says so only in its body. The bodies are in the
trace too: the plane ``/host:metadata`` holds every traced program's
``HloProto`` (stat ``Hlo Proto``, keyed by ``program_id``), instruction
metadata kept. So an event's scopes are those named in the ``op_name`` of
its instruction **or of any instruction of the computations it calls**,
transitively (a fusion's body, a loop's body and condition). An event with
one of the two scopes counts under it, whatever else its fusion holds; one
with both is ``mixed``; one with neither is ``unnamed`` (conntrack, the
policy ladder, the slab's packing).

One case more, and the largest operation of the cell is it: an instruction
that carries **no** ``op_name`` is the compiler's own, put in to change a
layout (``%copy.855 = s32[1,3,39667,256]{3,1,2,0} copy(%bitcast.158)``, the
whole 122 MB trie re-laid before ``jit(fn)/lpm.walk/reshape`` reads it). It
stands under the scopes of the instructions that use its result, followed
through further instructions without a name: the work is done for them.

``jax.profiler.ProfileData`` gives an event's name, start and duration
but neither its metadata's statistics nor its metadata id, so this file
reads the trace's protocol buffer itself: a forty-line walker over the
wire format (varints and length-delimited fields; field numbers from
``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``),
which needs nothing but Python. ``benchmarks/tests`` holds it equal to
``ProfileData`` on recorded traces.

Seconds are sums of event durations, cut to the traced interval, a chip;
an instruction that runs inside a loop is an event of its own beside the
loop's, so the four sums can read over the busy union that
``reduce/xplane.py`` reports (PERF.md says which operations do).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Set, Tuple

from benchmarks.mesh.trace import BATCH_SPAN, trace_file
from benchmarks.reduce import xplane

SCOPE_LPM = "lpm.walk"
SCOPE_LB = "lb.step"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PROGRAM_STAT = "program_id"
U64 = (1 << 64) - 1


# -- the wire format ---------------------------------------------------------
def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, at
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited one
    (a string, bytes, a sub-message or a packed list)."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[at:at + size], "little")
            at += size
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _ints(wire: int, value) -> List[int]:
    """A repeated integer field's values: one, or a packed run of them."""
    if wire == 0:
        return [value]
    out, at = [], 0
    while at < len(value):
        v, at = _varint(value, at)
        out.append(v)
    return out


# -- the trace ------------------------------------------------------------------
def _stat_names(plane) -> Dict[int, str]:
    out = {}
    for number, _w, entry in fields(plane):
        if number == 5:                           # stat_metadata: map entry
            for n2, _w2, meta in fields(entry):
                if n2 == 2:
                    f = {n3: v3 for n3, _w3, v3 in fields(meta)}
                    out[f.get(1, 0)] = _text(f.get(2, b""))
    return out


def _event_metadata(plane, stat_names: Dict[int, str]
                    ) -> Dict[int, Tuple[str, Dict[str, object]]]:
    """id → (name, {stat name: value}) of a plane's event metadata."""
    out = {}
    for number, _w, entry in fields(plane):
        if number != 4:
            continue
        for n2, _w2, meta in fields(entry):
            if n2 != 2:
                continue
            ident, name, stats = 0, "", {}
            for n3, _w3, v3 in fields(meta):
                if n3 == 1:
                    ident = v3
                elif n3 == 2:
                    name = _text(v3)
                elif n3 == 5:
                    key, value = None, None
                    for n4, _w4, v4 in fields(v3):
                        if n4 == 1:
                            key = stat_names.get(v4)
                        elif n4 in (3, 4, 6):     # uint64, int64, bytes
                            value = v4
                    stats[key] = value
            out[ident] = (name, stats)
    return out


def read_trace(path: str) -> Dict:
    """→ {"chips": {plane: [(metadata id, start_ns, dur_ns)] of its ``XLA
    Ops`` line}, "metadata": {plane: {id: (name, stats)}}, "programs":
    {program_id: HloProto bytes}}."""
    with open(path, "rb") as f:
        space = f.read()
    chips, metadata, programs = {}, {}, {}
    for number, _w, plane in fields(space):
        if number != 1:
            continue
        name = next((_text(v) for n, _w2, v in fields(plane) if n == 2), "")
        if name == METADATA_PLANE:
            for _id, (_n, stats) in _event_metadata(
                    plane, _stat_names(plane)).items():
                if stats.get(HLO_STAT) is not None:
                    programs[_id & U64] = stats[HLO_STAT]
        elif name.startswith(xplane.DEVICE_PLANE_PREFIX):
            metadata[name] = _event_metadata(plane, _stat_names(plane))
            chips[name] = _ops_line(plane)
    return {"chips": chips, "metadata": metadata, "programs": programs}


def _ops_line(plane) -> List[Tuple[int, float, float]]:
    for number, _w, line in fields(plane):
        if number != 3:
            continue
        head = {n: v for n, _w2, v in fields(line) if n in (2, 3)}
        if _text(head.get(2, b"")) != xplane.OPS_LINE:
            continue
        t0 = float(head.get(3, 0))
        events = []
        for n, _w2, event in fields(line):
            if n == 4:
                f = {n2: v2 for n2, _w3, v2 in fields(event) if n2 <= 3}
                events.append((f.get(1, 0), t0 + f.get(2, 0) / 1e3,
                               f.get(3, 0) / 1e3))
        return events
    return []


# -- the programs -----------------------------------------------------------------
def scopes_by_instruction(hlo_proto, scopes=(SCOPE_LPM, SCOPE_LB)
                          ) -> Dict[str, Set[str]]:
    """Instruction name → which of ``scopes`` it stands under, for every
    instruction of one ``HloProto``: those its ``op_name`` names, or that
    of any instruction of the computations it calls; for an instruction
    with no ``op_name`` at all, those of the instructions that use it."""
    module = next((v for n, _w, v in fields(hlo_proto) if n == 1), b"")
    own: Dict[int, Set[str]] = {}            # computation id → its scopes
    calls: Dict[int, Set[int]] = {}          # computation id → ones called
    insts = []       # (computation, id, name, has op_name, named, calls, uses)
    for number, _w, comp in fields(module):
        if number != 3:
            continue
        comp_id, found, called, first = 0, set(), set(), len(insts)
        for n2, _w2, v2 in fields(comp):
            if n2 == 5:
                comp_id = v2
            elif n2 == 2:
                ident, name, op_name, to, uses = 0, "", "", [], []
                for n3, w3, v3 in fields(v2):
                    if n3 == 1:
                        name = _text(v3)
                    elif n3 == 7:
                        op_name = next((_text(v4) for n4, _w4, v4
                                        in fields(v3) if n4 == 2), "")
                    elif n3 == 35:
                        ident = v3
                    elif n3 == 36:
                        uses += _ints(w3, v3)
                    elif n3 == 38:
                        to += _ints(w3, v3)
                named = {s for s in scopes if s in op_name.split("/")}
                insts.append([comp_id, ident, name, bool(op_name), named,
                              to, uses])
                found |= named
                called.update(to)
        for inst in insts[first:]:
            inst[0] = comp_id                # the id may follow the list
        own[comp_id], calls[comp_id] = found, called

    def reach(comp_id: int, seen: Set[int]) -> Set[str]:
        if comp_id in seen:
            return set()
        seen.add(comp_id)
        out = set(own.get(comp_id, ()))
        for c in calls.get(comp_id, ()):
            out |= reach(c, seen)
        return out

    out = {}
    users: Dict[Tuple[int, int], List[list]] = {}
    for inst in insts:
        comp_id, _ident, name, _has, named, to, uses = inst
        out[name] = named.union(*(reach(c, set()) for c in to))
        for operand in uses:
            users.setdefault((comp_id, operand), []).append(inst)

    def used_for(inst: list, seen: Set[str]) -> Set[str]:
        comp_id, ident, name, has_name, _named, _to, _uses = inst
        if has_name or out[name] or name in seen:
            return out[name]
        seen.add(name)
        return set().union(*(used_for(u, seen)
                             for u in users.get((comp_id, ident), ())))

    for inst in insts:
        if not inst[3] and not out[inst[2]]:
            out[inst[2]] = used_for(inst, set())
    return out


def instruction_of(event_name: str) -> str:
    """``%fusion.61 = s32[…] fusion(…)`` → ``fusion.61``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def scopes_of_events(trace: Dict, scopes=(SCOPE_LPM, SCOPE_LB)
                     ) -> Optional[Dict[str, Dict[int, frozenset]]]:
    """→ {plane: {event metadata id: the ``scopes`` its instruction stands
    under}} for every kind of event on the chips' lines; None when no
    instruction of any traced program names one of them (a program without
    the scopes, or one loaded from a compile cache written without)."""
    by_program = {pid: scopes_by_instruction(proto, scopes)
                  for pid, proto in trace["programs"].items()}
    if not any(found for m in by_program.values() for found in m.values()):
        return None
    out = {}
    for plane, meta in trace["metadata"].items():
        out[plane] = {
            ident: frozenset(by_program.get(
                (stats.get(PROGRAM_STAT) or 0) & U64, {}).get(
                    instruction_of(name), ()))
            for ident, (name, stats) in meta.items()}
    return out


def seconds_by_scope(trace: Dict, w0: float, w1: float,
                     scopes=(SCOPE_LPM, SCOPE_LB)) -> Optional[Dict]:
    """→ {"chips": {plane: {"first", "second", "mixed", "unnamed"}}, seconds
    of each chip's events inside [w0, w1] by which of the two ``scopes``
    they stand under, "named": the scopes some event of the trace stands
    under}; None when no traced program names either."""
    of = scopes_of_events(trace, scopes)
    if of is None:
        return None
    kinds = {frozenset(): "unnamed", frozenset(scopes[:1]): "first",
             frozenset(scopes[1:]): "second", frozenset(scopes): "mixed"}
    out, named = {}, set()
    for plane, events in trace["chips"].items():
        found_of = of[plane]
        tot = dict.fromkeys(kinds.values(), 0.0)
        for ident, start, dur in events:
            cut = min(start + dur, w1) - max(start, w0)
            if cut > 0:
                found = found_of.get(ident, frozenset())
                tot[kinds[found]] += cut / 1e9
                named |= found
        out[plane] = tot
    return {"chips": out, "named": named}


def scoped(run) -> Optional[Dict]:
    """→ {"batches": batches dispatched in the traced interval, "chips",
    "lpm_s", "lb_s", "mixed_s", "unnamed_s": seconds a chip (mean over them),
    "has_lb": whether any traced program names ``lb.step``} for this run,
    read once and kept on ``run.info``; None where there is nothing to
    read: no trace, or no program in it that names a scope."""
    if "lpm_scoped" in run.info:
        return run.info["lpm_scoped"]
    out = None
    path = trace_file(run) if run.trace is not None else None
    if path is not None:
        marks = xplane.read_planes(path)["marks"]
        trace = read_trace(path)
        by = seconds_by_scope(trace, marks[xplane.MARK_START][0],
                              marks[xplane.MARK_END][0])
        m0, m1 = run.trace["window_mono_s"]
        batches = sum(1 for name, t0, _d in run.spans
                      if name == BATCH_SPAN and m0 <= t0 < m1)
        if by and batches:
            chips = list(by["chips"].values())
            out = {"batches": batches, "chips": len(chips),
                   "has_lb": SCOPE_LB in by["named"]}
            for key, kind in (("lpm_s", "first"), ("lb_s", "second"),
                              ("mixed_s", "mixed"), ("unnamed_s", "unnamed")):
                out[key] = sum(c[kind] for c in chips) / len(chips)
    run.info["lpm_scoped"] = out
    return out
