#!/usr/bin/env python3
"""Put the traced programs back into a trace that ``cut_trace.py`` cut.

    python3 benchmarks/tests/keep_programs.py <whole.xplane.pb[.gz]>
                        <cut.xplane.pb>

``cut_trace.py`` keeps what ``reduce/xplane.read_planes`` reads and drops
event statistics and the HLO metadata plane; ``benchmarks/lpm/trace.py``
reads exactly those: an event's ``program_id`` and that program's
``HloProto`` in the plane ``/host:metadata``, where the scopes are. This
adds to the cut trace, in place, from the whole one it was cut from:

- to the metadata of every event the cut kept, its ``program_id``
  statistic;
- the plane ``/host:metadata`` with the programs those events belong to,
  each ``HloProto`` pruned to what the reader reads: every computation's
  id, and of every instruction its name, its id and its operands', the
  ids of the computations it calls and its ``op_name``, cut down to
  ``-`` where it names none of ``--scopes`` (an instruction with no
  ``op_name`` is told from one with another's; shapes, literals, layouts
  and the buffer assignment go: 1.3 MB → a few hundred KB).

Needs the protocol buffers of the trace and of XLA's HLO (``tensorflow``),
which the benchmark itself does not: a builder's tool beside
``cut_trace.py``, run where the trace came back to, not on the chip.
"""

import argparse
import gzip
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def pruned(hlo_bytes: bytes, scopes) -> bytes:
    from tensorflow.compiler.xla.service import hlo_pb2
    whole = hlo_pb2.HloProto()
    whole.ParseFromString(hlo_bytes)
    out = hlo_pb2.HloProto()
    out.hlo_module.name = whole.hlo_module.name
    out.hlo_module.id = whole.hlo_module.id
    for comp in whole.hlo_module.computations:
        c = out.hlo_module.computations.add(id=comp.id)
        for inst in comp.instructions:
            i = c.instructions.add(name=inst.name, id=inst.id)
            i.operand_ids.extend(inst.operand_ids)
            if set(scopes) & set(inst.metadata.op_name.split("/")):
                i.metadata.op_name = inst.metadata.op_name
            elif inst.metadata.op_name:
                i.metadata.op_name = "-"
            i.called_computation_ids.extend(inst.called_computation_ids)
    return out.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("whole")
    ap.add_argument("cut")
    ap.add_argument("--scopes", default="lpm.walk,lb.step,pre_ct.tally")
    args = ap.parse_args(argv)
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from benchmarks.lpm import trace as T
    from benchmarks.reduce import xplane
    opener = gzip.open if args.whole.endswith(".gz") else open
    whole, cut = xplane_pb2.XSpace(), xplane_pb2.XSpace()
    with opener(args.whole, "rb") as f:
        whole.ParseFromString(f.read())
    with open(args.cut, "rb") as f:
        cut.ParseFromString(f.read())
    planes = {p.name: p for p in whole.planes}
    wanted = set()
    for plane in cut.planes:
        if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        src = planes[plane.name]
        stat_id = next(k for k, m in src.stat_metadata.items()
                       if m.name == T.PROGRAM_STAT)
        plane.stat_metadata[stat_id].id = stat_id
        plane.stat_metadata[stat_id].name = T.PROGRAM_STAT
        for ident, meta in plane.event_metadata.items():
            stat = next((s for s in src.event_metadata[ident].stats
                         if s.metadata_id == stat_id), None)
            if stat is not None:
                meta.stats.add().CopyFrom(stat)
                wanted.add((stat.uint64_value or stat.int64_value)
                           & T.U64)
    src = planes[T.METADATA_PLANE]
    stat_id = next(k for k, m in src.stat_metadata.items()
                   if m.name == T.HLO_STAT)
    new = cut.planes.add(id=src.id, name=src.name)
    new.stat_metadata[stat_id].id = stat_id
    new.stat_metadata[stat_id].name = T.HLO_STAT
    for ident, meta in src.event_metadata.items():
        if ident & T.U64 not in wanted:
            continue
        hlo = next((s for s in meta.stats if s.metadata_id == stat_id), None)
        if hlo is None:
            continue
        kept = new.event_metadata[ident]
        kept.id, kept.name = ident, meta.name
        kept.stats.add(metadata_id=stat_id,
                       bytes_value=pruned(hlo.bytes_value,
                                           args.scopes.split(",")))
    with open(args.cut, "wb") as f:
        f.write(cut.SerializeToString())
    print(f"kept {len(new.event_metadata)} programs of {len(wanted)} "
          f"wanted, {os.path.getsize(args.cut)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
