#!/usr/bin/env python3
"""Cut a profiler trace down to a few batches, for the tests' data.

    python3 benchmarks/tests/cut_trace.py <in.xplane.pb[.gz]> <spans.json>
                        <out.xplane.pb> <out.spans.json> [--batches 3]

Keeps what ``reduce/xplane.read_planes`` reads and nothing else: of each
``/device:TPU:<n>`` plane the ``XLA Ops`` line, of ``/host:CPU`` the two
window marks (moved to bracket the kept interval, ``t_mono_ns`` moved with
them), and of the events those that start inside the interval: the first
``--batches`` whole ``datapath.pack`` → ``datapath.compute`` cycles after
the traced interval's start. Event statistics and the HLO metadata plane
are dropped. An operation's name is kept as ``read_planes`` reduces it
(``short_op``: ``%fusion.12 fusion``), which is what every reader sees;
a ``collective-permute`` operation keeps its whole HLO line.

Needs the trace's protocol buffer (``tensorflow.tsl``), which the
benchmark itself does not: a builder's tool, run where the trace came
back to, not on the chip.
"""

import argparse
import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("spans")
    ap.add_argument("out_trace")
    ap.add_argument("out_spans")
    ap.add_argument("--batches", type=int, default=3)
    args = ap.parse_args(argv)
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from benchmarks.reduce import xplane
    opener = gzip.open if args.trace.endswith(".gz") else open
    space = xplane_pb2.XSpace()
    with opener(args.trace, "rb") as f:
        space.ParseFromString(f.read())
    with open(args.spans) as f:
        recorded = json.load(f)
    spans = [tuple(s) for s in recorded["spans"]]

    # the marks tie the trace's clock to the spans' monotonic clock
    host = next(p for p in space.planes if p.name == "/host:CPU")
    names = {k: m.name for k, m in host.event_metadata.items()}
    stat_names = {k: m.name for k, m in host.stat_metadata.items()}
    marks = {}
    for line in host.lines:
        for e in line.events:
            if names.get(e.metadata_id) in (xplane.MARK_START,
                                            xplane.MARK_END):
                mono = next(s.int64_value or s.uint64_value for s in e.stats
                            if stat_names[s.metadata_id] == "t_mono_ns")
                marks[names[e.metadata_id]] = (
                    line.timestamp_ns + e.offset_ps / 1e3, float(mono))
    w0_ns, mono0_ns = marks[xplane.MARK_START]
    offset_ns = mono0_ns - w0_ns                # monotonic = trace + offset

    # the kept interval: whole batches, pack's start to compute's end
    packs = sorted(t for n, t, _d in spans
                   if n == "datapath.pack" and t * 1e9 > mono0_ns)
    start_s = packs[0] - 20e-6
    last_pack = packs[args.batches - 1]
    end_s = max(t + d for n, t, d in spans if n == "datapath.compute"
                and last_pack <= t < packs[args.batches + 2]) + 20e-6
    k0, k1 = start_s * 1e9 - offset_ns, end_s * 1e9 - offset_ns

    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e in line.events:
                t = line.timestamp_ns + e.offset_ps / 1e3
                if k0 <= t < k1:
                    nl.events.add(metadata_id=e.metadata_id,
                                  offset_ps=e.offset_ps,
                                  duration_ps=e.duration_ps)
                    if e.metadata_id not in new.event_metadata:
                        name = plane.event_metadata[e.metadata_id].name
                        if "collective-permute" not in name:
                            name = xplane.short_op(name)
                        new.event_metadata[e.metadata_id].id = e.metadata_id
                        new.event_metadata[e.metadata_id].name = name
    nh = out.planes.add(id=host.id, name=host.name)
    nh.stat_metadata[1].id = 1
    nh.stat_metadata[1].name = "t_mono_ns"
    nl = nh.lines.add(id=1, name="marks", timestamp_ns=0)
    for i, (name, t_ns) in enumerate(((xplane.MARK_START, k0),
                                      (xplane.MARK_END, k1)), 1):
        nh.event_metadata[i].id = i
        nh.event_metadata[i].name = name
        ev = nl.events.add(metadata_id=i, offset_ps=int(t_ns * 1e3),
                           duration_ps=1000)
        ev.stats.add(metadata_id=1, int64_value=int(t_ns + offset_ns))
    with open(args.out_trace, "wb") as f:
        f.write(out.SerializeToString())
    with open(args.out_spans, "w") as f:
        json.dump({"source": os.path.basename(args.trace),
                   "spans": [[n, t, d] for n, t, d in spans
                             if start_s <= t < end_s]}, f)
    print(f"kept {args.batches} batches, {end_s - start_s:.6f} s, "
          f"{os.path.getsize(args.out_trace)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
