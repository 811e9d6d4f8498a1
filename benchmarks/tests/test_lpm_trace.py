"""The readers of ``lpm100k-zipf.saturate-longflows``'s four per-layer
metrics: ``benchmarks/lpm/trace.py`` (device events → seconds under the
program's ``lpm.walk`` and ``lb.step`` scopes), ``benchmarks/lpm/
walk_bytes.py`` and the four files under ``layers/``.

(a) the wire-format walker reads the recorded traces as JAX's own reader
    does, event for event;
(b) which scopes an instruction stands under, over a program written by
    hand: its own ``op_name``, its fusion's body, a loop's body two calls
    down, both scopes, neither, and for an instruction the compiler put
    in, its users';
(c) the readers over a trace recorded on the chip (``data/
    lpm100k.xplane.pb`` with the program's spans of the same interval in
    ``data/lpm100k.spans.json``: three batches of the cell's traced run,
    cut with ``cut_trace.py`` and ``keep_programs.py``);
(d) where there is nothing to read they return None and never 0: an
    untraced run, a trace whose programs name no scope (every trace
    recorded before PR 34), a program with no counter.
"""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.lpm import trace as T, walk_bytes
from benchmarks.reduce import xplane
from benchmarks.tests.conftest import DATA

LPM_METRICS = ("kernels.lpm_us_per_batch", "kernels.lpm_hbm_share",
               "kernels.lb_us_per_batch", "lb.translated_share")
CELL = "lpm100k-zipf.saturate-longflows"


def reader(name):
    return harness.load_reader("layers", name).read


# -- (a) ---------------------------------------------------------------------
@pytest.mark.parametrize("name", ("small.xplane.pb", "mesh4.xplane.pb",
                                  "lpm100k.xplane.pb"))
def test_the_walker_reads_what_jax_reads(name):
    path = os.path.join(DATA, name)
    mine, theirs = T.read_trace(path), xplane.read_planes(path)
    assert set(mine["chips"]) == set(theirs["devices"])
    for plane, d in theirs["devices"].items():
        events, meta = mine["chips"][plane], mine["metadata"][plane]
        assert len(events) == len(d["ops"]) > 0
        for (ident, start, dur), (op, s, dt) in zip(events, d["ops"]):
            assert xplane.short_op(meta[ident][0]) == op
            # JAX's reader gives whole nanoseconds, the file picoseconds
            assert start == pytest.approx(s, abs=1.0)
            assert dur == pytest.approx(dt, abs=1.0)


# -- (b) ---------------------------------------------------------------------
def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint((number << 3) | 2) + varint(len(value)) + value


def instruction(name, op_name="", calls=(), ident=0, operands=()):
    """An ``HloInstructionProto``: name 1, metadata 7 (op_name 2), id 35,
    operand_ids 36 and called_computation_ids 38 (both packed)."""
    out = field(1, name)
    if op_name:
        out += field(7, field(2, op_name))
    if ident:
        out += field(35, ident)
    if operands:
        out += field(36, b"".join(varint(c) for c in operands))
    if calls:
        out += field(38, b"".join(varint(c) for c in calls))
    return out


def program(computations) -> bytes:
    """An ``HloProto`` (hlo_module 1) of computations {id: [instruction]}
    (``HloModuleProto.computations`` 3: instructions 2, id 5)."""
    module = b"".join(
        field(3, b"".join(field(2, i) for i in insts) + field(5, cid))
        for cid, insts in computations.items())
    return field(1, module)


HAND = program({
    1: [instruction("gather.1", "jit(fn)/jit(main)/lpm.walk/gather")],
    2: [instruction("select.2", "jit(fn)/jit(main)/lb.step/select_n")],
    3: [instruction("add.3", "jit(fn)/jit(main)/add"),
        instruction("fusion.9", "jit(fn)/jit(main)/add", calls=(1,))],
    4: [instruction("call.4", "", calls=(3,))],
    9: [instruction("fusion.1", "jit(fn)/jit(main)/lpm.walk/gather",
                    calls=(1,)),
        instruction("fusion.2", "jit(fn)/jit(main)/mul", calls=(2,)),
        instruction("fusion.3", "jit(fn)/jit(main)/lb.step/eq", calls=(1,)),
        instruction("while.4", "jit(fn)/jit(main)/while", calls=(4, 3)),
        instruction("copy.5", "jit(fn)/jit(main)/lpm.walk/reshape"),
        instruction("copy.6", ""),
        # the compiler's own: no op_name, so whose work it is says who
        # uses it, through further such instructions
        instruction("copy.10", "", ident=10),
        instruction("bitcast.11", "", ident=11, operands=(10,)),
        instruction("gather.12", "jit(fn)/jit(main)/lpm.walk/gather",
                    ident=12, operands=(11,)),
        instruction("copy.13", "", ident=13),
        instruction("scatter.14", "jit(fn)/jit(main)/scatter", ident=14,
                    operands=(13,)),
        instruction("copy.15", "ct['keys']", ident=15),
        instruction("eq.16", "jit(fn)/jit(main)/lb.step/eq", ident=16,
                    operands=(15, 10)),
        # a scope's name inside another word names nothing
        instruction("copy.7", "jit(fn)/jit(main)/not_lpm.walk_either/x")],
})


@pytest.mark.parametrize("inst,want", [
    ("gather.1", {"lpm.walk"}), ("fusion.1", {"lpm.walk"}),
    ("fusion.2", {"lb.step"}),                  # in its body alone
    ("fusion.3", {"lpm.walk", "lb.step"}),      # mixed
    ("while.4", {"lpm.walk"}),                  # two calls down
    ("copy.5", {"lpm.walk"}), ("copy.6", set()), ("copy.7", set()),
    ("add.3", set()),
    ("copy.10", {"lpm.walk", "lb.step"}),       # both use it
    ("bitcast.11", {"lpm.walk"}),
    ("copy.13", set()),                         # used by conntrack
    ("copy.15", set()),                         # named, for an argument
])
def test_an_instructions_scopes(inst, want):
    assert T.scopes_by_instruction(HAND)[inst] == want


def hand_trace(hlo):
    """One chip, one program, four events of 1 µs each, 10 µs apart."""
    names = ("%fusion.1 = s32[8] fusion(...)", "%fusion.2 = s32[8] fusion",
             "%fusion.3 = s32[8] fusion(...)", "%copy.6 = s32[8] copy(...)")
    return {"chips": {"/device:TPU:0": [(i, 1e4 * i, 1e3)
                                        for i in range(len(names))]},
            "metadata": {"/device:TPU:0": {
                i: (n, {T.PROGRAM_STAT: 7}) for i, n in enumerate(names)}},
            "programs": {7: hlo}}


def test_seconds_by_scope_over_a_trace_written_by_hand():
    by = T.seconds_by_scope(hand_trace(HAND), 0.0, 1e6)
    assert by["named"] == {"lpm.walk", "lb.step"}
    assert by["chips"]["/device:TPU:0"] == pytest.approx(
        {"first": 1e-6, "second": 1e-6, "mixed": 1e-6, "unnamed": 1e-6})
    # cut to the traced interval: half of the first event, none of the rest
    by = T.seconds_by_scope(hand_trace(HAND), 500.0, 900.0)
    assert by["chips"]["/device:TPU:0"] == pytest.approx(
        {"first": 0.4e-6, "second": 0.0, "mixed": 0.0, "unnamed": 0.0})
    # a program that names no scope: nothing to read, not zero seconds
    bare = program({9: [instruction("fusion.1", "jit(fn)/jit(main)/add")]})
    assert T.seconds_by_scope(hand_trace(bare), 0.0, 1e6) is None


# -- (c) ---------------------------------------------------------------------
def recorded_run(tmp_path, trace_name, spans_name, monkeypatch, rows=1024):
    """What the harness hands the readers after a traced run, from the
    recorded files; the NIC's log says ``rows`` verdicts a batch."""
    where = tmp_path / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, trace_name), where / "t.xplane.pb")
    spans = []
    if spans_name:
        with open(os.path.join(DATA, spans_name)) as f:
            spans = [tuple(s) for s in json.load(f)["spans"]]
    trace = xplane.reduce_file(str(where / "t.xplane.pb"), spans)
    monkeypatch.setattr(harness, "describe_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    w0, w1 = trace["window_mono_s"]
    batches = sum(1 for n, t, _d in spans if n == "datapath.pack"
                  and w0 <= t < w1)
    rows_of = {"total": 0, "lb_translated": 0, "lb_no_backend": 0,
               "lpm_walked": 0, "lpm_missed": 0}
    run_ = types.SimpleNamespace(
        info={"trace_dir": str(tmp_path)}, trace=trace, spans=spans,
        w0=w0, w1=w1,
        stats0={"pipeline": {"verdict_rows": dict(rows_of)}},
        stats1={"pipeline": {"verdict_rows": dict(
            rows_of, total=rows * batches, lb_translated=100 * batches,
            lpm_walked=rows * batches)}})
    run_.verdicts_by = lambda t: 0 if t < w1 else rows * batches
    return run_, batches


def test_readers_over_the_recorded_trace(tmp_path, monkeypatch):
    run_, batches = recorded_run(tmp_path, "lpm100k.xplane.pb",
                                 "lpm100k.spans.json", monkeypatch)
    assert os.path.getsize(os.path.join(DATA, "lpm100k.xplane.pb")) < 600_000
    # three whole batches, and the packs of the next that began in them
    assert 3 <= batches <= 6
    sc = T.scoped(run_)
    assert sc["batches"] == batches and sc["chips"] == 1 and sc["has_lb"]
    lpm_us = reader("kernels.lpm_us_per_batch")(run_)
    lb_us = reader("kernels.lb_us_per_batch")(run_)
    share = reader("kernels.lpm_hbm_share")(run_)
    assert lpm_us == pytest.approx(sc["lpm_s"] / batches * 1e6)
    assert lb_us == pytest.approx(sc["lb_s"] / batches * 1e6)
    # the walk is the larger of the two by far, and both are a part of
    # the batch's device time
    busy_us = run_.trace["busy_s"] / batches * 1e6
    assert 0 < lb_us < lpm_us < busy_us
    # the four kinds add up to the busy union: nothing of this program
    # runs inside another event
    total = sc["lpm_s"] + sc["lb_s"] + sc["mixed_s"] + sc["unnamed_s"]
    assert total == pytest.approx(run_.trace["busy_s"], rel=0.05)
    # a share of a peak, bound by latency: far under 1%, never over 1.05
    assert 0 < share < 0.01 <= 1.05
    assert share == pytest.approx(
        walk_bytes.walk_bytes(batches * 1024) / (sc["lpm_s"] * 819e9))
    assert reader("lb.translated_share")(run_) == pytest.approx(100 / 1024)


# -- (d) ---------------------------------------------------------------------
def test_readers_find_nothing_in_an_untraced_run():
    run_ = types.SimpleNamespace(info={}, trace=None, spans=[], w0=0.0,
                                 w1=1.0, stats0={"pipeline": {}},
                                 stats1={"pipeline": {}})
    for name in LPM_METRICS:
        assert reader(name)(run_) is None, name


@pytest.mark.parametrize("trace_name,spans_name", [
    ("small.xplane.pb", None), ("mesh4.xplane.pb", "mesh4.spans.json")])
def test_readers_find_nothing_where_no_program_names_a_scope(
        tmp_path, monkeypatch, trace_name, spans_name):
    """Traces recorded before PR 34, cut without their programs: what a
    parent's traced run gives these readers."""
    run_, _batches = recorded_run(tmp_path, trace_name, spans_name,
                                  monkeypatch)
    run_.stats0 = run_.stats1 = {"pipeline": {}}       # no counter either
    for name in LPM_METRICS:
        assert reader(name)(run_) is None, name


def test_the_cell_reads_the_four_metrics(manifest):
    cell = harness.resolve_cell(manifest, CELL)
    assert set(LPM_METRICS) <= set(cell.layers)
    assert cell.e2e == ["verdicts_per_s", "setup_s"]
    for name in LPM_METRICS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "verdicts_per_s"
        assert entry["layer"] == "kernels"
