"""The readers of ``l7-http.saturate-longflows``'s four per-layer metrics:
``benchmarks/l7/trace.py`` (device events → seconds under the program's
``l7.unpack`` and ``l7.match`` scopes), ``benchmarks/l7/match_bytes.py``
and the four files under ``layers/``.

(a) the wire-format walker reads the recorded trace as JAX's own reader
    does, event for event;
(b) the one exception ``l7/trace.py`` makes to ``lpm/trace.py``'s rule,
    over a program written by hand: a name that ends in
    ``broadcast_in_dim`` names no scope (a conntrack scatter that holds
    the match's merged ``True``), every other name does, and a program
    with nothing to blank comes back byte for byte;
(c) the readers over a trace recorded on the chip (``data/
    l7http.xplane.pb`` with the program's spans of the same interval in
    ``data/l7http.spans.json``: three batches of the cell's traced run,
    cut with ``cut_trace.py`` and ``keep_programs.py --scopes
    l7.unpack,l7.match``): the scoped seconds against a count by hand
    over the operations' names, and what the rule as it stands would
    have read;
(d) where there is nothing to read they return None and never 0: an
    untraced run, a trace whose programs name neither scope
    (``lpm100k.xplane.pb``: the egress cell's program), a program with
    no counter or no span;
(e) the cell at test size through ``run_cell`` on the CPU, traced: the
    span's and the counter's readers read numbers, the counter's the
    plain reference's share of the window's frames, and the device's
    readers, with no device plane to read, leave their metrics out.
"""

import copy
import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.l7 import match_bytes, trace as L7
from benchmarks.lpm import trace as T
from benchmarks.reduce import xplane
from benchmarks.tests.conftest import DATA
from benchmarks.tests.test_lpm_trace import (
    field, instruction, program, recorded_run as lpm_recorded_run)

L7_METRICS = ("datapath.l7_dict_us_per_batch", "kernels.l7_us_per_batch",
              "kernels.l7_hbm_share", "l7.checked_share")
CELL = "l7-http.saturate-longflows"
TRACE, SPANS = "l7http.xplane.pb", "l7http.spans.json"


def reader(name):
    return harness.load_reader("layers", name).read


def manifest():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- (a) ---------------------------------------------------------------------
def test_the_walker_reads_what_jax_reads():
    path = os.path.join(DATA, TRACE)
    assert os.path.getsize(path) < 500_000
    mine, theirs = T.read_trace(path), xplane.read_planes(path)
    assert set(mine["chips"]) == set(theirs["devices"])
    for plane, d in theirs["devices"].items():
        events, meta = mine["chips"][plane], mine["metadata"][plane]
        assert len(events) == len(d["ops"]) > 0
        for (ident, start, dur), (op, s, dt) in zip(events, d["ops"]):
            assert xplane.short_op(meta[ident][0]) == op
            assert start == pytest.approx(s, abs=1.0)
            assert dur == pytest.approx(dt, abs=1.0)


# -- (b) ---------------------------------------------------------------------
MERGED = "jit(fn)/l7.match/jit(_where)/broadcast_in_dim"
HAND = program({
    # conntrack's claim round: the scatter's update is the match's True
    1: [instruction("reshape.1", MERGED),
        instruction("transpose.2", MERGED),
        instruction("scatter.3", "jit(fn)/scatter")],
    # the match itself
    2: [instruction("gather.4", "jit(fn)/l7.match/gather"),
        instruction("eq.5", "jit(fn)/l7.match/eq"),
        instruction("true.6", MERGED)],
    3: [instruction("gather.7", "jit(fn)/l7.unpack/gather")],
    9: [instruction("fusion.10", "jit(fn)/scatter", calls=(1,)),
        instruction("fusion.11", "jit(fn)/l7.match/reduce_or", calls=(2,)),
        instruction("fusion.12", "jit(fn)/l7.unpack/gather", calls=(3,)),
        instruction("broadcast.13", MERGED, ident=13),
        # the compiler's own copy of the merged constant, for conntrack
        instruction("copy.14", "", ident=14, operands=(13,)),
        instruction("scatter.15", "jit(fn)/scatter", ident=15,
                    operands=(14,)),
        # ... and one for the match
        instruction("copy.16", "", ident=16),
        instruction("reduce.17", "jit(fn)/l7.match/reduce_and", ident=17,
                    operands=(16,))],
})


@pytest.mark.parametrize("inst,as_it_stands,with_the_exception", [
    ("fusion.10", {"l7.match"}, set()),         # conntrack's, by its body
    ("fusion.11", {"l7.match"}, {"l7.match"}),
    ("fusion.12", {"l7.unpack"}, {"l7.unpack"}),
    ("broadcast.13", {"l7.match"}, set()),
    ("copy.14", set(), set()),                  # its user is conntrack's
    ("copy.16", {"l7.match"}, {"l7.match"}),    # its user is the match
    ("true.6", {"l7.match"}, set()),
])
def test_a_merged_constant_names_no_scope(inst, as_it_stands,
                                          with_the_exception):
    assert T.scopes_by_instruction(HAND, L7.SCOPES)[inst] == as_it_stands
    blanked = L7.without_merged_constants(HAND)
    assert T.scopes_by_instruction(blanked, L7.SCOPES)[inst] \
        == with_the_exception


def test_a_program_with_nothing_to_blank_comes_back_byte_for_byte():
    bare = program({9: [instruction("fusion.1", "jit(fn)/l7.match/gather",
                                    calls=(1,), ident=3, operands=(1, 2))],
                    1: [instruction("gather.2", "jit(fn)/lpm.walk/gather")]})
    # fields this file's helpers do not write, kept as they are: a fixed
    # 64-bit one, a negative varint, bytes
    more = bare + field(9, 2 ** 64 - 5) + field(12, b"\x00\xff") \
        + bytes([(3 << 3) | 1]) + (7).to_bytes(8, "little") \
        + bytes([(4 << 3) | 5]) + (9).to_bytes(4, "little")
    assert L7.without_merged_constants(more) == more
    # and a blanked name is all that changes: the instructions in their
    # order, every other name as it was
    assert op_names(L7.without_merged_constants(HAND)) == [
        (inst, "-" if name == MERGED else name)
        for inst, name in op_names(HAND)]
    assert [name for _i, name in op_names(HAND)].count(MERGED) == 4


def op_names(hlo_proto):
    """(instruction name, op_name) of every instruction, in file order."""
    out = []
    (module,) = [v for n, _w, v in T.fields(hlo_proto) if n == 1]
    for n, _w, comp in T.fields(module):
        if n != 3:
            continue
        for n2, _w2, inst in T.fields(comp):
            if n2 == 2:
                f = {n3: v3 for n3, _w3, v3 in T.fields(inst)
                     if n3 in (1, 7)}
                meta = dict((n4, bytes(v4).decode())
                            for n4, _w4, v4 in T.fields(f.get(7, b"")))
                out.append((bytes(f[1]).decode(), meta.get(2, "")))
    return out


# -- (c) ---------------------------------------------------------------------
def recorded_run(tmp_path, trace_name, spans_name, monkeypatch, rows=1024):
    """What the harness hands the readers after a traced run, from the
    recorded files (``test_lpm_trace``'s, with this cell and its two
    counters): the NIC's log says ``rows`` verdicts a batch."""
    run_, batches = lpm_recorded_run(tmp_path, trace_name, spans_name,
                                     monkeypatch, rows)
    run_.cell = harness.resolve_cell(manifest(), CELL)
    run_.stats0 = {"pipeline": {"verdict_rows": {
        "total": 0, "l7_checked": 0, "l7_refused": 0}}}
    run_.stats1 = {"pipeline": {"verdict_rows": {
        "total": rows * batches, "l7_checked": 1000 * batches,
        "l7_refused": 3 * batches}}}
    return run_, batches


def seconds_of(run_, names) -> float:
    """Device seconds of the operations ``names`` (as ``read_planes``
    shortens them) in the recorded interval, a chip."""
    planes = xplane.read_planes(os.path.join(
        run_.info["trace_dir"], "plugins", "profile", "recorded",
        "t.xplane.pb"))
    w0, w1 = (planes["marks"][m][0] for m in (xplane.MARK_START,
                                              xplane.MARK_END))
    total = 0.0
    for d in planes["devices"].values():
        for op, start, dur in d["ops"]:
            if op.split(" ", 1)[0].lstrip("%") in names:
                total += max(0.0, min(start + dur, w1) - max(start, w0))
    return total / 1e9 / len(planes["devices"])


#: the operations of the chip's 1,024-row ``l7-http`` program that stand
#: under each scope, read by hand from the traced program's text and the
#: trace's own list of operations (PERF.md §5, PR 37). Unpack: the wire's
#: prefetch, the dictionary's gather (``%fusion``), the cut into bytes and
#: the copies the compiler put between them, the request test over the
#: unpacked path. Match: the four gathers of a set's rules (``%fusion.9``
#: - ``.12``), the compare over [1024, 3, 64] (``%fusion.164``), the
#: reductions over the rules and their copies
UNPACK_OPS = {"copy-start.17", "copy-done.17", "fusion", "fusion.331",
              "and_bitcast_fusion.2", "reshape.1889", "copy.770",
              "copy.771", "copy.772", "copy.773", "copy.787", "copy.788",
              "compare_reduce_fusion.2", "broadcast_clamp_fusion.11",
              "broadcast_clamp_fusion.12", "broadcast_clamp_fusion.13",
              "broadcast_clamp_fusion.14", "broadcast_clamp_fusion.15"}
MATCH_OPS = {"fusion.9", "fusion.10", "fusion.11", "fusion.12",
             "fusion.164", "fusion.240", "fusion.290", "copy.784",
             "copy.785", "copy.790", "convert_reduce_fusion.2",
             "compare_select_fusion.44", "broadcast_clamp_fusion.9"}
#: conntrack's eleven claim-round scatters over ``pred[65536]`` and two
#: small operations beside them, which hold the match's merged ``True``
MERGED_OPS = {"fusion.118", "fusion.122", "fusion.126", "fusion.130",
              "fusion.134", "fusion.138", "fusion.142", "fusion.146",
              "fusion.150", "fusion.154", "fusion.161",
              "compare_select_fusion.16", "broadcast_in_dim.26"}


def test_readers_over_the_recorded_trace(tmp_path, monkeypatch):
    run_, batches = recorded_run(tmp_path, TRACE, SPANS, monkeypatch)
    assert 3 <= batches <= 6
    sc = L7.scoped(run_)
    assert sc["batches"] == batches and sc["chips"] == 1
    assert run_.info[L7.KEPT] is sc and "lpm_scoped" not in run_.info
    # the scoped seconds against the count by hand (JAX's reader, which
    # the hand count goes through, gives whole nanoseconds, the file
    # picoseconds: events of a microsecond differ in the third place)
    assert sc["unpack_s"] == pytest.approx(seconds_of(run_, UNPACK_OPS),
                                           rel=5e-3)
    assert sc["match_s"] == pytest.approx(seconds_of(run_, MATCH_OPS),
                                          rel=5e-3)
    assert 0 < sc["unpack_s"] < sc["match_s"] and sc["mixed_s"] == 0
    assert sc["l7_s"] == pytest.approx(
        sc["unpack_s"] + sc["match_s"] + sc["mixed_s"])
    l7_us = reader("kernels.l7_us_per_batch")(run_)
    assert l7_us == pytest.approx(sc["l7_s"] / batches * 1e6)
    # a part of the batch's device time, and a small one
    busy_us = run_.trace["busy_s"] / batches * 1e6
    assert 0 < l7_us < 0.25 * busy_us
    # the four kinds add up to the busy union: nothing of this program
    # runs inside another event
    total = sc["l7_s"] + sc["unnamed_s"]
    assert total == pytest.approx(run_.trace["busy_s"], rel=0.01)
    # what the rule as it stands would have read: conntrack's scatters too
    trace = T.read_trace(T.trace_file(run_))
    planes = xplane.read_planes(T.trace_file(run_))["marks"]
    raw = T.seconds_by_scope(trace, planes[xplane.MARK_START][0],
                             planes[xplane.MARK_END][0], scopes=L7.SCOPES)
    merged = seconds_of(run_, MERGED_OPS)
    (chip,) = raw["chips"].values()
    assert chip["second"] == pytest.approx(sc["match_s"] + merged, rel=5e-3)
    assert merged > sc["l7_s"]
    # the share of the roofline: the hand count of the bytes over that time
    share = reader("kernels.l7_hbm_share")(run_)
    assert share == pytest.approx(
        (batches * 1024 * 72 + batches * 201 * 3 * 70)
        / (sc["l7_s"] * 819e9))
    assert share == pytest.approx(match_bytes.match_bytes(
        batches * 1024, batches, 200, 3) / (sc["l7_s"] * 819e9))
    assert 0 < share < 0.05 <= 1.05
    # the host's span, per batch, inside datapath.pack
    dict_us = reader("datapath.l7_dict_us_per_batch")(run_)
    pack_us = sum(d for n, t, d in run_.spans if n == "datapath.pack"
                  and run_.w0 <= t < run_.w1) / batches * 1e6
    assert 0 < dict_us <= pack_us \
        <= reader("datapath.host_us_per_batch")(run_)
    assert reader("l7.checked_share")(run_) == pytest.approx(1000 / 1024)


# -- (d) ---------------------------------------------------------------------
def test_readers_find_nothing_in_an_untraced_run():
    run_ = types.SimpleNamespace(info={}, trace=None, spans=[], w0=0.0,
                                 w1=1.0, stats0={"pipeline": {}},
                                 stats1={"pipeline": {}})
    for name in L7_METRICS:
        assert reader(name)(run_) is None, name


def test_readers_find_nothing_in_a_program_without_the_names(
        tmp_path, monkeypatch):
    """The egress cell's recorded trace: its program names ``lpm.walk``
    and ``lb.step``, neither of the lane's; its spans hold no dictionary's
    and its counters are a parent's."""
    run_, _batches = recorded_run(tmp_path, "lpm100k.xplane.pb",
                                  "lpm100k.spans.json", monkeypatch)
    parent = {"total": 0, "lb_translated": 0, "lb_no_backend": 0,
              "lpm_walked": 0, "lpm_missed": 0}
    run_.stats0 = {"pipeline": {"verdict_rows": dict(parent)}}
    run_.stats1 = {"pipeline": {"verdict_rows": dict(parent, total=4096)}}
    assert L7.scoped(run_) is None and run_.info[L7.KEPT] is None
    for name in L7_METRICS:
        assert reader(name)(run_) is None, name
    # ... while the egress readers still read it
    assert reader("kernels.lpm_us_per_batch")(run_) > 0


def test_the_cell_reads_the_four_metrics():
    m = manifest()
    cell = harness.resolve_cell(m, CELL)
    assert set(L7_METRICS) <= set(cell.layers)
    assert cell.e2e == ["verdicts_per_s", "setup_s"]
    layer_of = {"datapath.l7_dict_us_per_batch": "datapath host"}
    for name in L7_METRICS:
        entry = next(e for e in m["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "verdicts_per_s"
        assert entry["layer"] == layer_of.get(name, "kernels")
    # no other cell reads them, and this one reads no other kernel's
    for w in m["workloads"]:
        if w["name"] != CELL:
            assert not set(L7_METRICS) & set(
                harness.resolve_cell(m, w["name"]).layers)
    assert not {"kernels.lpm_us_per_batch", "kernels.lb_us_per_batch",
                "lb.translated_share"} & set(cell.layers)


# -- (e) ---------------------------------------------------------------------
def test_the_cell_at_test_size_reads_the_hosts_two(monkeypatch):
    cell = harness.resolve_cell(manifest(), CELL)
    cell.config = dict(
        copy.deepcopy(cell.config),
        daemon={"ct_capacity": 65536, "batch_size": 1024},
        rings={"ring_size": 1024, "frame_size": 2048, "n_frames": 1024},
        live_flows=2000)
    cell.config["world"]["n_rulesets"] = 16
    cell.traffic = dict(cell.traffic, schedule_frames_per_s=80000,
                        warmup_s=0.5)
    seen = {}
    sound_check = harness.check

    def check(sv, tr, run, *a, **kw):
        from benchmarks import reference as ref
        frames = tr.sched[run.accepted_idx][
            (run.verdict_t >= run.w0) & (run.verdict_t < run.w1)]
        has_set = sv.world.reasons(tr.flows) == ref.REASON_POLICY_L7
        seen["share"] = float(np.mean(has_set[frames]))
        return sound_check(sv, tr, run, *a, **kw)
    monkeypatch.setattr(harness, "check", check)
    r = harness.run_cell(cell, 3700000201, 1.5, True, time.monotonic())
    assert r["correct"] and r["control"]["caught"]
    assert r["refused_for"]["reason_policy_l7_gap"] > 0 \
        and r["refused_for"]["reason_policy_gap"] > 0
    m = r["metrics"]
    assert set(m) == set(cell.layers) - {
        "kernels.device_ns_per_row", "kernels.l7_us_per_batch",
        "kernels.l7_hbm_share"}                 # no device plane on a CPU
    assert 0 < m["datapath.l7_dict_us_per_batch"]["value"] \
        < m["datapath.host_us_per_batch"]["value"]
    # the counter against the reference's share of the window's frames;
    # the two ends of the window are read a harvest apart
    assert 0.98 < seen["share"] < 1.0
    assert m["l7.checked_share"]["value"] == pytest.approx(seen["share"],
                                                           abs=0.003)
    assert m["startup.compiles_in_window"]["value"] == 0
