"""The reduction from a profiler trace to busy time, top operations and
named idle gaps: on planes written out by hand, where every answer is
known, and on a small trace recorded on a TPU v5e chip by this benchmark
(``data/small.xplane.pb``: 0.1 s of ``ct1m-50k.saturate``, PERF.md PR 23)."""

import os

import numpy as np
import pytest

from benchmarks.reduce import xplane
from benchmarks.tests.conftest import DATA


def test_union():
    s = np.array([0.0, 5.0, 20.0, 22.0, 40.0])
    e = np.array([10.0, 8.0, 30.0, 35.0, 41.0])
    total, pieces = xplane.union_ns(s, e)
    assert total == 10 + 15 + 1
    assert pieces == [(0.0, 10.0), (20.0, 35.0), (40.0, 41.0)]
    assert xplane.union_ns(np.zeros(0), np.zeros(0)) == (0.0, [])


def test_short_op():
    line = ("%while.1 = (u32[]{:T(128)}, u16[2,1002,25007]{2,1,0:T(8,128)}) "
            "while((u32[]{:T(128)}) %tuple.146), condition=%c, body=%b")
    assert xplane.short_op(line) == "%while.1 while"
    assert xplane.short_op("%fusion.115 = u32[2097152,10]{0,1:T(8,128)} "
                           "fusion(u32[4]{0} %x), kind=kCustom") \
        == "%fusion.115 fusion"
    assert xplane.short_op("no equals sign") == "no equals sign"


def planes(ops0, ops1=None, w=(1000.0, 11000.0), mono0=5e9):
    devices = {"/device:TPU:0": {"ops": ops0, "lines": ["XLA Ops"]}}
    if ops1 is not None:
        devices["/device:TPU:1"] = {"ops": ops1, "lines": ["XLA Ops"]}
    return {"devices": devices,
            "marks": {xplane.MARK_START: (w[0], mono0),
                      xplane.MARK_END: (w[1], mono0 + w[1] - w[0])}}


def test_busy_is_the_union_inside_the_window():
    ops = [("a", 0.0, 2000.0),          # half before the window
           ("a", 3000.0, 1000.0),
           ("b", 3500.0, 1500.0),       # overlaps a: union 3000..5000
           ("c", 10500.0, 2000.0)]      # half after the window
    r = xplane.reduce_planes(planes(ops))
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx((1000 + 2000 + 500) * 1e-9)
    assert dict(map(tuple, r["device_ops"]))["a"] == pytest.approx(2000e-9)
    assert r["n_gaps"] == 2             # 2000..3000 and 5000..10500
    assert r["longest_gap_s"] == pytest.approx(5500e-9)
    # a share of a peak never passes 100%: busy cannot pass the window
    assert r["busy_s"] <= r["window_s"]


def test_busy_is_averaged_over_the_chips():
    r = xplane.reduce_planes(planes([("a", 1000.0, 4000.0)],
                                    [("a", 1000.0, 2000.0)]))
    assert r["busy_s"] == pytest.approx(3000e-9)
    assert r["busy_s_per_chip"]["/device:TPU:1"] == pytest.approx(2000e-9)


def test_gaps_are_named_by_the_innermost_open_span():
    # device busy 1000..2000 and 8000..11000 of the trace clock; monotonic
    # clock = trace clock + (5e9 - 1000) ns
    ops = [("a", 1000.0, 1000.0), ("a", 8000.0, 3000.0)]
    off = 5e9 - 1000.0

    def mono(t):
        return (t + off) / 1e9
    spans = [("outer", mono(1500.0), 5000e-9),      # 1500..6500
             ("inner", mono(3000.0), 1000e-9)]      # 3000..4000
    r = xplane.reduce_planes(planes(ops), spans)
    got = dict(map(tuple, r["idle_gaps"]))
    # the gap 2000..8000: outer 2000..3000 and 4000..6500, inner
    # 3000..4000, nobody 6500..8000
    assert got["outer"] == pytest.approx(3500e-9, rel=1e-3)
    assert got["inner"] == pytest.approx(1000e-9, rel=1e-3)
    assert got["no span open"] == pytest.approx(1500e-9, rel=1e-3)
    assert r["window_mono_s"][0] == pytest.approx(5.0)


def test_nothing_to_read_returns_nothing():
    assert xplane.reduce_planes({"devices": {}, "marks": {}}) is None
    p = planes([("a", 0.0, 1.0)])
    del p["marks"][xplane.MARK_END]
    assert xplane.reduce_planes(p) is None


def test_recorded_chip_trace():
    path = os.path.join(DATA, "small.xplane.pb")
    r = xplane.reduce_file(path)
    assert r is not None
    assert 0.05 < r["window_s"] < 0.5
    assert 0 < r["busy_s"] < r["window_s"]            # one chip, mostly idle
    assert list(r["busy_s_per_chip"]) == ["/device:TPU:0"]
    ops = r["device_ops"]
    assert 1 <= len(ops) <= 10
    assert all(name.startswith("%") and len(name) <= 120 and t > 0
               for name, t in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    # no spans handed in: every idle second is unnamed, and idle + busy is
    # the window
    (name, idle), = r["idle_gaps"]
    assert name == "no span open"
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
