"""The harness end to end on tiny worlds, up to but not including the look
for a chip: ``run_cell`` is what ``run.py`` calls once it has found one.

The FIFO claim the latency arithmetic leans on — the k-th verdict belongs
to the k-th frame the ring accepted — is what ``prefix_excess == 0`` says:
at every point where the shim's counters stood still, the frames passed
are exactly the plain reference's count of admitted frames among the first
K accepted. In the open-loop cells harvests are a frame or two, so the
points are nearly as many as the frames. One chip, and a 4-wide virtual
mesh (host steering, un-steer on finalize). ``tiny-cidrsvc`` is the world
whose flows leave the endpoint: egress frames, prefixes of mixed length,
services. ``tiny-l7`` is the world whose refusals have two reasons: HTTP
rule sets a port, a request line in every frame.
"""

import json
import time

import numpy as np
import pytest

from benchmarks import harness, reference as ref
from benchmarks.tests import stops
from benchmarks.tests.conftest import DATA

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
STOP_KEYS = {"refused_in_stop", "refused_on_time", "stop_episodes", "stop_s"}


def run(tiny_manifest, name, seed, seconds=1.5, traced=False, **kw):
    cell = harness.resolve_cell(tiny_manifest, name, data_root=DATA)
    return cell, harness.run_cell(cell, seed, seconds, traced,
                                  time.monotonic(), **kw)


def numbers(result):
    return {n["name"]: n for n in result["numbers"]}


@pytest.fixture(scope="module")
def runs(tiny_manifest):
    """One run of each tiny cell, shared by the tests below; seeds as
    large as the driver's."""
    return {
        name: run(tiny_manifest, name, seed, traced=traced)
        for name, seed, traced in (
            ("tiny-pods.saturate", 2147483659, False),
            ("tiny-dual.steady80", 4000000007, False),
            ("tiny-pods-mesh4.saturate", 31, False),
            ("tiny-pods.steady80", 77, True),
            ("tiny-cidrsvc.saturate", 3000000019, False),
            ("tiny-l7.saturate", 3000000021, False),
        )}


@pytest.mark.parametrize("name", [
    "tiny-pods.saturate", "tiny-dual.steady80", "tiny-pods-mesh4.saturate",
    "tiny-pods.steady80", "tiny-cidrsvc.saturate", "tiny-l7.saturate"])
def test_result_line_and_fifo(runs, name):
    cell, r = runs[name]
    assert CONTRACT_KEYS <= set(r)
    json.dumps(r)                                     # one JSON object
    n = numbers(r)
    assert r["correct"], [x for x in r["numbers"] if not x["ok"]]
    # a closed loop loses nothing by construction; an open loop that meets
    # a stall of this machine long enough to fill the tiny ring loses the
    # frames the ring refused, and says so
    assert r["attempted"] > 1000
    assert r["failed"] == 0 or (cell.traffic["loop"] == "open"
                                and r["failed"] < r["attempted"] // 10)
    assert n["unverdicted"]["value"] == 0
    # whose loss: only refusals that found the generator on time are failed
    assert STOP_KEYS <= set(r["nic"])
    assert r["failed"] == r["nic"]["refused_on_time"]
    if cell.traffic["loop"] != "open":
        assert not any(r["nic"][k] for k in STOP_KEYS - {"stop_s"})
    assert n["prefix_excess"]["value"] == 0           # the FIFO claim
    assert n["stable_points"]["value"] >= 100
    assert n["probe_mismatched"]["value"] == 0
    assert n["probe_rows"]["value"] >= 64
    assert r["compiles"]["in_window"] == 0
    assert r["device"]["platform"] == "cpu"           # named, never hidden
    traced = name == "tiny-pods.steady80"
    want = set(cell.layers if traced else cell.e2e)
    got = set(r["metrics"])
    assert got <= want
    for m, v in r["metrics"].items():
        assert v["unit"] == cell.units[m] and np.isfinite(v["value"])
    if not traced:
        assert got == want                            # every e2e metric
        assert r["metrics"]["setup_s"]["value"] > 0


def test_open_loop_latency_counts_from_the_due_time(runs):
    _cell, r = runs["tiny-dual.steady80"]
    m = r["metrics"]
    assert 0 < m["verdict_p50_ms"]["value"] <= m["verdict_p90_ms"]["value"]
    # the other kind's readers beside them, and the window's first parts
    assert m["verdict_p90_ms"]["value"] \
        <= r["also"]["nic.verdict_p99_ms"]["value"]
    assert set(r["window_prefixes"]) == {"0.25", "0.5"}
    assert set(r["window_prefixes"]["0.5"]) == set(m)
    # 0.8 x 2,500 frames/s for 1.5 s, every frame due in the window sampled
    assert 2000 < r["latency_samples"] < 4000
    assert r["nic"]["rate"] == pytest.approx(2000.0)


def test_mesh_cell_steers(runs):
    cell, r = runs["tiny-pods-mesh4.saturate"]
    assert cell.config["daemon"]["n_shards"] == 4
    assert r["device"]["count"] == 4
    assert r["metrics"]["verdicts_per_s"]["value"] > 0


def test_traced_run_reads_spans_and_counters(runs):
    _cell, r = runs["tiny-pods.steady80"]
    m = r["metrics"]
    for name in ("nic.late_p99_ms", "nic.verdict_p99_ms", "nic.stop_ms",
                 "nic.refused_in_stop", "feeder.harvest_to_apply_ms",
                 "pipeline.deadline_flush_share", "datapath.compute_wait_ms",
                 "startup.compiles_in_window"):
        assert name in m, name
    assert m["startup.compiles_in_window"]["value"] == 0
    assert m["nic.stop_ms"]["value"] == r["nic"]["stop_s"] * 1e3
    assert m["nic.refused_in_stop"]["value"] == r["nic"]["refused_in_stop"]
    assert "verdict_p50_ms" in r["also"]      # what tracing costs end to end
    assert m["datapath.compute_wait_ms"]["value"] > 0
    # no device plane in a CPU trace: the device readers find nothing to
    # read and the line leaves them out
    assert "busy_s" not in r["device"] and "breakdown" not in r


def test_wrong_table_comes_out_not_correct(runs):
    """The control: the reference with one exercised rule taken out fails
    the comparisons the sound reference passes."""
    for name in ("tiny-pods.saturate", "tiny-dual.steady80",
                 "tiny-pods-mesh4.saturate", "tiny-cidrsvc.saturate",
                 "tiny-l7.saturate"):
        c = runs[name][1]["control"]
        assert c["caught"] is True, (name, c)
        assert c["frames_on_it"] >= 16
        assert c["prefix_excess"] > 0 and c["passed_gap"] > 0


#: the compared numbers of a run at the parent (66381af), in the result
#: line's order: name, how it is held, and the limit where it is the same
#: in every run
PARENTS_NUMBERS = [
    ("fill_table_gap", "max", 0), ("fill_denied", "max", 0),
    ("unverdicted", "eq", 0), ("log_overflow", "max", 0),
    ("stable_points", "min", 16), ("prefix_excess", "max", 0),
    ("passed_gap", "max", 0), ("reason_ok_gap", "max", 0),
    ("reason_policy_gap", "max", 0), ("reason_ct_full_gap", "max", 0),
    ("ct_full_share", "max", 0.01), ("pipeline_faults", "max", 0),
    ("feeder_faults", "max", 0), ("probe_rows", "min", 64),
    ("probe_mismatched", "max", 0), ("probe_refused_now", "max", None),
    ("probe_reopened", "max", None)]


@pytest.mark.parametrize("name", [
    "tiny-pods.saturate", "tiny-dual.steady80", "tiny-cidrsvc.saturate",
    "tiny-pods-mesh4.saturate"])
def test_a_world_that_states_no_reasons_has_the_parents_result_line(
        runs, name):
    """Names, order, how held and limits of ``numbers`` as the parent
    printed them, and no key the parent's line lacked."""
    _cell, r = runs[name]
    got = [(n["name"], n["how"], n["limit"]) for n in r["numbers"]]
    assert [g[:2] for g in got] == [p[:2] for p in PARENTS_NUMBERS]
    assert all(g[2] == p[2] for g, p in zip(got, PARENTS_NUMBERS)
               if p[2] is not None)
    assert list(r)[-1] == "numbers" and "refused_for" not in r
    assert set(r) == CONTRACT_KEYS | {
        "compiles", "also", "window_prefixes", "control", "latency_samples",
        "nic", "numbers"}


def test_a_world_that_states_two_reasons_holds_each_total(runs):
    """``tiny-l7``: refused frames of both reasons in the window, each
    reason's end total exact, and the probe met refused rows of both."""
    _cell, r = runs["tiny-l7.saturate"]
    n = numbers(r)
    got = [x["name"] for x in r["numbers"]]
    at = got.index("reason_policy_gap")
    assert got[at + 1] == "reason_policy_l7_gap"
    assert got[:at + 1] + got[at + 2:] == [p[0] for p in PARENTS_NUMBERS]
    assert n["reason_policy_gap"]["value"] == 0 \
        and n["reason_policy_l7_gap"]["value"] == 0
    assert n["reason_policy_l7_gap"]["limit"] == 0
    over = r["refused_for"]
    assert set(over) == {"reason_policy_gap", "reason_policy_l7_gap"}
    assert over["reason_policy_gap"] > 100
    assert over["reason_policy_l7_gap"] > 100
    assert list(r)[-1] == "numbers"


def swap_counted(monkeypatch, said, instead):
    """The program's verdicts-by-reason with every ``said`` counted as
    ``instead``: what a program does that drops the right frames and books
    them under the other reason."""
    sound = harness.reason_counts

    def counts(eng):
        c = sound(eng).copy()
        c[instead] += c[said]
        c[said] = 0
        return c
    monkeypatch.setattr(harness, "reason_counts", counts)


def swap_answered(said, instead):
    """``break_path``: every row ``Engine.submit`` answers the probe with
    ``said`` reads ``instead`` (the feeder's submissions, which name their
    ingest time, and so the rings' verdicts are untouched)."""
    def break_path(eng, shim):
        sound = eng.submit

        class Swapped:
            def __init__(self, ticket):
                self.ticket = ticket

            def result(self, timeout=None):
                out = dict(self.ticket.result(timeout=timeout))
                reason = np.array(out["reason"])
                out["reason"] = np.where(reason == said, instead, reason)
                return out
        eng.submit = lambda batch, **kw: sound(batch, **kw) if kw \
            else Swapped(sound(batch))
    return break_path


@pytest.mark.parametrize("said,instead", [(180, 130), (130, 180)])
def test_a_reason_swapped_either_way_comes_out_not_correct(
        tiny_manifest, monkeypatch, said, instead):
    """Where the answer is produced (the probe's rows) and where it is
    counted (the end totals): the right frames refused under the other
    reason is not correct."""
    _cell, r = run(tiny_manifest, "tiny-l7.saturate", 12 + said,
                   break_path=swap_answered(said, instead))
    n = numbers(r)
    assert not r["correct"]
    assert n["probe_mismatched"]["value"] > 0
    assert [x["name"] for x in r["numbers"] if not x["ok"]] \
        == ["probe_mismatched"]
    swap_counted(monkeypatch, said, instead)
    _cell, r = run(tiny_manifest, "tiny-l7.saturate", 14 + said)
    n = numbers(r)
    assert not r["correct"]
    gap = r["refused_for"][ref.REFUSAL_GAPS[said]]
    assert gap > 0
    assert n["reason_policy_gap"]["value"] == gap \
        == n["reason_policy_l7_gap"]["value"]
    assert n["passed_gap"]["value"] == 0 and n["probe_mismatched"]["ok"]


@pytest.mark.parametrize("name,seed", [("tiny-dual.steady80", 5),
                                       ("tiny-cidrsvc.saturate", 8),
                                       ("tiny-l7.saturate", 9)])
def test_broken_timed_path_comes_out_not_correct(tiny_manifest, name, seed):
    """A verdict altered where it is produced: the shim applies one wrong
    verdict in every 40th batch. The rest of the run is untouched."""
    def break_path(eng, shim):
        sound, seen = shim.apply_verdicts, [0]

        def apply(allow):
            allow = np.array(allow, dtype=bool)
            seen[0] += 1
            if seen[0] % 40 == 0 and allow.size:
                allow[0] = ~allow[0]
            sound(allow)
        shim.apply_verdicts = apply

    _cell, r = run(tiny_manifest, name, seed, break_path=break_path)
    n = numbers(r)
    assert not r["correct"]
    assert n["prefix_excess"]["value"] > 0 and not n["prefix_excess"]["ok"]
    assert n["unverdicted"]["value"] == 0             # still one per frame


def test_dropped_batch_comes_out_not_correct(tiny_manifest):
    """A part of the work left out: every 25th batch's verdicts are
    swallowed, so its frames never get one."""
    def break_path(eng, shim):
        sound, seen = shim.apply_verdicts, [0]

        def apply(allow):
            seen[0] += 1
            if seen[0] % 25 == 0:
                allow = np.zeros(0, dtype=bool)       # fail closed: all drop
            sound(allow)
        shim.apply_verdicts = apply

    _cell, r = run(tiny_manifest, "tiny-pods.saturate", 6,
                   break_path=break_path)
    assert not r["correct"]
    assert not numbers(r)["prefix_excess"]["ok"]


def test_failed_counts_what_the_ring_refused_on_time(tiny_manifest):
    """The server alone stands for 0.9 s of a 2 s window (the feeder held
    inside one verdict apply) while the generator keeps its schedule: the
    1,024-frame ring fills in half a second at 2,000 frames/s, the frames
    after that are refused on time, and those are `failed`. Nothing is
    wrong with any verdict."""
    cell, r = run(tiny_manifest, "tiny-dual.steady80", 11, seconds=2.0,
                  break_path=stops.hold_server(
                      harness.START_DELAY_S + 0.5 + 0.4, 0.9))
    nic, n = r["nic"], numbers(r)
    assert r["correct"], [x for x in r["numbers"] if not x["ok"]]
    assert nic["refused_on_time"] > 300
    assert r["failed"] == nic["refused_on_time"] + n["unverdicted"]["value"]
    assert r["attempted"] > r["failed"] + nic["refused_in_stop"] > 0
    assert nic["refused_aftermath"] <= nic["refused_in_stop"]
    assert (nic["stop_episodes"] == 0) == (nic["refused_in_stop"] == 0)


def test_a_new_cell_needs_only_new_files(tmp_path, tiny_manifest):
    """A later PR's cell: a configuration file, a traffic file, a knee file
    and a per-layer reader of its own, one entry each in the manifest, and
    not one edit to a file that is there."""
    import os
    import shutil
    root = tmp_path / "data"
    shutil.copytree(DATA, root)
    with open(root / "configs" / "tiny-dual.json") as f:
        cfg = json.load(f)
    cfg["name"] = "later-dual"
    cfg["live_flows"] = 1000
    (root / "configs" / "later-dual.json").write_text(json.dumps(cfg))
    (root / "knees" / "later-dual.json").write_text(
        json.dumps({"knee_frames_per_s": 2000}))
    with open(root / "traffic" / "steady80.json") as f:
        t = json.load(f)
    t["name"], t["knee_share"] = "steady50", 0.5
    (root / "traffic" / "steady50.json").write_text(json.dumps(t))
    m = json.loads(json.dumps(tiny_manifest))
    m["configs"].append({"name": "later-dual", "source": "test",
                         "file": os.path.relpath(
                             root / "configs" / "later-dual.json",
                             harness.REPO), "reduced": [], "why": "test"})
    m["workloads"].append({"name": "later-dual.steady50",
                           "config": "later-dual", "traffic": "steady50",
                           "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"].startswith("verdict_p"):
            e["workloads"].append("later-dual.steady50")
    cell = harness.resolve_cell(m, "later-dual.steady50",
                                data_root=str(root))
    assert harness.resolve_rate(cell) == 1000.0
    assert cell.e2e == ["verdict_p50_ms", "verdict_p90_ms", "setup_s"]
    r = harness.run_cell(cell, 9, 1.0, False, time.monotonic())
    assert r["correct"] and set(r["metrics"]) == set(cell.e2e)
