"""``tools/gate.py``: a result line read into its row, and what the gate's
exit code follows (the change's runs, never the parent's)."""

import json
import os
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import gate  # noqa: E402

#: a result line as the chip printed it (``ct1m-50k.saturate``, PR 51's
#: builder's run 12)
RECORDED = os.path.join(ROOT, "tests", "data", "gate",
                        "ct1m-50k.saturate.result.json")


def test_a_result_line_is_read_into_its_row():
    with open(RECORDED) as f:
        line = f.read()
    text = "[ring] a line before\n" + line + "[compare] name=x ok=True\n"
    result = gate.read_result(text)
    assert result == json.loads(line)
    run = gate.parse_run("change:ct1m-50k.saturate:2151100301:0:A")
    row = gate.row_of(run, 0, result)
    assert (row["side"], row["cell"], row["seed"], row["trace"],
            row["cache_name"]) == ("change", "ct1m-50k.saturate",
                                   2151100301, 0, "A")
    assert row["ok"] and row["correct"] is True and row["failed"] == 0
    assert row["e2e"] == {"verdicts_per_s": 142361.6,
                          "setup_s": 33.79451862799999}
    assert row["layers"]["host.cpu_us_per_row"] == 6.6380259845351555
    assert row["cache"] == {"cache_hits": 5} and row["in_window"] == 0
    assert row["device"] == "TPU v5 lite"
    # every compared number beside its limit, in the check's own order
    assert len(row["numbers"]) == 17 == len(result["numbers"])
    assert row["numbers"]["probe_refused_now"] == "3/14"
    assert row["numbers"]["ct_full_share"] == "0.006797993636568458/0.01"
    assert list(row["numbers"])[:5] == [
        "fill_table_gap", "fill_denied", "unverdicted", "log_overflow",
        "stable_points"]
    assert row["not_ok"] == []
    # a run that printed no result line is a row too, and not ok
    none = gate.row_of(run, 1, gate.read_result("Traceback ...\n"))
    assert none["ok"] is False and none["correct"] is None \
        and none["rc"] == 1


FAKE_RUN = textwrap.dedent('''\
    import json, os, sys
    seed = int(sys.argv[sys.argv.index("--seed") + 1])
    probe = {"name": "probe_refused_now", "value": %(probe)d, "limit": 14,
             "how": "max", "ok": %(probe)d <= 14}
    print("[ring] cache=" + os.environ["JAX_COMPILATION_CACHE_DIR"])
    print(json.dumps({
        "correct": probe["ok"], "attempted": 1000, "failed": %(failed)d,
        "metrics": {"verdicts_per_s": {"value": 1.0 * seed,
                                       "unit": "frames/s"}},
        "compiles": {"total": 6, "in_window": 0,
                     "cache": {"cache_hits": 5}},
        "numbers": [probe]}))
''')


def tree(tmp_path, name, probe=3, failed=0):
    root = tmp_path / name
    (root / "benchmarks").mkdir(parents=True)
    (root / "benchmarks" / "run.py").write_text(
        FAKE_RUN % {"probe": probe, "failed": failed})
    return f"{name}={root}"


def rows_of(out):
    with open(os.path.join(out, "gate.jsonl")) as f:
        return [json.loads(line) for line in f]


@pytest.mark.parametrize("fault", [{"probe": 15}, {"failed": 2}],
                         ids=["not-correct", "lost-frames"])
def test_a_run_of_the_change_that_is_not_clean_fails_the_gate(
        tmp_path, capsys, fault):
    out = str(tmp_path / "out")
    rc = gate.main(["--tree", tree(tmp_path, "parent"),
                    "--tree", tree(tmp_path, "change", **fault),
                    "--out", out, "--seconds", "1",
                    "parent:cell.a:7:0:P", "change:cell.a:7:0:P"])
    assert rc == 1
    parent, change = rows_of(out)
    assert parent["ok"] and not change["ok"]
    assert change["e2e"] == {"verdicts_per_s": 7.0}
    if "probe" in fault:
        assert change["not_ok"] == ["probe_refused_now"] \
            and change["numbers"]["probe_refused_now"] == "15/14"
    else:
        assert change["correct"] and change["failed"] == 2
    # both sides ran on the one named cache, and each run kept its output
    logs = sorted(f for f in os.listdir(out) if f.endswith(".out"))
    assert logs == ["01_parent_cell.a_s7_t0.out", "02_change_cell.a_s7_t0.out"]
    for name in logs:
        with open(os.path.join(out, name)) as f:
            assert f.readline().strip() == "[ring] cache=" + os.path.join(
                out, "cache", "P")
    assert capsys.readouterr().out.count("\n") == 2


def test_a_parents_run_that_is_not_correct_does_not(tmp_path):
    out = str(tmp_path / "out")
    rc = gate.main(["--tree", tree(tmp_path, "parent", probe=15),
                    "--tree", tree(tmp_path, "change"),
                    "--out", out, "--seconds", "1",
                    "change:cell.a:1:1:C", "parent:cell.a:1:1:P"])
    assert rc == 0
    change, parent = rows_of(out)
    assert change["ok"] and change["trace"] == 1
    assert parent["correct"] is False and not parent["ok"] \
        and parent["not_ok"] == ["probe_refused_now"]
    # a run the budget leaves no time for is skipped, and says so
    rc = gate.main(["--tree", tree(tmp_path, "change2"), "--out", out,
                    "--budget-s", "-1", "change2:cell.a:1:0:C"])
    assert rc == 0 and rows_of(out)[-1]["skipped"] is True
