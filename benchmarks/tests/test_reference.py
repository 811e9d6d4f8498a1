"""The plain reference and the comparisons built on it, on synthetic
logs: each comparison has to catch the fault it is there for."""

import numpy as np
import pytest

from benchmarks import reference as ref
from benchmarks.frames import concat
from benchmarks.laws import flowmix
from benchmarks.tests.conftest import tiny_config
from benchmarks.worlds import cidrsvc, groupports, httprules, podrules

LAW = {"live_share": 0.9, "zipf_s": 1.0, "new_allowed": 0.78,
       "new_denied": 0.18, "new_unknown": 0.04}
WORLDS = [
    (podrules, {"n_ids": 64, "n_rules": 512, "port_span": 250}),
    (groupports, {"n_ids": 400, "groups": 20, "n_rules": 200,
                  "port_span": 100, "v6_every": 4}),
]


@pytest.mark.parametrize("mod,params", WORLDS + [
    (cidrsvc, tiny_config("tiny-cidrsvc")["world"]),
    (httprules, tiny_config("tiny-l7")["world"])])
def test_law_and_reference_agree_on_kinds(mod, params):
    w = mod.build(params)
    mix = flowmix.generate(LAW, w, np.random.default_rng(3), 1000, 20000)
    want = ref.expected_allow(w, mix["flows"])
    kind = mix["kind"]
    assert want[kind == flowmix.KIND_LIVE].all()
    assert want[kind == flowmix.KIND_NEW_ALLOWED].all()
    assert not want[kind == flowmix.KIND_NEW_DENIED].any()
    assert not want[kind == flowmix.KIND_NEW_UNKNOWN].any()
    sched = mix["sched_flow"]
    new = sched >= 1000
    assert abs(new.mean() - 0.1) < 0.02
    # each new flow is sent once; the live set is hit by rank
    assert np.unique(sched[new]).size == new.sum()
    hits = np.bincount(sched[~new], minlength=1000)
    assert hits[0] > hits[10] > hits[500]
    # the same seed gives the same traffic, another seed the same flows
    # and the same frames in another order
    again = flowmix.generate(LAW, w, np.random.default_rng(3), 1000, 20000)
    other = flowmix.generate(LAW, w, np.random.default_rng(4), 1000, 20000)
    assert (again["sched_flow"] == sched).all()
    assert (other["sched_flow"] != sched).any()
    assert (np.sort(other["sched_flow"]) == np.sort(sched)).all()
    assert all((other["flows"][k] == mix["flows"][k]).all()
               for k in mix["flows"])


@pytest.mark.parametrize("mod,params", WORLDS)
def test_reference_is_the_rule_documents(mod, params):
    """The table against a direct reading of the documents the world hands
    the program: every (source selector, port, protocol) a document names
    is a cell the table admits, and it admits no other."""
    w = mod.build(params)
    table, cover = w.table()
    assert int(cover.sum()) == len(w.policy_docs())
    assert int(table.sum()) == int((cover > 0).sum())
    flows = w.allowed_flows(np.random.default_rng(0), 500, 20000, 40000)
    assert table[w.cells(flows)].all()
    denied = w.denied_flows(np.random.default_rng(0), 500, 20000, 40000)
    assert (w.cells(denied) >= 0).all() and not table[w.cells(denied)].any()
    unknown = w.unknown_flows(np.random.default_rng(0), 50, 20000, 40000)
    assert (w.cells(unknown) == -1).all()


def synthetic_log(allow, batch=7):
    """A log as nicgen would write it for verdicts applied in order,
    ``batch`` at a time, every entry stable."""
    done = np.arange(batch, allow.size + 1, batch)
    passes = np.cumsum(allow)[done - 1]
    return {"log_passes": passes.astype(np.int64),
            "log_drops": (done - passes).astype(np.int64),
            "log_txfull": np.zeros(done.shape, np.int64),
            "log_stable": np.ones(done.shape, bool)}


BASE = {"verdict_passes": 0, "verdict_drops": 0, "tx_full_drops": 0}


def test_prefix_check_passes_what_is_in_order():
    allow = np.random.default_rng(0).random(700) < 0.8
    pc = ref.prefix_check(allow, synthetic_log(allow), BASE, 0)
    assert pc["prefix_excess"] == 0 and pc["stable_points"] == 100
    assert pc["done_end"] == 700 and pc["passed_end"] == allow.sum()


def test_prefix_check_catches_a_wrong_verdict_and_a_wrong_order():
    rng = np.random.default_rng(1)
    allow = rng.random(700) < 0.8
    served = allow.copy()
    served[350] = ~served[350]                       # one verdict altered
    assert ref.prefix_check(allow, synthetic_log(served), BASE,
                            0)["prefix_excess"] == 1
    # the program counted one refusal: a drop of an admitted frame is
    # within it, a pass of a denied one is not
    drop_one = allow.copy()
    drop_one[np.nonzero(allow)[0][5]] = False
    assert ref.prefix_check(allow, synthetic_log(drop_one), BASE,
                            1)["prefix_excess"] == 0
    pass_one = allow.copy()
    pass_one[np.nonzero(~allow)[0][5]] = True
    assert ref.prefix_check(allow, synthetic_log(pass_one), BASE,
                            1)["prefix_excess"] == 1
    # verdicts applied out of order (two batches swapped)
    swapped = allow.copy()
    swapped[0:7], swapped[7:14] = allow[7:14].copy(), allow[0:7].copy()
    if allow[0:7].sum() != allow[7:14].sum():
        assert ref.prefix_check(allow, synthetic_log(swapped), BASE,
                                0)["prefix_excess"] > 0


def test_unstable_points_are_not_judged():
    allow = np.arange(70) % 2 == 0
    log = synthetic_log(allow)
    log["log_passes"][3] += 5                        # a torn read
    log["log_drops"][3] -= 5
    log["log_stable"][3] = False
    assert ref.prefix_check(allow, log, BASE, 0)["prefix_excess"] == 0
    log["log_stable"][3] = True
    assert ref.prefix_check(allow, log, BASE, 0)["prefix_excess"] > 0


def test_wrong_table_drops_one_exercised_rule():
    w = podrules.build({"n_ids": 64, "n_rules": 512, "port_span": 250})
    flows = w.allowed_flows(np.random.default_rng(0), 300, 20000, 40000)
    per_flow = np.full((300,), 20.0)
    wrong, cell = ref.wrong_table(w, flows, per_flow,
                                  np.random.default_rng(2))
    table, _ = w.table()
    assert table[cell] and not wrong[cell]
    assert (table != wrong).sum() == 1
    lost = ref.expected_allow(w, flows) & ~ref.expected_allow(w, flows, wrong)
    assert lost.any() and (w.cells(flows)[lost] == cell).all()
    none, _ = ref.wrong_table(w, flows, np.zeros((300,)),
                              np.random.default_rng(2))
    assert none is None


def sound_answers(w, flows):
    """What a sound program answers the probe for ``flows`` it has never
    seen: the reference's verdict, the world's reason, no state left by a
    refused flow."""
    want = ref.expected_allow(w, flows)
    return {"allow": want,
            "reason": np.where(want, ref.REASON_OK,
                               ref.refusal_reasons(w, flows)),
            "status": np.where(want, ref.STATUS_ESTABLISHED, ref.STATUS_NEW),
            "ct_full": np.zeros(want.shape, bool)}


def probe(w, flows, out):
    n = flows["sport"].shape[0]
    return ref.probe_check(w, flows, np.zeros((n,), bool),
                           np.ones((n,), bool), np.zeros((n,), bool), out)


@pytest.mark.parametrize("said,instead", [(180, 130), (130, 180)])
def test_a_refusal_under_the_other_reason_is_a_mismatch(said, instead):
    """The world says which reason a refused frame is dropped with: an
    answer that refuses the right frames under the other one (a matcher
    folded into the port lookup; every refusal counted as the request's)
    fails the probe, row for row."""
    w = httprules.build(tiny_config("tiny-l7")["world"])
    rng = np.random.default_rng(7)
    flows = concat([w.allowed_flows(rng, 200, 20000, 40000),
                            w.denied_flows(rng, 300, 20000, 40000)])
    stated = ref.refusal_reasons(w, flows)
    refused = ~ref.expected_allow(w, flows)
    assert (refused & (stated == 130)).sum() >= 30
    assert (refused & (stated == 180)).sum() >= 30
    out = sound_answers(w, flows)
    assert probe(w, flows, out)["probe_mismatched"] == 0
    swapped = dict(out, reason=np.where(out["reason"] == said, instead,
                                        out["reason"]))
    assert probe(w, flows, swapped)["probe_mismatched"] \
        == int((refused & (stated == said)).sum())
    assert not ref.verdict([ref.compare(
        "probe_mismatched", probe(w, flows, swapped)["probe_mismatched"],
        0)])


def test_a_world_without_reasons_means_130_and_a_strange_one_is_refused():
    w = podrules.build(WORLDS[0][1])
    flows = w.denied_flows(np.random.default_rng(0), 50, 20000, 40000)
    assert not hasattr(w, "reasons")
    assert (ref.refusal_reasons(w, flows) == ref.REASON_POLICY).all()
    out = sound_answers(w, flows)
    assert probe(w, flows, out)["probe_mismatched"] == 0
    l7 = dict(out, reason=np.full((50,), ref.REASON_POLICY_L7))
    assert probe(w, flows, l7)["probe_mismatched"] == 50

    class Strange:
        def reasons(self, flows):
            return np.full(flows["sport"].shape, 131)
    with pytest.raises(ValueError):
        ref.refusal_reasons(Strange(), flows)
    assert set(ref.REFUSAL_GAPS) == {ref.REASON_POLICY,
                                     ref.REASON_POLICY_L7}


def test_compare():
    assert ref.compare("a", 0, 0)["ok"] and not ref.compare("a", 1, 0)["ok"]
    assert ref.compare("a", 5, 3, "min")["ok"]
    assert not ref.compare("a", 2, 3, "min")["ok"]
    assert not ref.verdict([ref.compare("a", 0, 0), ref.compare("b", 1, 0)])
