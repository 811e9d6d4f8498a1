"""The HTTP world (``worlds/httprules.py``) and its plain reference.

(a) The reference against a loop over the documents' text, request by
    request: ``str.startswith`` on the path as cut to 64 bytes, the method
    by name, both read back from the bytes each frame carries.
(b) The table and the reasons against the program's oracle on the tiny
    world, row for row.
(c) Every contrast case the world's docstring names, by hand.
(d) ``GET``, ``POST`` and a 70-byte path through ``frames_of`` and the
    shim's mock rings: the harvested columns are what the world states.
(e) A world with no contrast is refused.
"""

import numpy as np
import pytest

from benchmarks import frames, reference as ref
from benchmarks.laws import flowmix
from benchmarks.tests.conftest import tiny_config
from benchmarks.tests.test_frames_direction import (
    EP_V4, EP_V6_WORDS, assert_columns, through_the_shim, traffic_law)
from benchmarks.worlds import httprules

TINY = tiny_config("tiny-l7")["world"]
#: the source's numbers (``build_config4``, full preset): 200 sets from
#: port 80, its three rule forms, peers in 11.0.0.0/8
SOURCE = {k: TINY[k] for k in ("builder", "live_requests", "denied_split",
                               "long_path_share")}
WORLDS = [pytest.param(TINY, id="tiny"), pytest.param(SOURCE, id="source")]


def hand_flows(cases):
    """(port, method, path) triples → a flow set, a peer each."""
    n = len(cases)
    return {"src": frames.v4_words((0x0B000001 + 257 * np.arange(n))
                                   .astype(np.uint32)),
            "sport": (30000 + np.arange(n)).astype(np.int32),
            "dport": np.array([c[0] for c in cases], np.int32),
            "proto": np.full((n,), frames.PROTO_TCP, np.int32),
            "is_v6": np.zeros((n,), bool),
            **httprules.request_columns([c[1:] for c in cases])}


def judge(world, cases):
    """→ (admitted, reason if refused) of each hand-made case, by the
    reference."""
    flows = hand_flows(cases)
    return (ref.expected_allow(world, flows).tolist(),
            ref.refusal_reasons(world, flows).tolist())


# -- (a): the reference against a loop over the documents' text ----------------
def by_the_documents(world, flows):
    """Per flow, from the documents' text and the frame's own bytes: the
    (set, rule) that admits it, or the drop reason."""
    by_port = {}
    for s, doc in enumerate(world.policy_docs()):
        (to,) = doc["ingress"][0]["toPorts"]
        (port,) = to["ports"]
        assert port["protocol"] == "TCP" and "fromEndpoints" not in \
            doc["ingress"][0]
        by_port[int(port["port"])] = (s, to["rules"]["http"])
    out = []
    for dport, payload, plen in zip(flows["dport"].tolist(),
                                    flows["payload"], flows["payload_len"]):
        line = payload[:plen].tobytes().decode()
        method, path, rest = line.split(" ", 2)
        assert rest == "HTTP/1.1\r\nHost: x\r\n\r\n"
        if dport not in by_port:
            out.append((None, 130))
            continue
        s, rules = by_port[dport]
        hit = next((r for r, rule in enumerate(rules)
                    if rule.get("method", method) == method
                    and path[:64].startswith(rule["path"])), None)
        out.append((None, 180) if hit is None else ((s, hit), 0))
    return out


@pytest.mark.parametrize("params", WORLDS)
def test_reference_agrees_with_a_loop_over_the_documents(params):
    w = httprules.build(params)
    rng = np.random.default_rng(5)
    n_requests = w.case.size
    # requests of the catalogue, each on its own port, on a set's drawn
    # anew or on any port at all, and the three kinds as the law draws them
    q = rng.integers(0, n_requests, 7000)
    u = rng.random(7000)
    ports = np.where(u < 0.5, w.first_port + rng.integers(
        0, w.n_rulesets, 7000), rng.integers(1, 40000, 7000))
    ports = np.where(u < 0.2, w.first_port + w._req_set[q], ports)
    flows = frames.concat([
        w._requests(rng, q, 20000, 40000, ports),
        w.allowed_flows(rng, 2000, 20000, 40000),
        w.denied_flows(rng, 1500, 20000, 40000),
        w.unknown_flows(rng, 500, 20000, 40000)])
    assert flows["sport"].shape[0] == 11000
    cell = w.cells(flows)
    want = ref.expected_allow(w, flows)
    reason = ref.refusal_reasons(w, flows)
    R = len(w.rules)
    admitted = refused = 0
    for c, ok, why, (hit, drop) in zip(cell.tolist(), want.tolist(),
                                       reason.tolist(),
                                       by_the_documents(w, flows)):
        if hit is None:
            assert c == -1 and not ok and why == drop
            refused += 1
        else:
            assert ok and (c // R, c % R) == hit
            admitted += 1
    assert admitted >= 2400 and refused >= 2400
    assert want[7000:9000].all() and not want[9000:].any()
    assert (reason[9000:][cell[9000:] < 0] >= 130).all()
    assert set(reason[9000:10500].tolist()) == {130, 180}
    assert set(reason[10500:].tolist()) == {130}


def test_the_table_has_a_cell_a_rule_and_each_admits_alone():
    w = httprules.build(TINY)
    table, cover = w.table()
    docs = w.policy_docs()
    rules = sum(len(d["ingress"][0]["toPorts"][0]["rules"]["http"])
                for d in docs)
    assert len(docs) == 16 and table.shape == (rules,) and table.all()
    assert (cover == 1).all()
    # two rule forms that can admit one request cover each other
    shared = httprules.build(dict(TINY, rules=TINY["rules"] + [
        {"method": "GET", "path": "/api/v{i}/deep"}],
        live_requests=[0.7, 0.1, 0.1, 0.1]))
    cover = shared.table()[1].reshape(16, 4)
    assert (cover[:, [0, 3]] == 2).all() and (cover[:, 1:3] == 1).all()


def test_the_method_numbers_are_the_programs():
    from cilium_tpu.utils import constants as C
    assert httprules.METHODS == C.HTTP_METHODS
    assert httprules.PATH_CUT == C.L7_PATH_MAXLEN
    assert (httprules.REASON_POLICY, httprules.REASON_POLICY_L7) \
        == (int(C.DropReason.POLICY), int(C.DropReason.POLICY_L7)) \
        == (ref.REASON_POLICY, ref.REASON_POLICY_L7)


# -- (b): against the program's oracle, row for row ----------------------------
@pytest.fixture(scope="module")
def oracle_engine():
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    w = httprules.build(TINY)
    eng = Engine(DaemonConfig(ct_capacity=1 << 16, auto_regen=False),
                 datapath=FakeDatapath(DaemonConfig(ct_capacity=1 << 16)))
    try:
        w.load(eng)
        eng.regenerate()
        yield w, eng
    finally:
        eng.stop()


def oracle_says(w, eng, flows):
    ep_slot = eng.active.snapshot.ep_slot_of[w.ep_id]
    out = eng.classify(frames.columns_of(flows, w.ep_v4, w.ep_v6_words,
                                         ep_slot))
    return (np.asarray(out["allow"]).astype(bool),
            np.asarray(out["reason"]).astype(np.int64))


def test_table_and_reasons_against_the_programs_oracle(oracle_engine):
    w, eng = oracle_engine
    rng = np.random.default_rng(9)
    every = np.arange(w.case.size)                  # the whole catalogue
    flows = frames.concat([
        w._requests(rng, every, 20000, 40000),
        w.allowed_flows(rng, 1500, 20000, 40000),
        w.denied_flows(rng, 1000, 20000, 40000),
        w.unknown_flows(rng, 300, 20000, 40000)])
    want = ref.expected_allow(w, flows)
    allow, reason = oracle_says(w, eng, flows)
    assert (allow == want).all(), np.nonzero(allow != want)[0][:10]
    assert (reason[want] == ref.REASON_OK).all()
    stated = ref.refusal_reasons(w, flows)
    assert (reason[~want] == stated[~want]).all()
    assert {130, 180} <= set(reason[~want].tolist())
    assert np.unique(w.cells(flows)[want]).size == 16 * 3


# -- (c): the contrast cases, by hand -------------------------------------------
LONG = "/seg-0123456789abcdef" * 4                  # 84 bytes


def case_across_a_number():
    # port 81's set holds GET /api/v1: a proper prefix of /api/v12/x
    return [(81, "GET", "/api/v12/x"), (87, "PUT", "/public/70/a")], \
        [True, True]


def case_shorter_than_the_rule():
    return [(81, "GET", "/api/v"), (92, "GET", "/api/v1"),
            (85, "POST", "/submit/"), (85, "GET", "/")], [180] * 4


def case_the_rules_own_path():
    return [(81, "GET", "/api/v1"), (85, "POST", "/submit/5"),
            (95, "HEAD", "/public/15")], [True] * 3


def case_right_path_wrong_method():
    return [(83, "POST", "/api/v3/x"), (83, "GET", "/submit/3"),
            (83, "PUT", "/api/v3/x")], [180] * 3


def case_right_request_on_another_ports_set():
    return [(80, "GET", "/api/v3/x"), (83, "GET", "/api/v0/x"),
            (81, "POST", "/submit/2/x"), (93, "GET", "/public/7/asset.js")], \
        [180] * 4


def case_any_method_on_public():
    return [(87, m, "/public/7/asset.js") for m in httprules.METHODS], \
        [True] * 9


def case_over_64_bytes_decided_by_the_first_64():
    return [(84, "GET", "/api/v4" + LONG), (84, "GET", "/api/vx4" + LONG),
            (84, "GET", LONG[:63] + "/api/v4")], [True, 180, 180]


def case_forbidden_zone():
    return [(80 + i, m, "/forbidden/zone") for i in (0, 7, 15)
            for m in ("GET", "POST")], [180] * 6


def case_a_port_no_document_names():
    return [(79, "GET", "/api/v0/x"), (96, "GET", "/api/v16/x"),
            (8080, "GET", "/public/1/asset.js")], [130] * 3


CASES = [case_across_a_number, case_shorter_than_the_rule,
         case_the_rules_own_path, case_right_path_wrong_method,
         case_right_request_on_another_ports_set, case_any_method_on_public,
         case_over_64_bytes_decided_by_the_first_64, case_forbidden_zone,
         case_a_port_no_document_names]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_contrast_case_by_hand(case, oracle_engine):
    """Each answer is written out above: True for admitted, else the drop
    reason. The reference, the loop over the documents and the program's
    oracle all have to give it."""
    w, eng = oracle_engine
    cases, answers = case()
    flows = hand_flows(cases)
    want, why = judge(w, cases)
    got = [True if ok else r for ok, r in zip(want, why)]
    assert got == answers
    by_docs = [True if hit is not None else drop
               for hit, drop in by_the_documents(w, flows)]
    assert by_docs == answers
    allow, reason = oracle_says(w, eng, flows)
    said = [True if a else int(r) for a, r in zip(allow, reason)]
    assert said == answers


@pytest.mark.parametrize("params", WORLDS)
def test_every_world_holds_every_contrast_case_and_sends_it(params):
    w = httprules.build(params)
    for c in httprules.ADMITTED + httprules.REFUSED:
        assert (w.case == c).sum() >= w.n_rulesets, c
    # the catalogue's cases are what their names say
    req, sets = w._req, w._req_set
    path = [bytes(p).rstrip(b"\0").decode() for p in req["http_path"]]
    for q in np.nonzero(w.case == "across")[0][:50].tolist():
        rule = next(p.format(i=sets[q]) for _m, p in w.rules
                    if path[q].startswith(p.format(i=sets[q])))
        assert path[q][len(rule)].isdigit()          # across a number
    for q in np.nonzero(w.case == "long")[0][:50].tolist():
        assert req["payload_len"][q] - len(" HTTP/1.1\r\nHost: x\r\n\r\n") \
            - len(httprules.METHODS[req["http_method"][q]]) - 1 > 64
    # the tiny cell's window is some 60,000 frames: the heaviest live
    # flows are admitted contrast cases, every refused request is a
    # contrast case, both reasons are there, single-cover rules are used
    mix = flowmix.generate(traffic_law(), w, np.random.default_rng(2),
                           2000, 60000)
    flows, kind = mix["flows"], mix["kind"]
    want = ref.expected_allow(w, flows)
    assert want[kind <= flowmix.KIND_NEW_ALLOWED].all()
    assert not want[kind >= flowmix.KIND_NEW_DENIED].any()
    key = {(int(s), int(m), bytes(p)): c for s, m, p, c in zip(
        sets, req["http_method"], req["http_path"], w.case)}
    case_of = np.array([key.get((int(d) - w.first_port, int(m), bytes(p)),
                                "unnamed")
                        for d, m, p in zip(flows["dport"],
                                           flows["http_method"],
                                           flows["http_path"])])
    heavy = case_of[:250]
    assert set(heavy.tolist()) == set(httprules.ADMITTED[1:])
    denied = case_of[kind == flowmix.KIND_NEW_DENIED]
    assert set(denied.tolist()) == set(httprules.REFUSED) | {"unnamed"}
    why = ref.refusal_reasons(w, flows)
    per_flow = np.bincount(mix["sched_flow"], minlength=want.size)
    assert per_flow[~want & (why == 180)].sum() >= 500
    assert per_flow[~want & (why == 130)].sum() >= 500
    assert per_flow[:250].sum() >= 0.5 * per_flow[:2000].sum()
    cell = w.cells(flows)
    per_cell = np.bincount(cell[want], weights=per_flow[want],
                           minlength=w.table()[0].size)
    assert ((w.table()[1] == 1) & (per_cell >= 16)).sum() >= 8


# -- (d): through the shim -------------------------------------------------------
def test_requests_reach_the_tokenizer_as_the_world_states_them():
    w = httprules.build(TINY)
    cases = [(81, "GET", "/api/v1/x"), (85, "POST", "/submit/5/x"),
             (84, "GET", httprules._stretch("/api/v4", 70)),
             (84, "DELETE", httprules._stretch("/public/4", 96)),
             (87, "CONNECT", "/public/7/asset.js"), (80, "GET", "/")]
    flows = hand_flows(cases)
    assert (flows["http_path"][2] != 0).all()        # cut at 64 bytes
    assert bytes(flows["http_path"][2]) \
        == httprules._stretch("/api/v4", 70).encode()[:64]
    rng = np.random.default_rng(4)
    drawn = frames.concat([w.allowed_flows(rng, 120, 20000, 40000),
                           w.denied_flows(rng, 60, 20000, 40000),
                           w.unknown_flows(rng, 20, 20000, 40000)])
    flows = frames.concat([flows, drawn])
    table, lens = frames.frames_of(flows, EP_V4, EP_V6_WORDS)
    assert table.shape[1] == 208 and (lens == 54 + flows["payload_len"]).all()
    want = frames.columns_of(flows, EP_V4, EP_V6_WORDS, 0)
    assert set(want["http_method"].tolist()) >= {0, 1, 3, 8}
    assert (want["direction"] == frames.DIR_INGRESS).all()
    assert_columns(through_the_shim(flows), want)


# -- (e): a world with no contrast is refused -------------------------------------
@pytest.mark.parametrize("change", [
    {"n_rulesets": 1},                               # no other port's set
    {"rules": [{"path": "/public/{i}"}], "live_requests": [1.0]},
    {"rules": [{"method": "GET", "path": "/api/v{i}"},
               {"method": "POST", "path": "/submit/{i}"}],
     "live_requests": [0.5, 0.5]},                   # no any-method rule
    {"live_requests": [0.5, 0.5]},                   # not one a rule form
    {"denied_split": [0.5, 0.6]},
    {"rules": [{"method": "BREW", "path": "/pot/{i}"}],
     "live_requests": [1.0]},
    {"first_port": 19000},                           # into the unnamed ports
], ids=["one-set", "no-method-rule", "no-any-method-rule", "shares-a-form",
        "split-over-one", "unknown-method", "ports-overlap"])
def test_parameters_that_leave_no_contrast_are_refused(change):
    with pytest.raises(ValueError):
        httprules.build(dict(TINY, **change))
