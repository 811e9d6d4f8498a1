"""The mixed node (``worlds/mixednode.py``: east-west L3/L4 over v4 and v6,
egress through the prefix table and a service, HTTP rule sets, on one
endpoint) and its plain reference, at test size (``tiny-mixed``).

(a) The reference against a loop over the documents' own text, flow by
    flow: ``ipaddress`` containment over the whole ipcache (the pods' /32s
    and /128s, the listed prefixes, every prefix a document names), the
    group labels, ``str.startswith`` on the request's path as the frame
    carries it.
(b) The table and the reasons against the program's oracle, row for row.
(c) Every case in which planes meet, its answer written out by hand here:
    the reference, the loop and the oracle all have to give it.
(d) Frames of both directions, both families and requests through
    ``frames_of``, the shim's mock rings and its tokenizer in one harvest.
(e) ``tiny-mixed.saturate`` through ``run_cell`` on the jitted datapath:
    correct, the control caught, both reasons' totals held, the readers of
    the wire's counters read; a verdict flipped in one plane's rows alone
    comes out not correct.
(f) A world that leaves a meeting case out is refused.
"""

import collections
import copy
import importlib.util
import ipaddress
import json
import os
import time

import numpy as np
import pytest

from benchmarks import frames, harness, reference as ref
from benchmarks.laws import flowmix
from benchmarks.tests.conftest import DATA, REPO, tiny_config
from benchmarks.tests.test_frames_direction import (
    EP_V4, EP_V6_WORDS, assert_columns, through_the_shim, traffic_law)
from benchmarks.worlds import mixednode

TINY = tiny_config("tiny-mixed")["world"]
CELL = "tiny-mixed.saturate"
NEW_READERS = ("datapath.wire_bytes_per_row", "datapath.wire_needed_share",
               "kernels.lpm_dualstack_hbm_share")
#: what the documents give each meeting case: True for admitted, else the
#: drop reason. Written out here, not read from the world
ANSWERS = {
    "a_pod_request": True,
    "a_pod_request_refused": 180,
    "b_listed_peer": True,
    "b_listed_peer_refused": 180,
    "c_pod_in_listed_prefix": True,
    "d_one_past_the_pods": 130,
    "e_egress_on_an_ingress_port": True,
    "e_ingress_from_an_egress_prefix": 130,
    "f_v6_pod_udp": True,
    "f_v6_pod_udp_refused": 130,
    "g_egress_to_a_pod": 130,
    "g_egress_past_the_pods": True,
}


@pytest.fixture(scope="module")
def world():
    return mixednode.build(TINY)


def judged(world, flows):
    """→ per flow, True or the drop reason, by the reference."""
    return [True if ok else int(why) for ok, why in zip(
        ref.expected_allow(world, flows), ref.refusal_reasons(world, flows))]


# -- (a): the reference against a loop over the documents' text ----------------
class Documents:
    """The deployment as text: what ``load`` hands the program, read back
    with ``ipaddress`` and plain dicts; nothing of the world's numpy."""

    def __init__(self, world):
        net = ipaddress.ip_network
        self.pod_group = {}                        # address → group label
        for i, v4, v6 in world.pod_addresses():
            group = f"g{i % world.a.groups}"
            self.pod_group[net(v4).network_address] = group
            if v6:
                self.pod_group[net(v6).network_address] = group
        self.ipcache = {net(p): net(q) for p, q in world.b.listed()}
        self.l4, self.http, self.selectors = set(), {}, []
        self.frontends = {}
        services = {s.name: s for s in world.b.services()}
        for doc in world.policy_docs():
            assert doc["endpointSelector"] == {"matchLabels": {"app": "web"}}
            for rule in doc.get("ingress", ()):
                (to,) = rule["toPorts"]
                (port,) = to["ports"]
                if "rules" in to:
                    assert "fromEndpoints" not in rule \
                        and port["protocol"] == "TCP"
                    self.http[int(port["port"])] = to["rules"]["http"]
                else:
                    (sel,) = rule["fromEndpoints"]
                    self.l4.add((sel["matchLabels"]["group"],
                                 int(port["port"]), port["protocol"]))
            for rule in doc.get("egress", ()):
                for c in rule.get("toCIDR", ()):
                    self.selectors.append((net(c), ()))
                for cs in rule.get("toCIDRSet", ()):
                    self.selectors.append((net(cs["cidr"]), tuple(
                        net(x) for x in cs["except"])))
                for ts in rule.get("toServices", ()):
                    svc = services[ts["k8sService"]["serviceName"]]
                    self.selectors += [(net(b.addr + "/32"), ())
                                       for b in svc.lb_backends]
        for cidr, excepts in self.selectors:
            for p in (cidr, *excepts):
                self.ipcache[p] = p
        for svc in services.values():
            for fe in svc.frontends:
                self.frontends[(ipaddress.ip_address(fe.addr), fe.port)] \
                    = ipaddress.ip_address(svc.lb_backends[0].addr)

    def egress(self, ip):
        """Admitted iff the longest prefix holding ``ip`` carries a CIDR
        identity some document's selector holds; a pod's /32 or /128 is
        the longest there is, and carries none."""
        if ip in self.pod_group:
            return False
        best = None
        for p in self.ipcache:
            if p.version == ip.version and ip in p \
                    and (best is None or p.prefixlen > best.prefixlen):
                best = p
        if best is None:
            return False
        labelled = self.ipcache[best]
        return any(labelled.subnet_of(c)
                   and not any(labelled.subnet_of(x) for x in xs)
                   for c, xs in self.selectors)

    def judge(self, flows):
        out = []
        for src, sport, dport, proto, v6, egress, payload, plen in zip(
                flows["src"], flows["sport"].tolist(),
                flows["dport"].tolist(), flows["proto"].tolist(),
                flows["is_v6"].tolist(), flows["egress"].tolist(),
                flows["payload"], flows["payload_len"].tolist()):
            words = [int(x) for x in src]
            ip = ipaddress.ip_address(
                (words[0] << 96) | (words[1] << 64) | (words[2] << 32)
                | words[3]) if v6 else ipaddress.ip_address(words[3])
            if egress:
                ip = self.frontends.get((ip, dport), ip) if proto == 6 \
                    else ip
                out.append(True if self.egress(ip) else 130)
            elif proto == 6 and dport in self.http:
                method, path, _rest = payload[:plen].tobytes().decode() \
                    .split(" ", 2)
                hit = any(rule.get("method", method) == method
                          and path[:64].startswith(rule["path"])
                          for rule in self.http[dport])
                out.append(True if hit else 180)
            else:
                group = self.pod_group.get(ip)
                name = {6: "TCP", 17: "UDP"}.get(proto)
                out.append(True if (group, dport, name) in self.l4 else 130)
        return out


def crossovers(world, rng, n):
    """Flows no draw of the world's makes: any pod to any port around the
    rules' (a frame without a request to a port with a set is no flow of
    this deployment: the program admits it unmatched, as Cilium hands a
    segment without one to the proxy), pods with requests their port's set
    refuses, peers of every kind to the pods' ports, egress to pods, to
    their neighbours and to anywhere."""
    a, c = world.a, world.c
    pods = rng.integers(0, a.n_ids, n)
    ports = 1000 + rng.integers(-30, a.port_span + 20, n)
    from_pods = world.pod_flows(pods, 30000 + np.arange(n), ports,
                                np.where(rng.random(n) < 0.3, 17, 6))
    req = world._from_pods(rng, c.denied_flows(rng, n, 30000, 40000))
    near = world.pod_base + rng.integers(-50, a.n_ids + 50, n)
    anywhere = rng.integers(0x01000000, 0xDF000000, n)
    return frames.concat([
        from_pods, world.whole(req),
        world.peer_flows(near, 31000 + np.arange(n), ports),
        world.peer_flows(near, 32000 + np.arange(n), 443, egress=True),
        world.peer_flows(anywhere, 33000 + np.arange(n),
                         rng.integers(1, 65535, n), egress=True)])


def test_reference_agrees_with_a_loop_over_the_documents(world):
    rng = np.random.default_rng(5)
    flows = frames.concat([
        world.allowed_flows(rng, 4000, 20000, 40000),
        world.denied_flows(rng, 2500, 20000, 40000),
        world.unknown_flows(rng, 500, 20000, 40000),
        crossovers(world, rng, 700),
        *[f for f, _answer in world.meeting.values()]])
    n = flows["sport"].shape[0]
    assert n >= 10000
    got = judged(world, flows)
    want = Documents(world).judge(flows)
    wrong = [i for i in range(n) if got[i] != want[i]]
    assert not wrong, [(i, got[i], want[i]) for i in wrong[:10]]
    plane = world.plane_of(flows)
    for k in range(3):                  # every plane, both ways
        here = [got[i] for i in np.nonzero(plane == k)[0]]
        assert here.count(True) >= 700 and len(here) - here.count(True) \
            >= 500, k
    assert got.count(180) >= 300 and got.count(130) >= 2000
    # an admitted flow's cell lies in its own plane's part of the table
    cell = world.cells(flows)
    start = np.array([world.offset[p] for p in mixednode.PLANES]
                     + [world.table()[0].size])
    ok = cell >= 0
    assert ((cell[ok] >= start[plane[ok]])
            & (cell[ok] < start[plane[ok] + 1])).all()


def test_the_joined_table_is_the_three_tables_end_to_end(world):
    allowed, cover = world.table()
    parts = [w.table() for w in (world.a, world.b, world.c)]
    assert allowed.size == sum(p[0].size for p in parts) == cover.size
    for name, (part_allowed, part_cover) in zip(mixednode.PLANES, parts):
        at = world.offset[name]
        assert (allowed[at:at + part_allowed.size] == part_allowed).all()
        assert (cover[at:at + part_cover.size] == part_cover).all()
    docs = world.policy_docs()
    assert len(docs) == len(world.a.policy_docs()) \
        + len(world.b.policy_docs()) + len(world.c.policy_docs())
    # the control has single-cover rules to take out in every plane, and
    # the window uses them
    mix = flowmix.generate(traffic_law(), world, np.random.default_rng(2),
                           2000, 60000)
    cell = world.cells(mix["flows"])
    per_flow = np.bincount(mix["sched_flow"], minlength=cell.size)
    per_cell = np.bincount(cell[cell >= 0], weights=per_flow[cell >= 0],
                           minlength=allowed.size)
    used = (cover == 1) & (per_cell >= 16)
    start = [world.offset[p] for p in mixednode.PLANES] + [allowed.size]
    assert all(used[start[k]:start[k + 1]].sum() >= 3 for k in range(3))


# -- (b): against the program's oracle, row for row ----------------------------
@pytest.fixture(scope="module")
def oracle_engine(world):
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    eng = Engine(DaemonConfig(ct_capacity=1 << 16, auto_regen=False),
                 datapath=FakeDatapath(DaemonConfig(ct_capacity=1 << 16)))
    try:
        world.load(eng)
        eng.regenerate()
        yield eng
    finally:
        eng.stop()


def oracle_says(world, eng, flows):
    ep_slot = eng.active.snapshot.ep_slot_of[world.ep_id]
    out = eng.classify(frames.columns_of(flows, world.ep_v4,
                                         world.ep_v6_words, ep_slot))
    return [True if a else int(r) for a, r in zip(
        np.asarray(out["allow"]).astype(bool), np.asarray(out["reason"]))]


def test_table_and_reasons_against_the_programs_oracle(world, oracle_engine):
    rng = np.random.default_rng(9)
    flows = frames.concat([
        world.allowed_flows(rng, 3000, 20000, 40000),
        world.denied_flows(rng, 2000, 20000, 40000),
        world.unknown_flows(rng, 500, 20000, 40000),
        crossovers(world, rng, 300)])
    got = judged(world, flows)
    said = oracle_says(world, oracle_engine, flows)
    wrong = [i for i in range(len(got)) if got[i] != said[i]]
    assert not wrong, [(i, got[i], said[i]) for i in wrong[:10]]
    assert {True, 130, 180} == set(got)
    cells = world.cells(flows)[np.array([g is True for g in got])]
    start = [world.offset[p] for p in mixednode.PLANES]
    assert all(((cells >= lo)).any() for lo in start)


# -- (c): the cases in which planes meet, by hand ------------------------------
@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_meeting_case_by_hand(name, world, oracle_engine):
    flows, stated = world.meeting[name]
    n = flows["sport"].shape[0]
    assert stated == ANSWERS[name] and n >= 1
    assert judged(world, flows) == [ANSWERS[name]] * n
    assert Documents(world).judge(flows) == [ANSWERS[name]] * n
    assert oracle_says(world, oracle_engine, flows) == [ANSWERS[name]] * n


def test_the_meeting_cases_are_what_their_names_say(world):
    m, e = world.meeting, world.b.ipcache
    assert set(m) == set(ANSWERS)
    pod = world.pod_of
    # a: a pod's address, a request, a port with a set
    for name in ("a_pod_request", "a_pod_request_refused"):
        f = m[name][0]
        assert (pod(f) >= 0).all() and (f["payload_len"] > 0).all() \
            and (world.set_of(f) >= 0).all()
    # b: no pod's, under a listed prefix of the peers' own net
    f = m["b_listed_peer"][0]
    assert (pod(f) < 0).all() and (f["src"][:, 3] >> 24 == 11).all()
    held = e.longest(f["src"][:, 3].astype(np.int64))
    assert (held >= 0).all() and (e.plen[held] > 8).all()
    # c, d: the last pods and the address one past them, inside the anchor
    c, d = m["c_pod_in_listed_prefix"][0], m["d_one_past_the_pods"][0]
    lo, plen = world.anchor
    for f in (c, d):
        assert ((f["src"][:, 3].astype(np.int64) >> (32 - plen))
                == lo >> (32 - plen)).all()
    assert (pod(c) >= 0).all() and (pod(d) < 0).all()
    assert int(d["src"][0, 3]) == int(c["src"][:, 3].max()) + 1
    assert d["dport"][0] in c["dport"]
    # e: one destination, out on the pods' ports and in from it
    out, back = m["e_egress_on_an_ingress_port"][0], \
        m["e_ingress_from_an_egress_prefix"][0]
    assert out["egress"].all() and not back["egress"].any()
    assert (out["src"] == back["src"]).all()
    assert ((out["dport"] >= 1000)
            & (out["dport"] < 1000 + world.a.port_span)).all()
    # f: over v6 and UDP
    for name in ("f_v6_pod_udp", "f_v6_pod_udp_refused"):
        f = m[name][0]
        assert f["is_v6"].all() and (f["proto"] == 17).all()
    # g: a pod's address and the one past the pods, both left for
    g, past = m["g_egress_to_a_pod"][0], m["g_egress_past_the_pods"][0]
    assert g["egress"].all() and past["egress"].all()
    assert (pod(g) >= 0).all() and (pod(past) < 0).all()


def test_the_traffic_holds_the_meeting_cases_and_every_plane(world):
    mix = flowmix.generate(traffic_law(), world, np.random.default_rng(2),
                           2000, 60000)
    flows, kind = mix["flows"], mix["kind"]
    want = ref.expected_allow(world, flows)
    assert want[kind <= flowmix.KIND_NEW_ALLOWED].all()
    assert not want[kind >= flowmix.KIND_NEW_DENIED].any()
    # the ten heaviest ranks are the admitted meeting cases, their planes
    # in HEAD_TURN's order
    assert world.plane_of(frames.take(flows, slice(0, 10))).tolist() \
        == list(mixednode.HEAD_TURN)
    admitted = frames.concat([f for name, (f, _a) in world.meeting.items()
                              if ANSWERS[name] is True])
    key = {(tuple(s), int(d), int(p), bool(e)) for s, d, p, e in zip(
        admitted["src"].tolist(), admitted["dport"], admitted["proto"],
        admitted["egress"])}
    head = frames.take(flows, slice(0, 10))
    assert {(tuple(s), int(d), int(p), bool(e)) for s, d, p, e in zip(
        head["src"].tolist(), head["dport"], head["proto"],
        head["egress"])} == key
    # the refused ones are among the new flows that are denied
    denied = frames.take(flows, kind == flowmix.KIND_NEW_DENIED)
    refused = frames.concat([f for name, (f, _a) in world.meeting.items()
                             if ANSWERS[name] is not True])
    have = {(tuple(s), int(d)) for s, d in zip(denied["src"].tolist(),
                                               denied["dport"])}
    assert {(tuple(s), int(d)) for s, d in zip(
        refused["src"].tolist(), refused["dport"])} <= have
    # the frames' planes are the flows' shares, within a few per cent, and
    # a quarter of A's are v6, a tenth UDP, a tenth of B's to the frontend
    plane = world.plane_of(flows)
    per_flow = np.bincount(mix["sched_flow"], minlength=plane.size)
    share = np.array([per_flow[plane == k].sum() for k in range(3)]) \
        / per_flow.sum()
    assert np.abs(share - world.shares).max() < 0.04, share
    live = np.arange(plane.size) < 2000
    in_a = live & (plane == 0)
    assert 0.15 < flows["is_v6"][in_a].mean() < 0.35
    assert 0.04 < (flows["proto"][in_a] == 17).mean() < 0.2
    why = ref.refusal_reasons(world, flows)
    assert per_flow[~want & (why == 180)].sum() >= 100
    assert per_flow[~want & (why == 130)].sum() >= 500
    # requests come from pods and from the peers' net
    req = live & (plane == 2)
    from_pod = world.pod_of(flows) >= 0
    assert 0.1 < from_pod[req].mean() < 0.45


# -- (d): through the shim -------------------------------------------------------
def test_every_kind_of_frame_in_one_harvest(world):
    rng = np.random.default_rng(4)
    flows = frames.concat([
        *[f for f, _answer in world.meeting.values()],
        world.allowed_flows(rng, 120, 20000, 40000),
        world.denied_flows(rng, 60, 20000, 40000),
        world.unknown_flows(rng, 20, 20000, 40000)])
    n = flows["sport"].shape[0]
    assert n <= 256
    table, lens = frames.frames_of(flows, EP_V4, EP_V6_WORDS)
    header = np.where(flows["is_v6"], 54, 34) \
        + np.where(flows["proto"] == 17, 8, 20)
    assert table.shape[1] == 208 and (lens == header
                                      + flows["payload_len"]).all()
    plain = flows["payload_len"] == 0
    assert set(lens[plain].tolist()) == {42, 54, 62, 74}
    assert lens[~plain].min() >= 75 and lens[~plain].max() <= 180
    kinds = {(bool(v6), bool(out), bool(p > 0)) for v6, out, p in zip(
        flows["is_v6"], flows["egress"], flows["payload_len"])}
    assert kinds == {(False, False, False), (False, False, True),
                     (False, True, False), (True, False, False)}
    want = frames.columns_of(flows, EP_V4, EP_V6_WORDS, 0)
    assert (want["http_method"][plain] == 255).all()
    assert set(want["direction"].tolist()) == {frames.DIR_EGRESS,
                                               frames.DIR_INGRESS}
    assert_columns(through_the_shim(flows), want)


# -- (e): the tiny cell through run_cell -----------------------------------------
@pytest.fixture(scope="module")
def mixed_manifest():
    """The tests' manifest with the tiny cell in it, as the README's
    "Adding things" has a later PR add one: entries appended, in memory."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {e["name"]: e for e in json.load(f)["per_layer"]}
    m["configs"].append({
        "name": "tiny-mixed", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/tests/data/configs/tiny-mixed.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-mixed",
                           "traffic": "saturate", "chips": 1, "why": "test"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny-l7.saturate" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    for name in NEW_READERS + ("l7.checked_share", "lb.translated_share",
                               "host.flow_hashes_per_row"):
        m["per_layer"].append(dict(copy.deepcopy(real[name]),
                                   workloads=[CELL]))
    return m


def run(manifest, seed, seconds=1.5, **kw):
    from benchmarks.nic import nicgen
    nicgen.build_shim()
    nicgen.build()
    cell = harness.resolve_cell(manifest, CELL, data_root=DATA)
    return cell, harness.run_cell(cell, seed, seconds, False,
                                  time.monotonic(), **kw)


def numbers(result):
    return {n["name"]: n for n in result["numbers"]}


@pytest.fixture(scope="module")
def mixed_run(mixed_manifest):
    return run(mixed_manifest, 3000000042)


def test_the_tiny_cell_is_correct_and_its_control_is_caught(mixed_run):
    cell, r = mixed_run
    n = numbers(r)
    assert r["correct"], [x for x in r["numbers"] if not x["ok"]]
    assert r["failed"] == 0 and r["attempted"] > 1000
    for name in ("unverdicted", "prefix_excess", "passed_gap",
                 "reason_policy_gap", "reason_policy_l7_gap",
                 "probe_mismatched", "fill_table_gap", "fill_denied"):
        assert n[name]["value"] == 0, name
    assert n["probe_rows"]["value"] >= 64
    assert r["refused_for"]["reason_policy_gap"] >= 100
    assert r["refused_for"]["reason_policy_l7_gap"] >= 20
    assert r["control"]["caught"] is True
    assert r["control"]["passed_gap"] == r["control"]["frames_on_it"] >= 16
    assert r["compiles"]["in_window"] == 0
    assert set(r["metrics"]) == set(cell.e2e) \
        == {"verdicts_per_s", "setup_s"}


def test_the_wire_readers_read_the_tiny_cell(mixed_run):
    _cell, r = mixed_run
    also = r["also"]
    # every row of a full batch rides the 12-word wire, and the dictionary
    # beside it; a padded bucket's rows cost the same and verdict nothing
    assert also["datapath.wire_bytes_per_row"]["value"] > 48
    assert also["datapath.wire_bytes_per_row"]["unit"] == "bytes/row"
    share = also["datapath.wire_needed_share"]["value"]
    # about half the rows need 16 bytes, an eighth 44 (the v6 quarter of
    # A's half), a fifth 20 and a dictionary: under a half of 48 and more
    assert 0.15 < share < 0.55, share
    assert 0.1 < also["l7.checked_share"]["value"] < 0.3
    assert 0.005 < also["lb.translated_share"]["value"] < 0.1
    assert also["host.flow_hashes_per_row"]["value"] < 1.5
    # no device plane in a CPU run: the walk's share finds nothing to read
    assert "kernels.lpm_dualstack_hbm_share" not in also


def flip_one_plane(plane):
    """``break_path``: in every 20th harvest that holds one, the verdict of
    one row of ``plane`` (0: ingress without a request, 1: egress, 2: a
    request) is turned over where the shim applies it; no other row is
    touched."""
    def break_path(eng, shim):
        poll, apply = shim.poll_batch, shim.apply_verdicts
        harvested, seen = collections.deque(), [0]

        def poll_batch(*args, **kw):
            got = poll(*args, **kw)
            if got is not None:
                n = int(shim.last_poll_rows)
                of = np.where(np.asarray(got["direction"][:n])
                              == frames.DIR_EGRESS, 1,
                              np.where(np.asarray(got["http_method"][:n])
                                       != 255, 2, 0))
                harvested.append(of)
            return got

        def apply_verdicts(allow):
            allow = np.array(allow, dtype=bool)
            of = harvested.popleft()
            rows = np.nonzero(of[:allow.size] == plane)[0]
            seen[0] += 1
            if seen[0] % 20 == 0 and rows.size:
                allow[rows[0]] = ~allow[rows[0]]
            apply(allow)
        shim.poll_batch, shim.apply_verdicts = poll_batch, apply_verdicts
    return break_path


@pytest.mark.parametrize("plane", [0, 1, 2],
                         ids=["east-west", "egress", "http"])
def test_a_verdict_flipped_in_one_planes_rows_is_not_correct(
        mixed_manifest, plane):
    _cell, r = run(mixed_manifest, 11 + plane,
                   break_path=flip_one_plane(plane))
    n = numbers(r)
    assert not r["correct"]
    assert n["prefix_excess"]["value"] > 0 and not n["prefix_excess"]["ok"]
    assert n["unverdicted"]["value"] == 0             # still one per frame


def test_the_walks_share_over_a_recorded_trace(tmp_path, monkeypatch):
    """``kernels.lpm_dualstack_hbm_share`` over the trace recorded on the
    chip for ``lpm100k-zipf``: the v4-only reader's bytes, each row's by
    its family."""
    from benchmarks.tests.test_lpm_trace import reader, recorded_run
    run_, batches = recorded_run(tmp_path, "lpm100k.xplane.pb",
                                 "lpm100k.spans.json", monkeypatch)
    dual = reader("kernels.lpm_dualstack_hbm_share")
    assert dual(run_) is None                        # no such counter
    rows = run_.stats1["pipeline"]["verdict_rows"]
    run_.stats0["pipeline"]["verdict_rows"]["wide_needed"] = 0
    rows["wide_needed"] = 0
    v4_only = reader("kernels.lpm_hbm_share")(run_)
    assert dual(run_) == pytest.approx(v4_only)
    rows["wide_needed"] = rows["total"] // 4         # a quarter over v6
    assert dual(run_) == pytest.approx(v4_only * (0.75 * 4 + 0.25 * 16) / 4)
    assert 0 < dual(run_) < 0.01


# -- (f): a world that leaves a meeting case out is refused ------------------------
def changed(**blocks):
    params = copy.deepcopy(TINY)
    for name, change in blocks.items():
        if isinstance(change, dict):
            params[name].update(change)
        else:
            params[name] = change
    return params


@pytest.mark.parametrize("params", [
    changed(http={"first_port": 1000}),              # C's ports on A's
    changed(east_west={"port_span": 7100}),          # A's ports on 8000
    changed(pod_anchor_from="223.0.0.0"),            # no prefix to end in
    changed(pod_anchor_from="150.0.0.0"),            # outside the cover
    changed(plane_shares=[0.6, 0.4, 0.0]),
    changed(plane_shares=[0.5, 0.3, 0.3]),
    changed(pod_requesters=0.0),
    changed(east_west={"builder": "podrules"}),
    changed(east_west={"n_rules": 2}),               # the last pods' groups
    #                                                  have no rule at all
], ids=["ports-http-on-l4", "ports-l4-on-service", "no-anchor",
        "anchor-uncovered", "a-plane-left-out", "shares-over-one",
        "no-pod-requesters", "another-builder", "too-few-rules"])
def test_parameters_that_leave_a_meeting_case_out_are_refused(params):
    with pytest.raises(ValueError):
        mixednode.build(params)


def test_the_reference_imports_nothing_of_the_program():
    spec = importlib.util.find_spec("benchmarks.worlds.mixednode")
    with open(spec.origin) as f:
        text = f.read()
    imports = [line.strip() for line in text.splitlines()
               if line.strip().startswith(("import ", "from "))]
    program = [i for i in imports if "cilium_tpu" in i]
    # one, inside load(): the label parser a user of the engine calls
    assert program == ["from cilium_tpu.model.labels import Labels"]
