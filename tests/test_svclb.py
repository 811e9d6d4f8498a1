"""The node that holds a cluster's services (``worlds/svclb.py``: 10,000
services behind ClusterIPs at full size, nine flows in ten through a
frontend) and its plain reference, at test size (``tiny-svclb``: 64
services, M = 251, 16 groups; PR 45).

(a) The reference against a loop over the deployment's own text, flow by
    flow: the services as plain data, ``ipaddress`` and dicts; a frontend's
    flow is translated to a backend first and judged there, as the program
    does it, whichever backend.
(b) The table against the program's oracle, row for row; and the program's
    three executors of the LB step (oracle, host mirror, jnp) agree on the
    world's own flows, each flow given a backend of its frontend's service.
(c) Every case a nearly right LB gets wrong, its answer written out by hand
    here: the reference, the loop and the oracle all have to give it.
(d) Frames of both protocols through ``frames_of``, the shim's mock rings
    and a harvest.
(e) ``tiny-svclb.saturate`` through ``run_cell`` on the jitted datapath:
    correct, the control caught, nine rows in ten translated; a verdict
    flipped in the service rows alone comes out not correct.
(f) A world that leaves a case out is refused.
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
from benchmarks.tests.conftest import DATA, REPO
from benchmarks.tests.test_frames_direction import (
    EP_V4, EP_V6_WORDS, assert_columns, through_the_shim, traffic_law)
from benchmarks.worlds import svclb

CONFIG = os.path.join("tests", "data", "configs", "tiny-svclb.json")
with open(os.path.join(REPO, CONFIG)) as _f:
    TINY_CONFIG = json.load(_f)
TINY = TINY_CONFIG["world"]
CELL = "tiny-svclb.saturate"
#: what the documents give each case: True for admitted, else the drop
#: reason. Written out here, not read from the world
ANSWERS = {
    "a_row_s_admitted": True,
    "a_row_s_plus_1_refused": 130,
    "b_tcp_port_admitted": True,
    "b_udp_port_refused": 130,
    "c_dns_tcp_admitted": True,
    "c_dns_udp_refused": 130,
    "d_port_no_frontend_has": 130,
    "e_through_the_frontend": True,
    "e_straight_on_the_frontends_port": 130,
    "f_external_named": True,
    "f_external_neighbour_not": 130,
    "g_most_backends": True,
    "g_fewest_backends": True,
}


@pytest.fixture(scope="module")
def world():
    return svclb.build(TINY)


def judged(world, flows):
    """→ per flow, True or the drop reason, by the reference."""
    return [True if ok else int(why) for ok, why in zip(
        ref.expected_allow(world, flows), ref.refusal_reasons(world, flows))]


def dst_of(flows):
    return flows["src"][:, 3].astype(np.int64)


# -- (a): the reference against a loop over the deployment's text ---------------
class Documents:
    """The deployment as text: what ``load`` hands the program, read back
    with ``ipaddress`` and plain dicts; nothing of the world's numpy."""

    def __init__(self, world):
        ip = ipaddress.ip_address
        services = world.services()
        self.frontends, self.group_of, self.admitted_ips = {}, {}, set()
        by_name = {}
        for svc in services:
            by_name[(svc["namespace"], svc["name"])] = svc
            assert len({port for _a, port in svc["backends"]}) == 1
            for addr, port, proto in svc["frontends"]:
                assert (ip(addr), port, proto) not in self.frontends
                self.frontends[(ip(addr), port, proto)] = svc
        for s, group, _app in world.applications():
            assert not services[s]["external"]
            for addr, _port in services[s]["backends"]:
                assert ip(addr) not in self.group_of    # a pod, one service
                self.group_of[ip(addr)] = group
        assert sum(not s["external"] for s in services) \
            == len(world.applications())
        self.l4 = set()
        for doc in world.policy_docs():
            assert doc["endpointSelector"] == {"matchLabels": {"app": "web"}}
            (rule,) = doc["egress"]
            if "toServices" in rule:
                (ts,) = rule["toServices"]
                svc = by_name[(ts["k8sService"]["namespace"],
                               ts["k8sService"]["serviceName"])]
                assert svc["external"] and "toPorts" not in rule
                self.admitted_ips |= {ip(a) for a, _p in svc["backends"]}
            else:
                (sel,), (to,) = rule["toEndpoints"], rule["toPorts"]
                (port,) = to["ports"]
                self.l4.add((sel["matchLabels"]["group"], int(port["port"]),
                             port["protocol"]))

    def judge(self, flows, backend=0):
        """``backend``: which of a service's backends a frontend's flow is
        sent to (the answer may not depend on it)."""
        out = []
        for dst, dport, proto, egress in zip(
                dst_of(flows).tolist(), flows["dport"].tolist(),
                flows["proto"].tolist(), flows["egress"].tolist()):
            assert egress
            at, port = ipaddress.ip_address(dst), dport
            svc = self.frontends.get((at, port, proto))
            if svc is not None:
                addr, port = svc["backends"][backend % len(svc["backends"])]
                at = ipaddress.ip_address(addr)
            name = {6: "TCP", 17: "UDP"}.get(proto)
            if at in self.group_of:
                ok = (self.group_of[at], port, name) in self.l4
            else:
                ok = at in self.admitted_ips
            out.append(True if ok else 130)
        return out


def crossovers(world, rng, n):
    """Flows no draw of the world's makes: any frontend address on any of
    the ports around, over either protocol; any pod and any external
    backend on target, frontend and other ports; the addresses around the
    pods' and the services' nets. All leave the endpoint: the deployment
    has no ingress document, and the world draws no ingress flow."""
    ports = np.concatenate([np.array(svclb.FE_PORTS), [53, 54, 8443],
                            svclb.TPORT_BASE + np.arange(-1, world.span + 1)])
    vip = svclb.SVC_NET + rng.integers(0, world.n_services + 2, n)
    pod = world.pod_address(rng.integers(0, world.n_pods, n))
    ext = svclb.EXT_NET + rng.integers(0, world.n_external * 256, n)
    near = svclb.POD_NET + rng.integers(-8, world.n_pods * 3 + 8, n)
    parts = [world._flows(d, ports[rng.integers(0, ports.size, n)],
                          rng.random(n) < 0.4)
             for d in (vip, pod, ext, near)]
    flows = frames.concat(parts)
    m = flows["sport"].shape[0]
    return dict(flows, sport=(30000 + np.arange(m)).astype(np.int32))


def the_flows(world, rng, n_allowed, n_denied, n_unknown, n_cross):
    return frames.concat([
        world.allowed_flows(rng, n_allowed, 20000, 40000),
        world.denied_flows(rng, n_denied, 20000, 40000),
        world.unknown_flows(rng, n_unknown, 20000, 40000),
        crossovers(world, rng, n_cross),
        *[f for f, _answer in world.cases.values()]])


def test_reference_agrees_with_a_loop_over_the_documents(world):
    rng = np.random.default_rng(5)
    flows = the_flows(world, rng, 4000, 2500, 500, 800)
    n = flows["sport"].shape[0]
    assert n >= 10000
    got, docs = judged(world, flows), Documents(world)
    for backend in (0, 1, 7):
        want = docs.judge(flows, backend)
        wrong = [i for i in range(n) if got[i] != want[i]]
        assert not wrong, [(i, got[i], want[i]) for i in wrong[:10]]
    svc, _f = world.frontend_of(flows)
    through = svc >= 0
    assert np.array(got, object)[through].tolist().count(True) >= 3000
    assert np.array(got, object)[through].tolist().count(130) >= 2000
    assert np.array(got, object)[~through].tolist().count(True) >= 300
    assert got.count(130) >= 4000
    assert set(ref.refusal_reasons(world, flows).tolist()) == {130}
    assert not hasattr(world, "reasons")


def test_the_world_is_the_deployment_its_parameters_state(world):
    mix = {int(k): v for k, v in TINY["backends_mix"].items()}
    assert world.n_services == 64 and world.n_external == 8
    assert world.named.sum() == 4 and (world.named <= world.external).all()
    sizes, counts = np.unique(world.n_backends, return_counts=True)
    assert dict(zip(sizes.tolist(), counts.tolist())) == {
        k: round(v * 64) for k, v in mix.items()}
    # ClusterIPs consecutive from 10.96.0.1, every frontend of a service on
    # its one address; the DNS is rank 0, port 53 over both protocols
    services = world.services()
    assert services[0]["name"] == "kube-dns" \
        and sorted(services[0]["frontends"]) == [("10.96.0.1", 53, 6),
                                                 ("10.96.0.1", 53, 17)]
    for s, svc in enumerate(services):
        assert {a for a, _p, _q in svc["frontends"]} \
            == {str(ipaddress.ip_address(0x0A600001 + s))}
        assert len(svc["backends"]) == world.n_backends[s]
    assert sum(len(s["frontends"]) for s in services) == world.n_frontends
    # the pods: 110 to a node's /24 from 10.128.0.0, none twice
    pods = [ipaddress.ip_address(a) for s in services if not s["external"]
            for a, _p in s["backends"]]
    assert len(set(pods)) == len(pods) == world.n_pods
    assert all(1 <= int(p) & 255 <= 110 for p in pods)
    nodes = {int(p) >> 8 for p in pods}
    assert min(nodes) == 0x0A8000 and len(nodes) == -(-world.n_pods // 110)
    net = ipaddress.ip_network("10.96.0.0/12")
    assert not any(p in net for p in pods)
    # a service's pods lie on several nodes, as a Deployment's do
    big = int(np.argmax(np.where(world.external, 0, world.n_backends)))
    assert len({int(ipaddress.ip_address(a)) >> 8
                for a, _p in services[big]["backends"]}) >= 3
    # the rows' order is the services' order
    assert world.row_order() == [(s["namespace"], s["name"])
                                 for s in services]
    docs = world.policy_docs()
    assert len(docs) == TINY["n_rules"] + TINY["named_external"]
    # the control has single-cover rules to take out, and the window uses
    # cells of both kinds
    allowed, cover = world.table()
    mix_ = flowmix.generate(traffic_law(), world, np.random.default_rng(2),
                            2000, 60000)
    cell = world.cells(mix_["flows"])
    per_flow = np.bincount(mix_["sched_flow"], minlength=cell.size)
    per_cell = np.bincount(cell[cell >= 0], weights=per_flow[cell >= 0],
                           minlength=allowed.size)
    used = (cover == 1) & (per_cell >= 16)
    assert used[:world._n_l4].sum() >= 10 and used[world._n_l4:].sum() >= 2
    assert cover.max() == 1


# -- (b): against the program's oracle, and the LB step's three executors -------
@pytest.fixture(scope="module")
def oracle_engine(world):
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    cfg = DaemonConfig(ct_capacity=1 << 16, auto_regen=False, maglev_m=251,
                       lb_map_max=4096)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    try:
        world.load(eng)
        eng.regenerate()
        yield eng
    finally:
        eng.stop()


def columns(world, eng, flows):
    ep_slot = eng.active.snapshot.ep_slot_of[world.ep_id]
    return frames.columns_of(flows, world.ep_v4, world.ep_v6_words, ep_slot)


def oracle_says(world, eng, flows):
    out = eng.classify(columns(world, eng, flows))
    return [True if a else int(r) for a, r in zip(
        np.asarray(out["allow"]).astype(bool), np.asarray(out["reason"]))]


def test_table_against_the_programs_oracle(world, oracle_engine):
    rng = np.random.default_rng(9)
    flows = the_flows(world, rng, 3000, 2000, 500, 300)
    got = judged(world, flows)
    said = oracle_says(world, oracle_engine, flows)
    wrong = [i for i in range(len(got)) if got[i] != said[i]]
    assert not wrong, [(i, got[i], said[i]) for i in wrong[:10]]
    assert {True, 130} == set(got)


def test_the_programs_rows_are_the_worlds_services(world, oracle_engine):
    lb = oracle_engine.active.snapshot.lb
    names = [f"{ns}/{name}" for ns, name in world.row_order()]
    assert lb.n_services == world.n_services == lb.maglev.shape[0]
    assert lb.maglev.shape[1] == 251 and lb.n_frontends == world.n_frontends
    assert len(lb.backends) == world.n_backends.sum()
    # row r is service r: its frontends name it, its entries are its backends
    assert [lb.fe_names[np.nonzero(lb.fe_service == r)[0][0]]
            for r in range(lb.n_services)] == names
    for r in (0, 1, 2, world.n_services - 1):
        mine = {(b.addr, b.port) for b in
                lb.backends[lb.row_base[r]:lb.row_base[r + 1]]}
        assert mine == set(world.services()[r]["backends"])
        assert set(np.unique(lb.maglev[r]).tolist()) \
            == set(range(lb.row_base[r], lb.row_base[r + 1]))
    gauges = oracle_engine.metrics.gauges
    assert gauges["lb_services"] == 64 and gauges["lb_frontends"] == 89
    assert gauges["lb_backends"] == 892
    assert gauges["lb_maglev_bytes"] == 64 * 251 * 4


def test_oracle_mirror_and_kernel_give_a_backend_of_the_frontends_service(
        world, oracle_engine):
    """The three executors of the LB step, row for row, on the world's own
    flows; and what the configuration guarantees of the translation."""
    import jax.numpy as jnp
    from cilium_tpu.compile.lb import lb_translate_np
    from cilium_tpu.kernels.lb import lb_step
    rng = np.random.default_rng(3)
    flows = the_flows(world, rng, 1500, 600, 100, 200)
    lb = oracle_engine.active.snapshot.lb
    batch = columns(world, oracle_engine, flows)
    dst, dport, rev, none, fe = lb_translate_np(lb, batch)
    got = lb_step({k: jnp.asarray(v) for k, v in lb.tensors().items()},
                  {k: jnp.asarray(v) for k, v in batch.items()
                   if k in ("src", "dst", "sport", "dport", "proto",
                            "valid")})
    for mine, theirs in zip((dst, dport, rev, none), got):
        assert (np.asarray(theirs) == mine).all()
    svc, _f = world.frontend_of(flows)
    assert ((fe >= 0) == (svc >= 0)).all() and not none.any()
    assert ((rev > 0) == (svc >= 0)).all() and 0.5 < (svc >= 0).mean() < 0.95
    services = world.services()
    for i in np.nonzero(svc >= 0)[0][::7].tolist():
        to = (str(ipaddress.ip_address(int(dst[i, 3]))), int(dport[i]))
        assert to in services[svc[i]]["backends"], i
    # a flow is given one backend whatever batch it rides in, and flows of
    # one frontend spread over its backends
    again = lb_translate_np(lb, {k: v[::-1] for k, v in batch.items()})
    assert (again[0][::-1] == dst).all() and (again[1][::-1] == dport).all()
    dns = np.nonzero(svc == 0)[0]
    assert len({int(d) for d in dst[dns, 3]}) == min(
        world.n_backends[0], len({int(d) for d in dst[dns, 3]})) > 1
    # the oracle translates as they do
    out = oracle_engine.classify(batch)
    assert (np.asarray(out["svc"]).astype(bool) == (svc >= 0)).all()
    through = svc >= 0
    assert (np.asarray(out["nat_dst"])[through] == dst[through]).all()
    assert (np.asarray(out["nat_dport"])[through] == dport[through]).all()


# -- (c): the cases a nearly right LB gets wrong, by hand -------------------------
@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_case_by_hand(name, world, oracle_engine):
    flows, stated = world.cases[name]
    n = flows["sport"].shape[0]
    assert stated == ANSWERS[name] and n >= 1
    assert judged(world, flows) == [ANSWERS[name]] * n
    assert Documents(world).judge(flows) == [ANSWERS[name]] * n
    assert oracle_says(world, oracle_engine, flows) == [ANSWERS[name]] * n


def test_the_cases_are_what_their_names_say(world):
    c = world.cases
    assert set(c) == set(ANSWERS)

    def one(name):
        flows = c[name][0]
        svc, f = world.frontend_of(flows)
        return flows, int(svc[0]), int(f[0])
    # a: neighbouring rows
    _fa, a, _ = one("a_row_s_admitted")
    _fb, a1, _ = one("a_row_s_plus_1_refused")
    assert a1 == a + 1 and not world.external[[a, a1]].any()
    # b: one address, two ports, a TCP and a UDP frontend of one service
    fb, b, _ = one("b_tcp_port_admitted")
    fu, b2, _ = one("b_udp_port_refused")
    assert b == b2 and dst_of(fb)[0] == dst_of(fu)[0]
    assert fb["dport"][0] != fu["dport"][0]
    assert (fb["proto"][0], fu["proto"][0]) == (6, 17)
    # c: the DNS's one address and port, both protocols
    ft, s, _ = one("c_dns_tcp_admitted")
    fu, s2, _ = one("c_dns_udp_refused")
    assert s == s2 == 0 and ft["dport"][0] == fu["dport"][0] == 53
    assert dst_of(ft)[0] == dst_of(fu)[0] == 0x0A600001
    # d: ClusterIPs, on ports no frontend has
    fd = c["d_port_no_frontend_has"][0]
    assert (world.frontend_of(fd)[0] < 0).all()
    assert ((dst_of(fd) > svclb.SVC_NET)
            & (dst_of(fd) <= svclb.SVC_NET + world.n_services)).all()
    # e: the frontend, and one of its backends on the frontend's port
    fe, e, _ = one("e_through_the_frontend")
    fs = c["e_straight_on_the_frontends_port"][0]
    pod = world.pod_at(dst_of(fs))
    assert world.pod_service[pod[0]] == e
    assert fs["dport"][0] == fe["dport"][0] != world.tport[e]
    # f: external neighbours
    _f, named, _ = one("f_external_named")
    _f, other, _ = one("f_external_neighbour_not")
    ext = np.nonzero(world.external)[0].tolist()
    assert ext.index(other) == ext.index(named) + 1
    assert world.named[named] and not world.named[other]
    # g: the most and the fewest backends among the admitted in-cluster
    _f, most, _ = one("g_most_backends")
    _f, fewest, _ = one("g_fewest_backends")
    assert world.n_backends[most] >= 60 and world.n_backends[fewest] == 2


def test_the_traffic_holds_the_cases_and_nine_flows_in_ten_go_through(world):
    mix = flowmix.generate(traffic_law(), world, np.random.default_rng(2),
                           2000, 60000)
    flows, kind = mix["flows"], mix["kind"]
    want = ref.expected_allow(world, flows)
    assert want[kind <= flowmix.KIND_NEW_ALLOWED].all()
    assert not want[kind >= flowmix.KIND_NEW_DENIED].any()
    # the heaviest ranks are the admitted cases, in the cases' order
    admitted = frames.concat([f for name, (f, _a) in world.cases.items()
                              if ANSWERS[name] is True])
    h = admitted["sport"].shape[0]
    head = frames.take(flows, slice(0, h))
    assert (head["src"] == admitted["src"]).all() \
        and (head["dport"] == admitted["dport"]).all() \
        and (head["proto"] == admitted["proto"]).all()
    # the refused ones are the first of the new flows that are denied, and
    # (d) the first of the unknown ones
    def keys(f):
        return {(int(a), int(p), int(q)) for a, p, q in zip(
            dst_of(f), f["dport"], f["proto"])}
    refused = frames.concat([f for name, (f, _a) in world.cases.items()
                             if ANSWERS[name] is not True
                             and name != "d_port_no_frontend_has"])
    assert keys(refused) <= keys(frames.take(
        flows, kind == flowmix.KIND_NEW_DENIED))
    assert keys(world.cases["d_port_no_frontend_has"][0]) <= keys(
        frames.take(flows, kind == flowmix.KIND_NEW_UNKNOWN))
    # nine flows in ten, and about nine frames in ten, go to a frontend
    svc, _f = world.frontend_of(flows)
    per_flow = np.bincount(mix["sched_flow"], minlength=svc.size)
    live = np.arange(svc.size) < 2000
    assert 0.87 < (svc[live] >= 0).mean() < 0.93
    assert 0.85 < per_flow[svc >= 0].sum() / per_flow.sum() < 0.96
    # a tenth or so over UDP, and both kinds of refusal
    assert 0.02 < (flows["proto"][live] == 17).mean() < 0.3
    denied = kind == flowmix.KIND_NEW_DENIED
    assert (svc[denied] >= 0).mean() > 0.8 and (svc[denied] < 0).sum() > 20
    # no two flows that reach one service share a source port, so no two
    # translate to one conntrack key
    pod = world.pod_at(dst_of(flows))
    reach = np.where(svc >= 0, svc, np.where(
        pod >= 0, world.pod_service[np.maximum(pod, 0)], -1))
    known = reach >= 0
    pairs = np.stack([reach[known], flows["sport"][known]], axis=1)
    assert np.unique(pairs, axis=0).shape[0] == pairs.shape[0]
    # the services' ranks are heavy-tailed: the DNS first
    counts = np.bincount(svc[live & (svc >= 0)], minlength=world.n_services)
    assert counts.argmax() == 0 and counts[0] > 8 * np.median(
        counts[counts > 0])


# -- (d): through the shim -------------------------------------------------------
def test_both_protocols_in_one_harvest(world):
    rng = np.random.default_rng(4)
    flows = frames.concat([
        *[f for f, _answer in world.cases.values()],
        world.allowed_flows(rng, 140, 20000, 40000),
        world.denied_flows(rng, 70, 20000, 40000),
        world.unknown_flows(rng, 20, 20000, 40000)])
    n = flows["sport"].shape[0]
    assert n <= 256
    table, lens = frames.frames_of(flows, EP_V4, EP_V6_WORDS)
    udp = flows["proto"] == 17
    assert table.shape[1] == frames.FRAME_STRIDE
    assert (lens[udp] == 42).all() and (lens[~udp] == 54).all()
    assert 10 <= udp.sum() <= n - 10
    want = frames.columns_of(flows, EP_V4, EP_V6_WORDS, 0)
    assert (want["direction"] == frames.DIR_EGRESS).all()
    assert (want["dst"][:, 3] == flows["src"][:, 3]).all()
    assert (want["src"][:, 3] == EP_V4).all()
    assert_columns(through_the_shim(flows), want)


# -- (e): the tiny cell through run_cell -----------------------------------------
NEW_READERS = ("kernels.lb_hbm_share", "engine.lb_build_s")


@pytest.fixture(scope="module")
def tiny_manifest():
    """The tests' manifest with the tiny cell in it, as the README's
    "Adding things" has a later PR add one: entries appended, in memory."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {e["name"]: e for e in json.load(f)["per_layer"]}
    m["configs"].append({
        "name": "tiny-svclb", "source": "test", "reduced": [], "why": "test",
        "file": CONFIG.replace(os.sep, "/")})
    m["workloads"].append({"name": CELL, "config": "tiny-svclb",
                           "traffic": "saturate", "chips": 1, "why": "test"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny-cidrsvc.saturate" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    for name in NEW_READERS + ("lb.translated_share",
                               "kernels.lb_us_per_batch"):
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
def tiny_run(tiny_manifest):
    return run(tiny_manifest, 3000000045)


def test_the_tiny_cell_is_correct_and_its_control_is_caught(tiny_run):
    cell, r = tiny_run
    n = numbers(r)
    assert r["correct"], [x for x in r["numbers"] if not x["ok"]]
    assert r["failed"] == 0 and r["attempted"] > 1000
    for name in ("unverdicted", "prefix_excess", "passed_gap",
                 "reason_policy_gap", "probe_mismatched", "fill_table_gap",
                 "fill_denied"):
        assert n[name]["value"] == 0, name
    assert "reason_policy_l7_gap" not in n and "refused_for" not in r
    assert n["probe_rows"]["value"] >= 64
    assert r["control"]["caught"] is True
    assert r["control"]["passed_gap"] == r["control"]["frames_on_it"] >= 16
    assert r["compiles"]["in_window"] == 0
    assert set(r["metrics"]) == set(cell.e2e) \
        == {"verdicts_per_s", "setup_s"}
    assert set(NEW_READERS) <= set(cell.layers)


def test_nine_rows_in_ten_are_translated(tiny_run):
    _cell, r = tiny_run
    also = r["also"]
    assert 0.8 < also["lb.translated_share"]["value"] < 0.97
    # no device plane and no spans in an untraced CPU run
    assert not set(NEW_READERS) & set(also)
    assert "kernels.lb_us_per_batch" not in also


def flip_a_service_row(eng, shim):
    """``break_path``: in every 20th harvest that holds one, the verdict of
    one row whose destination is a ClusterIP is turned over where the shim
    applies it; no other row is touched."""
    poll, apply = shim.poll_batch, shim.apply_verdicts
    harvested, seen = collections.deque(), [0]

    def poll_batch(*args, **kw):
        got = poll(*args, **kw)
        if got is not None:
            n = int(shim.last_poll_rows)
            dst = np.asarray(got["dst"][:n, 3]).astype(np.int64)
            harvested.append((dst > svclb.SVC_NET)
                             & (dst < svclb.SVC_NET + (1 << 20)))
        return got

    def apply_verdicts(allow):
        allow = np.array(allow, dtype=bool)
        rows = np.nonzero(harvested.popleft()[:allow.size])[0]
        seen[0] += 1
        if seen[0] % 20 == 0 and rows.size:
            allow[rows[0]] = ~allow[rows[0]]
        apply(allow)
    shim.poll_batch, shim.apply_verdicts = poll_batch, apply_verdicts


def test_a_verdict_flipped_in_the_service_rows_alone_is_not_correct(
        tiny_manifest):
    _cell, r = run(tiny_manifest, 17, break_path=flip_a_service_row)
    n = numbers(r)
    assert not r["correct"]
    assert n["prefix_excess"]["value"] > 0 and not n["prefix_excess"]["ok"]
    assert n["unverdicted"]["value"] == 0             # still one per frame


# -- (f): a world that leaves a case out is refused ------------------------------
def changed(**params):
    return dict(copy.deepcopy(TINY), **params)


@pytest.mark.parametrize("params", [
    changed(named_external=8),                  # every external one named
    changed(named_external=0),
    changed(external_services=7),               # does not divide 64
    changed(udp_share=0.0),                     # (b): no UDP frontend
    changed(ports_mix={"1": 1.0}),              # (b): no second port
    changed(backends_mix={"2": 0.5, "5": 0.6}),
    changed(n_rules=17),                        # no multiple of 16
    changed(n_rules=16 * 9),                    # more a group than ports
    changed(target_ports=9),                    # a multiple of 3
    changed(service_share=1.0),
    changed(n_groups=64, n_rules=64),           # (a): one service a group,
    #                                             one port: all alike
], ids=["all-named", "none-named", "externals-uneven", "no-udp",
        "one-port-each", "mix-over-one", "rules-uneven",
        "rules-over-ports", "ports-by-three", "no-straight-flows",
        "no-opposite-neighbours"])
def test_parameters_that_leave_a_case_out_are_refused(params):
    with pytest.raises(ValueError):
        svclb.build(params)
    # the control: another count of rules that fits builds
    assert svclb.build(changed(n_rules=96)).n_rules == 96


def test_the_reference_imports_nothing_of_the_program():
    spec = importlib.util.find_spec("benchmarks.worlds.svclb")
    with open(spec.origin) as f:
        text = f.read()
    imports = [line.strip() for line in text.splitlines()
               if line.strip().startswith(("import ", "from "))]
    program = [i for i in imports if "cilium_tpu" in i]
    # two, inside load(): the label parser and the service model a user of
    # the engine hands it
    assert program == [
        "from cilium_tpu.model.labels import Labels",
        "from cilium_tpu.model.services import Backend, Frontend, Service"]
    load_at = text.index("    def load(self, eng)")
    assert all(text.index(i) > load_at for i in program)
