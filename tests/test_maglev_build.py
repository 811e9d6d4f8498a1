"""The load-balancer's build without a Python step a slot (PR 45).

``compile/lb.py`` populated a Maglev row with three nested Python loops over
numpy scalars (0.16 s a 250-backend row at M = 16,381: eleven minutes for a
cluster's 10,000 services) and filled the frontend table with a loop that
hashed a key at a time. Both loops live on here, word for word, as the plain
references the new build is held to, element for element: the oracle,
``kernels/lb.py`` and the shim's steering all read these tables, so a
different but valid Maglev table is a different datapath.

(a) ``maglev_rows`` (the shim's ``shim_maglev_fill``, and the Python-int
    turns where the library is not built) equals the loop at M in {251,
    1021, 16381}, 1-250 backends, weights 1-4, names in both orders;
(b) the frontend table equals the loop's, crowded windows and doublings
    included, and the backend arrays a ``parse_addr`` an entry;
(c) a second ``build_lb`` over the same registry populates no row, one with
    one service's backends changed populates one, and both equal a build
    from scratch; ``lb_maglev_rows_built_total`` says so of an engine, and
    does not move across a policy-only regeneration;
(d) Maglev's own properties at 16,381;
(e) ``lb_map_max`` refuses the upsert past it and leaves the registry as it
    was.
"""

import numpy as np
import pytest

from cilium_tpu.compile import lb as lbmod
from cilium_tpu.compile.lb import (FE_KEY_WORDS, LBConfig, build_lb,
                                   maglev_rows, maglev_table)
from cilium_tpu.kernels.hashing import hash_words_np
from cilium_tpu.model.services import (Backend, Frontend, Service,
                                       ServiceRegistry)
from cilium_tpu.utils.ip import addr_to_words, parse_addr


# -- the loops this PR replaced, word for word -------------------------------
def _str_hash_words(s: str) -> np.ndarray:
    data = s.encode()
    data += b"\x00" * (-len(data) % 4)
    return np.frombuffer(data, dtype="<u4").astype(np.uint32)


def maglev_table_loop(backends, m: int) -> np.ndarray:
    """Standard Maglev population (the upstream pkg/loadbalancer algorithm
    shape): each backend gets a permutation of [0, M) from (offset, skip)
    derived from its name hash; backends take turns claiming their next
    unclaimed slot, weighted backends take ``weight`` consecutive turns."""
    n = len(backends)
    if n == 0:
        return np.full((m,), -1, dtype=np.int32)
    offsets = np.empty(n, dtype=np.int64)
    skips = np.empty(n, dtype=np.int64)
    for i, b in enumerate(backends):
        name = f"{b.addr}:{b.port}"
        h1 = int(hash_words_np(_str_hash_words(name + "#o"))[()])
        h2 = int(hash_words_np(_str_hash_words(name + "#s"))[()])
        offsets[i] = h1 % m
        skips[i] = h2 % (m - 1) + 1
    table = np.full((m,), -1, dtype=np.int32)
    next_idx = np.zeros(n, dtype=np.int64)
    filled = 0
    while filled < m:
        for i, b in enumerate(backends):
            for _ in range(b.weight):
                # claim the backend's next unclaimed permutation slot
                while True:
                    c = (offsets[i] + next_idx[i] * skips[i]) % m
                    next_idx[i] += 1
                    if table[c] < 0:
                        table[c] = i
                        filled += 1
                        break
                if filled == m:
                    return table
    return table


def frontend_table_loop(fe_keys: np.ndarray, probe_depth: int):
    """The open-addressed frontend table; grow until every key fits in the
    window."""
    F = fe_keys.shape[0]
    cap = 8
    while cap < 2 * max(F, 1):
        cap *= 2
    while True:
        tab_keys = np.zeros((cap, FE_KEY_WORDS), dtype=np.uint32)
        tab_val = np.full((cap,), -1, dtype=np.int32)
        ok = True
        for i in range(F):
            base_h = int(hash_words_np(fe_keys[i])[()]) & (cap - 1)
            for d in range(probe_depth):
                s = (base_h + d) & (cap - 1)
                if tab_val[s] < 0:
                    tab_keys[s] = fe_keys[i]
                    tab_val[s] = i
                    break
            else:
                ok = False
                break
        if ok:
            break
        cap *= 2
    return tab_keys, tab_val


# -- (a) the Maglev rows --------------------------------------------------------
def backends_of(n: int, weights: str, seed: int = 0):
    rng = np.random.default_rng(1000 * n + seed)
    w = np.ones(n, np.int64) if weights == "ones" \
        else rng.integers(1, 5, n)
    return [Backend(f"10.{(i >> 8) & 255}.{i & 255}.{1 + seed}", 8000 + i % 7,
                    int(w[i])) for i in range(n)]


@pytest.mark.parametrize("order", ["as-listed", "reversed"])
@pytest.mark.parametrize("weights", ["ones", "1-4"])
@pytest.mark.parametrize("n", [1, 2, 15, 60, 250])
@pytest.mark.parametrize("m", [251, 1021, 16381])
def test_the_fill_equals_the_loop(m, n, weights, order):
    backends = backends_of(n, weights)
    if order == "reversed":
        backends = backends[::-1]
    want = maglev_table_loop(backends, m)
    native = maglev_rows([backends], m)
    plain = maglev_rows([backends], m, native=False)
    assert native.dtype == plain.dtype == want.dtype == np.int32
    assert (native[0] == want).all() and (plain[0] == want).all()
    assert (maglev_table(backends, m) == want).all()


def test_the_native_fill_is_the_one_that_runs():
    assert lbmod._native_fill() is not None, \
        "libflowshim.so lacks shim_maglev_fill: make -C cilium_tpu/shim"


def test_many_rows_at_once_are_the_rows_one_at_a_time():
    lists = [backends_of(n, "1-4", seed) for seed, n in enumerate(
        [3, 0, 1, 40, 2, 0, 17, 5, 250, 2, 9, 33])]
    for m in (251, 1021):
        rows = maglev_rows(lists, m)
        assert rows.shape == (len(lists), m)
        for row, backends in zip(rows, lists):
            assert (row == maglev_table_loop(backends, m)).all()
        assert (maglev_rows(lists, m, native=False) == rows).all()
    with pytest.raises(ValueError, match="prime"):
        maglev_rows(lists, 250)


# -- (b) the frontend table and the backend arrays ---------------------------------
@pytest.mark.parametrize("n,seed", [(0, 0), (1, 0), (5, 1), (64, 2),
                                    (500, 3), (3000, 4), (14001, 5)])
def test_the_frontend_table_equals_the_loops(n, seed):
    rng = np.random.default_rng(seed)
    keys = np.zeros((n, FE_KEY_WORDS), np.uint32)
    keys[:, 2] = 0xFFFF
    keys[:, 3] = 0x0A600001 + rng.permutation(4 * n + 8)[:n] // 3
    keys[:, 4] = rng.choice([80, 443, 9090, 53], n)
    keys[:, 5] = rng.choice([6, 17], n)
    keys = np.unique(keys, axis=0)[rng.permutation(
        np.unique(keys, axis=0).shape[0])]
    want_keys, want_val = frontend_table_loop(keys, 8)
    got_keys, got_val = lbmod._fill_frontend_table(keys, 8)
    assert got_keys.shape == want_keys.shape
    assert (got_val == want_val).all() and (got_keys == want_keys).all()


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_crowded_windows_place_as_the_loop_and_double_with_it(depth):
    """Shallow windows: most keys lose their first slot, chains of keys
    displace each other, and the capacity doubles until all fit."""
    rng = np.random.default_rng(depth)
    for n in (7, 40, 300):
        keys = np.zeros((n, FE_KEY_WORDS), np.uint32)
        keys[:, 3] = rng.permutation(1 << 16)[:n]
        keys[:, 4] = 80
        want_keys, want_val = frontend_table_loop(keys, depth)
        got_keys, got_val = lbmod._fill_frontend_table(keys, depth)
        assert got_val.shape == want_val.shape, (n, depth)
        assert (got_val == want_val).all() and (got_keys == want_keys).all()
        assert want_val.shape[0] > 2 * n or depth == 3


def some_services(n=24, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n):
        nb = int(rng.choice([0, 1, 2, 5, 15, 60]))
        v6 = s % 5 == 0
        out.append(Service(
            name=f"svc{s:03d}", namespace="prod",
            frontends=tuple(Frontend(
                f"fd00::{s + 1:x}" if v6 else f"10.96.0.{s + 1}", p,
                17 if (s + p) % 4 == 0 else 6) for p in (80, 443)[:1 + s % 2]),
            lb_backends=tuple(Backend(
                f"fd00:1::{s:x}:{b + 1:x}" if v6 else f"10.128.{s}.{b + 1}",
                8000 + s % 3, 1 + (s + b) % 3) for b in range(nb))))
    return out


def assert_same_tables(a, b):
    for name, arr in a.tensors().items():
        assert arr.dtype == b.tensors()[name].dtype, name
        assert np.array_equal(arr, b.tensors()[name]), name
    assert a.frontends == b.frontends and a.backends == b.backends
    assert a.fe_names == b.fe_names
    assert np.array_equal(a.row_base, b.row_base)


def test_build_lb_gives_the_loops_tables():
    services = some_services()
    lb = build_lb(services, LBConfig(maglev_m=251))
    base = 0
    with_fe = [s for s in services if s.frontends]
    assert lb.n_services == len(with_fe) and lb.rows_built == len(with_fe)
    for row, svc in zip(lb.maglev, with_fe):
        want = maglev_table_loop(list(svc.lb_backends), 251)
        assert (row == np.where(want >= 0, want + base, -1)).all()
        base += len(svc.lb_backends)
    fe_keys = np.array([[*addr_to_words(parse_addr(fe.addr)[0]), fe.port,
                         fe.proto] for fe in lb.frontends], np.uint32)
    want_keys, want_val = frontend_table_loop(fe_keys, 8)
    assert (lb.tab_keys == want_keys).all() and (lb.tab_val == want_val).all()
    for i, b in enumerate(lb.backends):
        assert tuple(lb.be_addr[i]) == addr_to_words(parse_addr(b.addr)[0])
        assert lb.be_port[i] == b.port
    assert lb.be_addr.shape == (base, 4) and lb.be_addr.dtype == np.uint32
    with pytest.raises(ValueError, match="duplicate service frontend"):
        build_lb(services + [Service(
            name="twin", namespace="prod",
            frontends=(Frontend("10.96.0.2", 80),))])


# -- (c) a row is kept while its backends stand ------------------------------------
def registry_of(services):
    reg = ServiceRegistry()
    for svc in services:
        reg.upsert(svc)
    return reg


def test_a_second_build_populates_no_row_and_a_changed_service_one():
    services = some_services()
    reg = registry_of(services)
    cfg = LBConfig(maglev_m=251)
    first = build_lb(reg, cfg)
    assert first.rows_built == first.n_services == len(services)
    second = build_lb(reg, cfg, prev=first)
    assert second.rows_built == 0 and second.maglev is first.maglev
    assert_same_tables(second, first)
    # one service's backends change (one more: every later row's indices
    # shift), and one only re-weighted
    grown = services[7]
    reg.upsert(Service(name=grown.name, namespace=grown.namespace,
                       frontends=grown.frontends,
                       lb_backends=grown.lb_backends
                       + (Backend("10.129.0.1", 8000),)))
    third = build_lb(reg, cfg, prev=second)
    assert third.rows_built == 1
    assert_same_tables(third, build_lb(reg, cfg))
    heavy = next(s for s in services[8:] if s.lb_backends)
    reg.upsert(Service(name=heavy.name, namespace=heavy.namespace,
                       frontends=heavy.frontends, lb_backends=tuple(
                           Backend(b.addr, b.port, b.weight + 1)
                           for b in heavy.lb_backends)))
    fourth = build_lb(reg, cfg, prev=third)
    assert fourth.rows_built == 1
    assert_same_tables(fourth, build_lb(reg, cfg))
    # a service comes and one goes; another M keeps nothing
    reg.upsert(Service(name="svc0035", namespace="prod",
                       frontends=(Frontend("10.96.1.1", 80),),
                       lb_backends=(Backend("10.130.0.1", 80),)))
    reg.delete("prod", services[2].name)
    fifth = build_lb(reg, cfg, prev=fourth)
    assert fifth.rows_built == 1 and fifth.n_services == len(services)
    assert_same_tables(fifth, build_lb(reg, cfg))
    other_m = build_lb(reg, LBConfig(maglev_m=1021), prev=fifth)
    assert other_m.rows_built == other_m.n_services
    assert_same_tables(other_m, build_lb(reg, LBConfig(maglev_m=1021)))


def test_the_engine_counts_rows_built_and_a_policy_change_builds_none():
    from cilium_tpu.observe.trace import TRACER
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    cfg = DaemonConfig(ct_capacity=1 << 10, auto_regen=False,
                       trace_sample_rate=1.0)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    try:
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
        services = [s for s in some_services() if s.lb_backends]
        for svc in services:
            eng.upsert_service(svc)
        doc = {"endpointSelector": {"matchLabels": {"app": "web"}},
               "egress": [{"toServices": [{"k8sService": {
                   "serviceName": services[3].name, "namespace": "prod"}}]}]}
        eng.apply_policy([doc])
        eng.regenerate()
        built = "lb_maglev_rows_built_total"
        gauges = eng.metrics.gauges
        assert eng.metrics.counters[built] == len(services)
        assert gauges["lb_services"] == len(services)
        assert gauges["lb_maglev_bytes"] == len(services) * 251 * 4
        assert gauges["lb_frontends"] == sum(len(s.frontends)
                                             for s in services)
        assert gauges["lb_backends"] == sum(len(s.lb_backends)
                                            for s in services)
        # a policy-only change, through a full build (forced) and through
        # whatever the engine would do by itself
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["203.0.113.0/24"]}]}])
        eng.regenerate(force=True)
        full = eng.metrics.counters["regen_full_total"]
        assert full >= 2 and eng.metrics.counters[built] == len(services)
        assert eng.active.snapshot.lb.rows_built == 0
        spans = [s for s in TRACER.spans(limit=1 << 12)
                 if s["name"] == "engine.regen.lb"]
        assert len(spans) == full
        assert [s["attrs"]["rows_built"] for s in spans][-1] == 0
        assert all(s["parent"] == "engine.regen.compile" for s in spans)
        # one service's backends change: one row
        svc = services[5]
        eng.upsert_service(Service(
            name=svc.name, namespace=svc.namespace, frontends=svc.frontends,
            lb_backends=svc.lb_backends[:-1] or (Backend("10.9.9.9", 1),)))
        eng.regenerate()
        assert eng.metrics.counters[built] == len(services) + 1
        text = eng.metrics.render_prometheus()
        assert "ciliumtpu_lb_maglev_rows_built_total" in text
        assert "ciliumtpu_lb_maglev_bytes" in text
    finally:
        eng.stop()


def test_the_hbm_ledger_has_a_line_for_the_lb_tables():
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    cfg = DaemonConfig(ct_capacity=1 << 10, auto_regen=False, batch_size=64)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    try:
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
        eng.regenerate()
        groups = eng.datapath.hbm_ledger()["groups"]
        assert groups["lb"] == 0 and groups["policy"] > 0
        for svc in some_services(6):
            eng.upsert_service(svc)
        lb = eng.regenerate(force=True).snapshot.lb
        ledger = eng.datapath.hbm_ledger()
        want = sum(a.nbytes for a in lb.tensors().values())
        assert ledger["groups"]["lb"] == want >= lb.maglev.nbytes
        assert ledger["device_bytes"] == sum(
            v for k, v in ledger["groups"].items() if k != "wire_pool")
    finally:
        eng.stop()


# -- (d) Maglev's own properties at the production size ------------------------------
M = 16381


def test_every_slot_is_filled_and_the_shares_are_even():
    for n in (2, 15, 250):
        row = maglev_table(backends_of(n, "ones"), M)
        assert row.min() == 0 and row.max() == n - 1
        share = np.bincount(row, minlength=n)
        assert share.sum() == M
        assert share.max() - share.min() <= 1, (n, share.min(), share.max())
    # weight w: w slots a turn, so w times the share, within a round
    backends = [Backend(f"10.0.0.{i + 1}", 80, 1 + i % 4) for i in range(12)]
    share = np.bincount(maglev_table(backends, M), minlength=12)
    unit = M / sum(b.weight for b in backends)
    for b, got in zip(backends, share):
        assert abs(got - b.weight * unit) <= b.weight


def test_taking_one_backend_of_15_away_moves_under_two_fifteenths():
    backends = backends_of(15, "ones")
    before = maglev_table(backends, M)
    after = maglev_table(backends[:7] + backends[8:], M)
    # the same backend by name: indices past the removed one shift down
    renumbered = np.where(before > 7, before - 1, before)
    moved = (renumbered != after) | (before == 7)
    assert (before == 7).sum() in (M // 15, M // 15 + 1)
    assert moved.mean() < 2 / 15, moved.mean()


# -- (e) lb_map_max ---------------------------------------------------------------
def test_lb_map_max_refuses_the_upsert_past_it_and_leaves_the_registry():
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    assert DaemonConfig().lb_map_max == 65536
    cfg = DaemonConfig(ct_capacity=1 << 10, auto_regen=False, lb_map_max=10)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    try:
        reg = eng.ctx.services
        assert reg.lb_map_max == 10

        def svc(name, n_fe, n_be):
            return Service(
                name=name, namespace="prod",
                frontends=tuple(Frontend(f"10.96.0.{hash(name) % 200 + 1}",
                                         1000 + f) for f in range(n_fe)),
                lb_backends=tuple(Backend(f"10.128.{hash(name) % 200}."
                                          f"{b + 1}", 80)
                                  for b in range(n_be)))
        eng.upsert_service(svc("a", 2, 6))
        eng.upsert_service(svc("b", 1, 4))                  # 10 backends: fits
        before = (reg.all(), reg.revision, reg.export_rnat_state())
        with pytest.raises(ValueError, match=r"backends.*lb_map_max 10 "
                                             r"\(bpf-lb-map-max\)"):
            eng.upsert_service(svc("c", 1, 1))
        with pytest.raises(ValueError, match=r"frontends.*lb_map_max"):
            eng.upsert_service(svc("d", 8, 0))
        assert (reg.all(), reg.revision, reg.export_rnat_state()) == before
        # replacing a service counts what it had as freed
        eng.upsert_service(svc("a", 2, 5))
        eng.upsert_service(svc("c", 1, 1))
        with pytest.raises(ValueError, match="lb_map_max"):
            eng.upsert_service(svc("a", 2, 6))
        assert eng.delete_service("prod", "b")
        eng.upsert_service(svc("a", 2, 9))
        assert ServiceRegistry().lb_map_max is None         # a bare registry
    finally:
        eng.stop()


def test_a_k8s_service_selector_is_looked_up_not_scanned_for():
    """``match`` by (namespace, name) finds what the scan found, a service
    whose extra labels restate the name included."""
    from cilium_tpu.model.selectors import EndpointSelector
    name, ns = "k8s:io.kubernetes.service.name", \
        "k8s:io.kubernetes.service.namespace"
    reg = registry_of(some_services(12))
    reg.upsert(Service(name="odd", namespace="prod",
                       extra_labels=((name, "svc003"), ("k8s:tier", "db"))))

    def scan(sel):
        return [s for s in reg.all() if sel.matches(s.labels)]
    for sel in (EndpointSelector.from_labels({name: "svc003", ns: "prod"}),
                EndpointSelector.from_labels({name: "svc003", ns: "other"}),
                EndpointSelector.from_labels({name: "nope", ns: "prod"}),
                EndpointSelector.from_labels({name: "odd", ns: "prod"}),
                EndpointSelector.from_labels({ns: "prod"}),
                EndpointSelector.from_labels({"k8s:tier": "db"}),
                EndpointSelector()):
        got = sorted(reg.match(sel), key=lambda s: s.name)
        assert got == scan(sel), sel
    assert [s.name for s in reg.match(EndpointSelector.from_labels(
        {name: "svc003", ns: "prod"}))] == ["odd", "svc003"]
    reg.delete("prod", "odd")
    assert [s.name for s in reg.match(EndpointSelector.from_labels(
        {name: "svc003", ns: "prod"}))] == ["svc003"]
