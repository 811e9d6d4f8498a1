"""ISSUE 11: the vectorized flow-observe engine (observe/observer.py), the
hubble-relay-style fan-in (observe/relay.py), per-rule hit counters, and the
explainable-flow surface (API route, CLI, blackbox provenance).

Pinned here:
- FlowFilter mask composition (allow-OR / deny-subtract / field-AND) over
  the columnar ring, including CIDR matching on v4-mapped words
- one-shot vs follow read modes; follow NEVER loses records silently —
  every ring wraparound past a cursor is an explicit structured gap
  (acceptance criterion), including under a live writer race
- relay fan-in: k-way merge ordering, node tags, per-source cursors/lag,
  gap re-emission; the 4-engine fan-in phase `make chaos` runs
- per-rule hit/drop counters {rule=} with capped cardinality, scraped
  concurrently with a sharded soak (the satellite race test)
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.observe.observer import (FlowFilter, FlowObserver,
                                         FollowCursor, compose_mask,
                                         parse_filters)
from cilium_tpu.observe.relay import FlowRelay
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.runtime.flowlog import FlowLog
from cilium_tpu.runtime.metrics import Metrics
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr

from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine

from tests.test_audit import setup_web, sharded_audited_engine, web_batch
from tests.test_pipeline import POLICY, fake_engine, mk_chunks, pkt


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def _words(addr: str) -> np.ndarray:
    a16, _ = parse_addr(addr)
    return np.frombuffer(a16, dtype=">u4").astype(np.uint32)


def mk_batch_out(n, *, allow=True, reason=0, rule=3, pfx=0x10A, pre=1,
                 ident=1234, sport0=40000, dport=443, proto=C.PROTO_TCP,
                 direction=C.DIR_EGRESS, src="192.168.1.10", dst="10.1.2.3"):
    """Synthetic (batch, out) pair in the kernels/records column layout —
    enough surface for the flowlog/observer to extract."""
    batch = {
        "valid": np.ones(n, dtype=bool),
        "ep_slot": np.zeros(n, dtype=np.int32),
        "src": np.tile(_words(src), (n, 1)),
        "dst": np.tile(_words(dst), (n, 1)),
        "sport": np.arange(sport0, sport0 + n, dtype=np.uint32),
        "dport": np.full(n, dport, dtype=np.uint32),
        "proto": np.full(n, proto, dtype=np.int32),
        "direction": np.full(n, direction, dtype=np.int32),
    }
    out = {
        "allow": np.full(n, allow, dtype=bool),
        "reason": np.full(n, reason, dtype=np.int32),
        "status": np.full(n, pre, dtype=np.int32),
        "matched_rule": np.full(n, rule, dtype=np.int32),
        "lpm_prefix": np.full(n, pfx, dtype=np.int32),
        "ct_state_pre": np.full(n, pre, dtype=np.int32),
        "remote_identity": np.full(n, ident, dtype=np.int32),
    }
    return batch, out


def fill(log, n, **kw):
    now = kw.pop("now", 1)
    batch, out = mk_batch_out(n, **kw)
    log.append_batch(batch, out, now=now, ep_ids=(1,))


# --------------------------------------------------------------------------- #
# filter mask composition
# --------------------------------------------------------------------------- #
class TestFilterMasks:
    def _cols(self):
        log = FlowLog(capacity=64, mode="all")
        fill(log, 4, allow=True, rule=3, dport=443)
        fill(log, 3, allow=False, reason=int(C.DropReason.POLICY_DENY),
             rule=7, dport=80, dst="10.9.0.5")
        fill(log, 2, allow=False, reason=int(C.DropReason.CT_INVALID),
             rule=-1, pfx=-1, dst="172.16.3.9", proto=C.PROTO_UDP,
             dport=53, direction=C.DIR_INGRESS)
        cols, _, _ = log.snapshot_columns()
        return cols

    def test_verdict_reason_and_allow_or(self):
        cols = self._cols()
        m = FlowFilter(verdict="FORWARDED").mask(cols)
        assert int(m.sum()) == 4
        m = FlowFilter(
            reasons=(int(C.DropReason.POLICY_DENY),)).mask(cols)
        assert int(m.sum()) == 3
        # allowlist ORs its filters
        m = compose_mask(cols, allow=[
            FlowFilter(verdict="FORWARDED"),
            FlowFilter(reasons=(int(C.DropReason.CT_INVALID),))])
        assert int(m.sum()) == 6

    def test_deny_subtracts_and_fields_and(self):
        cols = self._cols()
        # empty allowlist = everything; denylist subtracts
        m = compose_mask(cols, deny=[FlowFilter(verdict="DROPPED")])
        assert int(m.sum()) == 4
        # fields inside one filter AND: dropped AND udp = the CT_INVALID rows
        m = compose_mask(cols, allow=[
            FlowFilter(verdict="DROPPED", protos=(C.PROTO_UDP,))])
        assert int(m.sum()) == 2

    def test_rule_identity_direction_ports(self):
        cols = self._cols()
        assert int(FlowFilter(rules=(7,)).mask(cols).sum()) == 3
        assert int(FlowFilter(rules=(3, 7)).mask(cols).sum()) == 7
        assert int(FlowFilter(identities=(1234,)).mask(cols).sum()) == 9
        assert int(FlowFilter(
            direction=C.DIR_INGRESS).mask(cols).sum()) == 2
        assert int(FlowFilter(dports=(80,)).mask(cols).sum()) == 3
        # port matches src OR dst
        assert int(FlowFilter(ports=(443,)).mask(cols).sum()) == 4

    def test_cidr_matching_v4_mapped(self):
        cols = self._cols()
        assert int(FlowFilter(dst_cidrs=("10.0.0.0/8",)).mask(cols).sum()) \
            == 7
        assert int(FlowFilter(
            dst_cidrs=("172.16.0.0/12",)).mask(cols).sum()) == 2
        assert int(FlowFilter(
            src_cidrs=("192.168.1.0/24",)).mask(cols).sum()) == 9
        # any-direction cidr: src OR dst
        assert int(FlowFilter(cidrs=("10.9.0.0/16",)).mask(cols).sum()) == 3
        # OR within the cidr list
        assert int(FlowFilter(
            dst_cidrs=("10.9.0.0/16", "172.16.0.0/12")).mask(cols).sum()) \
            == 5

    def test_parse_filters(self):
        allow, deny = parse_filters({
            "verdict": "dropped", "reason": "POLICY_DENY,6",
            "proto": "TCP", "rule": "3,7", "not_dport": "53",
            "last": "10"})                 # non-filter keys ignored
        assert len(allow) == 1 and len(deny) == 1
        f = allow[0]
        assert f.verdict == "DROPPED"
        assert int(C.DropReason.POLICY_DENY) in f.reasons and 6 in f.reasons
        assert f.protos == (C.PROTO_TCP,) and f.rules == (3, 7)
        assert deny[0].dports == (53,)
        # each not_* KEY is its own deny filter (independent exclusions
        # OR via compose_mask; one AND-ed filter would deny almost nothing)
        _, deny = parse_filters({"not_verdict": "FORWARDED",
                                 "not_dport": "53,80"})
        assert len(deny) == 2
        assert {f.verdict for f in deny} == {"FORWARDED", None}
        assert (53, 80) in {f.dports for f in deny}
        with pytest.raises(ValueError):
            parse_filters({"reason": "NO_SUCH_REASON"})
        with pytest.raises(ValueError):
            parse_filters({"verdict": "MAYBE"})
        # value validation covers the DENYLIST too, and CIDRs fail at
        # parse time (a 400), not inside the scan (a 500)
        with pytest.raises(ValueError):
            parse_filters({"not_verdict": "MAYBE"})
        with pytest.raises(ValueError):
            parse_filters({"cidr": "banana"})
        # repeated scalar --not flags reach the parser comma-joined (the
        # API accumulates duplicate not_* keys); each part denies alone
        _, deny = parse_filters({"not_verdict": "FORWARDED,DROPPED"})
        assert {f.verdict for f in deny} == {"FORWARDED", "DROPPED"}
        # an unknown not_* key is a typo'd exclusion: silently dropping it
        # would fail OPEN (streaming the very flows the operator excluded)
        with pytest.raises(ValueError):
            parse_filters({"not_identty": "123"})

    def test_monitor_follower_handles_gap_records(self):
        """The legacy `monitor --api -f` surface: gap markers render as a
        line (not a TypeError on missing flow fields) and pass every
        client-side filter — loss is never hidden."""
        from cilium_tpu.cli.commands import _flow_line, _flow_matches
        gap = {"gap": True, "dropped": 7, "resume_seq": 42}
        line = _flow_line(gap)
        assert "7" in line and "42" in line and "gap" in line

        class _Args:
            verdict = "DROPPED"
            endpoint = 3
            ip = "1.2.3.4"
            port = 80
        assert _flow_matches(gap, _Args())


# --------------------------------------------------------------------------- #
# observe read modes
# --------------------------------------------------------------------------- #
class TestObserveModes:
    def test_oneshot_last_window_newest(self):
        log = FlowLog(capacity=64, mode="all")
        fill(log, 10)
        obs = FlowObserver(log)
        res = obs.observe(last=3)
        assert [r["seq"] for r in res["flows"]] == [8, 9, 10]
        assert res["matched"] == 10 and res["scanned"] == 10
        assert res["gap"] is None and res["cursor"] == 10

    def test_follow_truncation_resumes_without_loss(self):
        log = FlowLog(capacity=64, mode="all")
        fill(log, 10)
        cur = FollowCursor(FlowObserver(log))
        seqs = []
        for _ in range(5):
            seqs += [r["seq"] for r in cur.poll(limit=4)]
        assert seqs == list(range(1, 11))
        assert cur.poll(limit=4) == []     # drained

    def test_follow_gap_marker_counter_and_metrics(self):
        m = Metrics()
        log = FlowLog(capacity=8, mode="all", metrics=m)
        fill(log, 20)                      # ring keeps 13..20
        cur = FollowCursor(FlowObserver(log, metrics=m), cursor=5)
        out = cur.poll()
        assert out[0] == {"gap": True, "dropped": 7, "resume_seq": 13}
        assert [r["seq"] for r in out[1:]] == list(range(13, 21))
        assert cur.gaps == 1 and cur.dropped == 7
        assert log.follow_gaps == 1 and log.follow_gap_records == 7
        assert m.counters["flowlog_follow_gaps_total"] == 1
        assert m.counters["flowlog_follow_gap_records_total"] == 7

    def test_fresh_attach_is_not_a_gap(self):
        log = FlowLog(capacity=8, mode="all")
        fill(log, 20)
        res = FlowObserver(log).observe(since=0)
        assert res["gap"] is None
        assert [r["seq"] for r in res["flows"]] == list(range(13, 21))

    def test_filters_apply_in_follow_mode(self):
        log = FlowLog(capacity=64, mode="all")
        fill(log, 4, allow=True)
        fill(log, 3, allow=False, reason=int(C.DropReason.POLICY_DENY))
        cur = FollowCursor(FlowObserver(log),
                           allow=[FlowFilter(verdict="DROPPED")])
        out = cur.poll()
        assert len(out) == 3
        assert all(r["verdict"] == "DROPPED" for r in out)
        assert cur.cursor == 7             # advanced past non-matching too


# --------------------------------------------------------------------------- #
# follow-mode racing ring wraparound (acceptance: no silent loss)
# --------------------------------------------------------------------------- #
class TestFollowRacesWraparound:
    def test_live_writer_race_accounts_every_record(self):
        """A writer wrapping a small ring at full speed vs a follower with
        a small poll page: every appended record is either DELIVERED or
        covered by an explicit gap marker — seqs delivered strictly
        increasing, delivered + dropped == appended, nothing silent."""
        log = FlowLog(capacity=64, mode="all")
        n_batches, per = 150, 7
        stop = threading.Event()

        def writer():
            for i in range(n_batches):
                fill(log, per, now=i)
                if i % 10 == 0:
                    time.sleep(0.001)
            stop.set()

        cur = FollowCursor(FlowObserver(log))
        # the follower holds a cursor before the writer starts: a fresh
        # attach past a wrapped ring is no gap (above), so a wrap ahead of
        # the first poll would be owed no marker
        fill(log, 1, now=0)
        delivered = [r["seq"] for r in cur.poll(limit=16)]
        assert delivered == [1]
        t = threading.Thread(target=writer)
        t.start()
        while not (stop.is_set() and cur.cursor >= log.newest_seq):
            for r in cur.poll(limit=16):
                if not r.get("gap"):
                    delivered.append(r["seq"])
        t.join()
        total = 1 + n_batches * per
        assert log.newest_seq == total
        # a guaranteed lap (scheduling-independent): one burst larger than
        # the whole ring lands between two polls — also exercises the
        # single-batch-bigger-than-capacity trim path
        fill(log, 200, now=999)
        for r in cur.poll():
            if not r.get("gap"):
                delivered.append(r["seq"])
        total += 200
        # strictly increasing — no duplicates, no reordering
        assert all(a < b for a, b in zip(delivered, delivered[1:]))
        # explicit accounting: what wasn't delivered was declared dropped
        assert len(delivered) + cur.dropped == total
        # the ring provably wrapped past the follower and said so
        assert cur.gaps >= 1 and cur.dropped >= 136


# --------------------------------------------------------------------------- #
# relay fan-in
# --------------------------------------------------------------------------- #
class TestRelay:
    def _three(self):
        logs = {f"node{i}": FlowLog(capacity=64, mode="all")
                for i in range(3)}
        # interleaved times across sources: node0 t=1, node1 t=2, node2 t=3,
        # then node0 again at t=9 (newest globally)
        fill(logs["node0"], 2, now=1)
        fill(logs["node1"], 2, now=2)
        fill(logs["node2"], 2, now=3)
        fill(logs["node0"], 1, now=9)
        return logs

    def test_oneshot_merge_orders_and_tags(self):
        relay = FlowRelay(self._three())
        res = relay.observe()
        flows = res["flows"]
        assert len(flows) == 7
        times = [r["time"] for r in flows]
        assert times == sorted(times)
        assert flows[-1]["node"] == "node0" and flows[-1]["time"] == 9
        assert set(res["sources"]) == {"node0", "node1", "node2"}
        # last= is a GLOBAL window, not per-source
        res = relay.observe(last=3)
        assert len(res["flows"]) == 3
        assert res["flows"][-1]["time"] == 9

    def test_oneshot_last_zero_is_the_full_retained_window(self):
        """last=0 must not silently truncate a source to the observer's
        default one-shot cap: every retained record fans in."""
        log = FlowLog(capacity=8192, mode="all")
        for _ in range(3):             # 6000 retained > the default 4096
            fill(log, 2000, now=1)     # one-shot limit, under the per-
        relay = FlowRelay({"big": log})   # append extract cap
        res = relay.observe()
        assert len(res["flows"]) == 6000
        assert res["sources"]["big"]["matched"] == 6000

    def test_poll_cursors_lag_and_gap_reemission(self):
        m = Metrics()
        logs = self._three()
        relay = FlowRelay(logs, metrics=m)
        res = relay.poll()
        assert len(res["flows"]) == 7 and res["gaps"] == []
        assert all(v == 0 for v in res["lag"].values())
        assert relay.cursors()["node0"] == 3
        # wrap node1 past its cursor: 70 records through a 64-slot ring
        for i in range(10):
            fill(logs["node1"], 7, now=20 + i)
        res = relay.poll()
        assert len(res["gaps"]) == 1
        g = res["gaps"][0]
        # node1's cursor sat at seq 2; 70 appends through a 64-slot ring
        # retain 9..72 — seqs 3..8 are the declared loss
        assert g["node"] == "node1" and g["dropped"] == 6
        # the gap marker leads its source's run in the merged stream
        node1_rows = [r for r in res["flows"] if r["node"] == "node1"]
        assert node1_rows[0].get("gap") is True
        assert len(node1_rows) == 1 + 64
        assert m.counters["relay_source_gaps_total"] == 1
        assert 'relay_source_lag{source="node1"}' in m.gauges

    def test_poll_truncation_shows_lag(self):
        logs = {"a": FlowLog(capacity=256, mode="all")}
        fill(logs["a"], 100)
        relay = FlowRelay(logs)
        res = relay.poll(limit=30)
        assert len(res["flows"]) == 30
        assert res["lag"]["a"] == 70       # behind by what it didn't page
        res = relay.poll(limit=100)
        assert res["lag"]["a"] == 0

    def test_fan_in_over_four_engines(self):
        """The single-host stand-in for ROADMAP item 3's multi-host tier:
        four engines classify disjoint flows; one relay merges their rings
        with node attribution and loses nothing."""
        engines = []
        try:
            for i in range(4):
                eng = setup_web(fake_engine(flowlog_mode="all"))
                slot_of = eng.active.snapshot.ep_slot_of
                recs = [pkt("192.168.1.10", f"10.{i}.2.{j + 1}",
                            41000 + 10 * i + j, 443) for j in range(3)]
                eng.classify(batch_from_records(recs, slot_of),
                             now=100 + i)
                engines.append(eng)
            relay = FlowRelay({f"host{i}": e.flowlog
                               for i, e in enumerate(engines)})
            res = relay.poll()
            assert len(res["flows"]) == 12 and not res["gaps"]
            by_node = {n: sum(1 for r in res["flows"] if r["node"] == n)
                       for n in relay.cursors()}
            assert by_node == {f"host{i}": 3 for i in range(4)}
            # provenance rides through the fan-in
            assert all(r["matched_rule"] >= 0 and r["lpm_prefix"] >= 0
                       for r in res["flows"])
            # filtered fan-in: a rule filter applies on every source
            rule = res["flows"][0]["matched_rule"]
            res2 = relay.observe(allow=[FlowFilter(rules=(rule,))])
            assert len(res2["flows"]) == 12
        finally:
            for e in engines:
                e.stop()


# --------------------------------------------------------------------------- #
# engine integration: provenance columns, rule counters, explain
# --------------------------------------------------------------------------- #
class TestEngineObserver:
    def test_observe_and_explain_through_engine(self):
        eng = setup_web(fake_engine(flowlog_mode="all"))
        try:
            eng.classify(web_batch(eng), now=100)   # 443 allow, 80/22 drop
            res = eng.observer.observe(
                allow=[FlowFilter(verdict="DROPPED")])
            assert res["matched"] == 2
            fwd = eng.observer.observe(
                allow=[FlowFilter(verdict="FORWARDED")])["flows"]
            assert len(fwd) == 1
            r = fwd[0]
            # the allowed flow names its evidence
            assert r["matched_rule"] >= 0 and r["lpm_prefix"] >= 0
            assert r["ct_state_pre"] == "NEW"
            legend = eng.explain_provenance(fwd)
            rinfo = legend["rules"][str(r["matched_rule"])]
            assert rinfo["resolved"]
            pinfo = legend["prefixes"][str(r["lpm_prefix"])]
            assert pinfo["resolved"] and "10.0.0.0" in pinfo["prefix"]
            # rule filter round-trips: every flow this cell decided
            again = eng.observer.observe(
                allow=[FlowFilter(rules=(r["matched_rule"],),
                                  verdict="FORWARDED")])
            assert again["matched"] == 1
        finally:
            eng.stop()

    def test_rule_hit_counters_render(self):
        eng = setup_web(fake_engine(flowlog_mode="all"))
        try:
            for i in range(3):
                eng.classify(web_batch(eng), now=100 + i)
            text = eng.render_metrics()
            hit_lines = [ln for ln in text.splitlines()
                         if "policy_rule_hits_total{rule=" in ln]
            drop_lines = [ln for ln in text.splitlines()
                          if "policy_rule_drops_total{rule=" in ln]
            assert hit_lines and drop_lines
            # 3 batches x 1 allowed row through the ladder
            assert sum(int(float(ln.rsplit(" ", 1)[1]))
                       for ln in hit_lines) == 3
            # 3 batches x 2 denied rows (80 + 22)
            assert sum(int(float(ln.rsplit(" ", 1)[1]))
                       for ln in drop_lines) == 6
            # labels resolve to the ic/pc[/id] tag form
            assert 'rule="ic' in hit_lines[0]
        finally:
            eng.stop()

    def test_rule_label_cardinality_cap(self):
        eng = setup_web(fake_engine(flowlog_mode="all",
                                    rule_metrics_max=1))
        try:
            eng.classify(web_batch(eng), now=100)   # ≥2 distinct cells
            text = eng.render_metrics()
            labels = {ln.split('rule="')[1].split('"')[0]
                      for ln in text.splitlines()
                      if "policy_rule_" in ln and "rule=" in ln}
            assert "other" in labels
            assert len(labels - {"other"}) <= 1
        finally:
            eng.stop()

    def test_rule_counters_disabled(self):
        eng = setup_web(fake_engine(flowlog_mode="all", rule_metrics_max=0))
        try:
            eng.classify(web_batch(eng), now=100)
            assert "policy_rule_" not in eng.render_metrics()
        finally:
            eng.stop()

    def test_blackbox_verdict_summary_carries_provenance(self):
        eng = setup_web(fake_engine(flowlog_mode="all"))
        try:
            eng.classify(web_batch(eng), now=100)
            bundle = eng.debug_bundle()
            vs = bundle["verdict_summaries"][-1]
            assert vs["dropped"] == 2
            assert vs["top_drop_rules"] and vs["top_drop_prefixes"]
            assert vs["drop_ct_states"]
        finally:
            eng.stop()

    def test_api_observe_route(self, tmp_path):
        from cilium_tpu.runtime.api import APIServer, UnixAPIClient
        eng = setup_web(fake_engine(flowlog_mode="all"))
        sock = str(tmp_path / "api.sock")
        srv = APIServer(eng, sock)
        srv.start()
        try:
            eng.classify(web_batch(eng), now=100)
            client = UnixAPIClient(sock)
            code, res = client.get(
                "/v1/flows/observe?verdict=DROPPED&explain=1")
            assert code == 200 and res["matched"] == 2
            assert all(r["verdict"] == "DROPPED" for r in res["flows"])
            assert "legend" in res and res["legend"]["revision"] >= 0
            # follow from the returned cursor: drained, then new records
            cursor = res["cursor"]
            code, res = client.get(f"/v1/flows/observe?since={cursor}")
            assert code == 200 and res["flows"] == []
            eng.classify(web_batch(eng), now=101)
            code, res = client.get(f"/v1/flows/observe?since={cursor}")
            assert code == 200 and len(res["flows"]) == 3
            # denylist param
            code, res = client.get("/v1/flows/observe?not_verdict=DROPPED")
            assert code == 200
            assert all(r["verdict"] == "FORWARDED" for r in res["flows"])
            # bad filter → 400, not 500
            code, res = client.get("/v1/flows/observe?reason=BOGUS")
            assert code == 400
            # ... including bad CIDRs and bad DENYLIST verdicts (which
            # must never silently filter as the wrong polarity)
            code, res = client.get("/v1/flows/observe?cidr=banana")
            assert code == 400
            code, res = client.get("/v1/flows/observe?not_verdict=FORWARD")
            assert code == 400
            # percent-encoded values decode (the CLI quotes '/' in CIDRs)
            code, res = client.get(
                "/v1/flows/observe?dst_cidr=10.0.0.0%2F8")
            assert code == 200 and res["matched"] == 6
            # repeated not_* keys accumulate (repeatable --not flags) and
            # independent deny KEYS each exclude on their own (OR, not AND)
            code, res = client.get(
                "/v1/flows/observe?not_dport=80&not_dport=22")
            assert code == 200
            assert {r["dst_port"] for r in res["flows"]} == {443}
            code, res = client.get(
                "/v1/flows/observe?not_verdict=FORWARDED&not_dport=22")
            assert code == 200 and res["flows"]
            assert all(r["verdict"] == "DROPPED" and r["dst_port"] == 80
                       for r in res["flows"])
            # observer counters surfaced in /v1/status
            code, st = client.get("/v1/status")
            assert code == 200 and st["observer"]["queries"] >= 4
        finally:
            srv.stop()
            eng.stop()

    def test_cli_observe(self, tmp_path, capsys):
        from cilium_tpu.cli.main import main as cli_main
        from cilium_tpu.runtime.api import APIServer
        eng = setup_web(fake_engine(flowlog_mode="all"))
        sock = str(tmp_path / "api.sock")
        srv = APIServer(eng, sock)
        srv.start()
        try:
            eng.classify(web_batch(eng), now=100)
            rc = cli_main(["observe", "--api", sock,
                           "--verdict", "DROPPED"])
            out = capsys.readouterr().out
            assert rc == 0
            lines = [ln for ln in out.splitlines() if ln]
            assert len(lines) == 2
            # the one-line provenance rendering: verdict + evidence
            assert all("because rule" in ln and "/ CT " in ln
                       for ln in lines)
            assert all("DROPPED" in ln for ln in lines)
            # allowed flow resolves its winning prefix in the legend
            rc = cli_main(["observe", "--api", sock,
                           "--verdict", "FORWARDED"])
            out = capsys.readouterr().out
            assert rc == 0 and "prefix 10.0.0.0/8" in out
            # json mode emits records
            rc = cli_main(["observe", "--api", sock, "-o", "json",
                           "--not", "verdict=DROPPED"])
            out = capsys.readouterr().out
            assert rc == 0
            import json as _json
            recs = [_json.loads(ln) for ln in out.splitlines() if ln]
            assert all(r["verdict"] == "FORWARDED" for r in recs)
        finally:
            srv.stop()
            eng.stop()


# --------------------------------------------------------------------------- #
# concurrent {rule=} scrape during a sharded soak + follower racing wrap
# --------------------------------------------------------------------------- #
class TestScrapeRaceRuleLabels:
    def test_rule_family_scrape_races_sharded_soak_with_follower(self):
        """The satellite race: an 8-shard soak (auditor armed at 1.0 — the
        provenance columns are part of the audited surface) while (a) two
        scrapers hammer render_metrics asserting every exposition parses
        with the {rule=} family present and one TYPE per base, and (b) a
        follow-mode observer races the deliberately tiny flowlog ring —
        wraparound under load must surface as explicit gaps, with
        delivered + dropped == appended."""
        eng = sharded_audited_engine(flowlog_mode="all",
                                     flowlog_capacity=128)
        setup_web(eng)
        chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=16,
                           rows_per_chunk=8)
        errors = []
        stop = threading.Event()

        def scraper():
            seen_rule_family = False
            while not stop.is_set():
                try:
                    text = eng.render_metrics()
                    types = set()
                    for ln in text.splitlines():
                        if ln.startswith("# TYPE"):
                            assert "{" not in ln, f"labeled TYPE: {ln}"
                            base = ln.split()[2]
                            assert base not in types, f"dup TYPE {base}"
                            types.add(base)
                    seen_rule_family |= "policy_rule_hits_total{" in text
                except Exception as e:   # noqa: BLE001
                    errors.append(e)
                    return
            if not seen_rule_family:
                errors.append(AssertionError("no {rule=} family scraped"))

        cur = FollowCursor(FlowObserver(eng.flowlog))
        delivered = [0]

        def follower():
            try:
                while not stop.is_set() or cur.cursor < eng.flowlog.newest_seq:
                    for r in cur.poll(limit=32):
                        if not r.get("gap"):
                            delivered[0] += 1
                    time.sleep(0.002)
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=scraper, daemon=True)
                   for _ in range(2)]
        threads.append(threading.Thread(target=follower, daemon=True))
        for t in threads:
            t.start()
        try:
            eng.start_pipeline()
            for round_ in range(3):
                tickets = [eng.submit(dict(ch), now=100 + i)
                           for i, ch in enumerate(chunks)]
                assert eng.drain(timeout=30)
                for tk in tickets:
                    tk.result(timeout=5)
            eng.audit_step(budget=None)
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0 and st["mismatched_rows"] == 0
        finally:
            stop.set()
            for t in threads:
                t.join(10)
            eng.stop()
        assert not errors, errors[:1]
        # follower accounting over the whole soak (ring wrapped ~3x)
        total = eng.flowlog.newest_seq
        assert total > eng.flowlog.capacity
        assert delivered[0] + cur.dropped == total


# --------------------------------------------------------------------------- #
# slow soaks: the observer's overhead attestation + relay fan-in phase
# --------------------------------------------------------------------------- #
@pytest.mark.slow
class TestObserverOverheadSoak:
    def test_follow_filters_armed_under_two_percent(self):
        """The <2% contract in the PR 3 attestation form: (1) the precise,
        deterministic measurement — incremental follow-mode polling with a
        compound filter armed (verdict + ports + CIDR: the masks, the
        since-cursor column slice, and the matched-row rendering) costs,
        per appended batch, under 2% of the measured per-submission
        pipeline cost; (2) an interleaved end-to-end soak with a live
        follower thread as a loose gross-regression bound (wall-clock on
        a multi-threaded pipeline carries scheduler noise above 2%)."""
        import gc
        # 64-row chunks: the representative serving shape (the pipeline
        # coalesces toward batch_size=64 buckets) — an 8-row toy chunk
        # would understate the submit path the 2% is measured against
        eng = setup_web(fake_engine(flowlog_mode="all",
                                    pipeline_min_bucket=16))
        chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=8,
                           rows_per_chunk=64)
        # armed-but-selective (the needle case a follow filter exists
        # for): the full mask set runs every poll, but almost nothing
        # matches — matched rows are delivered PAYLOAD the consumer asked
        # for, not overhead, so the overhead contract measures the scan
        filters = [FlowFilter(verdict="DROPPED", dports=(9999,),
                              dst_cidrs=("10.0.0.0/8",))]

        def one_pass(n_rounds=4):
            t0 = time.perf_counter()
            n = 0
            for _r in range(n_rounds):
                for i, ch in enumerate(chunks):
                    eng.submit(dict(ch), now=1000 + i)
                    n += 1
                assert eng.drain(timeout=60)
            return (time.perf_counter() - t0) / n

        # micro: append+incremental-poll vs append-only, same ring geometry
        # and per-batch row count as the pipeline soak. The follower polls
        # once per 4 appended batches — a follower on a 1ms wall cadence
        # sees well over 4 batches per tick at soak throughput, so this is
        # the conservative end of the realistic cadence range. The armed
        # filter is selective (the needle case a follow filter exists
        # for): one row per poll window matches and pays its rendering.
        log = FlowLog(capacity=eng.config.flowlog_capacity, mode="all")
        b_plain, o_plain = mk_batch_out(
            64, allow=False, reason=int(C.DropReason.POLICY_DENY), dport=80)
        b_hit, o_hit = mk_batch_out(
            64, allow=False, reason=int(C.DropReason.POLICY_DENY), dport=80)
        b_hit["dport"][0] = 22           # the needle
        micro_filters = [FlowFilter(verdict="DROPPED", dports=(22,),
                                    dst_cidrs=("10.0.0.0/8",))]
        cur = FollowCursor(FlowObserver(log), allow=micro_filters)
        reps = 600

        def micro_pass(poll):
            t0 = time.perf_counter()
            for _ in range(reps):
                for bb, oo in ((b_plain, o_plain), (b_plain, o_plain),
                               (b_plain, o_plain), (b_hit, o_hit)):
                    log.append_batch(bb, oo, now=1, ep_ids=(1,))
                if poll:
                    cur.poll()
            return (time.perf_counter() - t0) / (reps * 4)

        one_pass(2)                      # warmup the pipeline path
        micro_pass(True)                 # warmup the micro path
        gc_was = gc.isenabled()
        gc.disable()
        try:
            micro_off = min(micro_pass(False) for _ in range(5))
            micro_on = min(micro_pass(True) for _ in range(5))

            off, on = [], []
            for _i in range(3):          # interleaved A/B windows
                off.append(one_pass())
                stop = threading.Event()
                fcur = FollowCursor(FlowObserver(eng.flowlog),
                                    allow=filters)

                def follow():
                    while not stop.is_set():
                        fcur.poll(limit=4096)
                        time.sleep(0.001)

                th = threading.Thread(target=follow, daemon=True)
                th.start()
                try:
                    on.append(one_pass())
                finally:
                    stop.set()
                    th.join(5)
        finally:
            if gc_was:
                gc.enable()
        per_submit = min(off)
        delta = micro_on - micro_off     # true per-batch follow cost
        frac = delta / per_submit
        assert frac < 0.02, \
            f"filters-armed follow adds {delta * 1e6:.1f}us/batch = " \
            f"{frac:.2%} of the {per_submit * 1e6:.1f}us submit path " \
            f"(budget 2%)"
        # the gross bound is LOOSE by design: the oracle-backed fake
        # engine is GIL-bound pure Python, so a concurrent poll thread
        # costs wall-clock far beyond its measured CPU (scheduler ping-
        # pong) — the precise 2% contract is the micro above (on a real
        # datapath device compute releases the GIL). This guards against
        # catastrophic regressions only (a lock held across the scan, a
        # render of unmatched rows).
        assert min(on) <= min(off) * 1.6, \
            f"end-to-end regression: off={min(off) * 1e6:.1f}us " \
            f"on={min(on) * 1e6:.1f}us"
        eng.stop()


class _Sharded4(FakeDatapath):
    pipeline_shards = 4


@pytest.mark.slow
class TestRelayFanInPhase:
    def test_relay_follows_live_4shard_mesh_plus_peers(self):
        """The relay fan-in phase: one 4-shard mesh engine under
        pipelined load + three plain engines classifying, all four rings
        fanned in by one live-polling relay. Every source's records are
        either merged (node-tagged, time-ordered per poll) or declared in
        a gap; the sharded engine's auditor (sampling 1.0 — provenance is
        part of the audited surface) stays clean throughout."""
        cfg = DaemonConfig(ct_capacity=4096, auto_regen=False,
                           batch_size=64, audit_enabled=True,
                           audit_sample_rate=1.0, flowlog_mode="all",
                           flowlog_capacity=256)
        mesh_eng = Engine(cfg, datapath=_Sharded4(cfg))
        setup_web(mesh_eng)
        peers = [setup_web(fake_engine(flowlog_mode="all"))
                 for _ in range(3)]
        engines = [mesh_eng] + peers
        relay = FlowRelay(
            {f"host{i}": e.flowlog for i, e in enumerate(engines)})
        delivered = {f"host{i}": 0 for i in range(4)}
        merged_ok = [True]
        stop = threading.Event()

        def pump_relay():
            while True:
                res = relay.poll(limit=64)
                for r in res["flows"]:
                    if not r.get("gap"):
                        delivered[r["node"]] += 1
                # per-poll merge ordering: (time, seq) nondecreasing per
                # node run is guaranteed by ring order; check global time
                # ordering of the merged page
                times = [r["time"] for r in res["flows"] if "time" in r]
                if times != sorted(times):
                    merged_ok[0] = False
                if stop.is_set() and not res["flows"]:
                    return
                time.sleep(0.002)

        th = threading.Thread(target=pump_relay, daemon=True)
        th.start()
        try:
            pl = mesh_eng.start_pipeline()
            assert pl.stats()["n_shards"] == 4
            chunks = mk_chunks(mesh_eng.active.snapshot.ep_slot_of,
                               n_chunks=16, rows_per_chunk=8)
            for round_ in range(3):
                tickets = [mesh_eng.submit(dict(ch), now=100 + i)
                           for i, ch in enumerate(chunks)]
                for peer in peers:
                    peer.classify(web_batch(peer), now=200 + round_)
                assert mesh_eng.drain(timeout=30)
                for tk in tickets:
                    tk.result(timeout=5)
            mesh_eng.audit_step(budget=None)
            st = mesh_eng.auditor.stats()
            assert st["checked_rows"] > 0 and st["mismatched_rows"] == 0
        finally:
            stop.set()
            th.join(15)
            for e in engines:
                e.stop()
        assert merged_ok[0], "merged page left time order"
        # fan-in accounting per source: delivered + declared-dropped ==
        # appended (no silent loss through the relay either)
        cursors = relay.cursors()
        for i, e in enumerate(engines):
            assert cursors[f"host{i}"] == e.flowlog.newest_seq
        got = sum(delivered.values())
        appended = sum(e.flowlog.newest_seq for e in engines)
        dropped = sum(
            o.flowlog.follow_gap_records
            for o in relay.observers.values())
        assert got + dropped == appended
        # the mesh engine's ring (256 slots vs ~384 rows) must have lapped
        # at least once if the follower ever fell behind — either way the
        # equality above proves nothing vanished silently
        assert delivered["host1"] == delivered["host2"] == \
            delivered["host3"]


# --------------------------------------------------------------------------- #
# JSONL file-tail source (ISSUE 12: the cross-process relay transport)
# --------------------------------------------------------------------------- #
def _jsonl_rec(seq, t=100, allow=True, dport=443, src="10.1.0.5",
               dst="10.2.0.9"):
    """A record in the flowlog JSONL sink's wire format (render_flow)."""
    return {"time": t, "verdict": "FORWARDED" if allow else "DROPPED",
            "drop_reason": 0 if allow else 133, "ct_state": "NEW",
            "src_ip": src, "dst_ip": dst, "src_port": 40000 + seq,
            "dst_port": dport, "proto": "TCP", "direction": "ingress",
            "endpoint_id": 1, "remote_identity": 1234,
            "matched_rule": 3, "lpm_prefix": 0, "ct_state_pre": "NEW",
            "seq": seq}


def _append(path, recs):
    import json as _json
    with open(path, "a") as f:
        for r in recs:
            f.write(_json.dumps(r) + "\n")


class TestJsonlTail:
    def test_tail_incremental_and_follow(self, tmp_path):
        from cilium_tpu.observe.relay import JsonlTailObserver
        p = str(tmp_path / "n0.jsonl")
        _append(p, [_jsonl_rec(s, t=100 + s) for s in range(1, 4)])
        obs = JsonlTailObserver(p)
        res = obs.observe()
        assert [r["seq"] for r in res["flows"]] == [1, 2, 3]
        cursor = res["cursor"]
        # nothing new: empty page, cursor stable
        res = obs.observe(since=cursor)
        assert res["flows"] == [] and res["cursor"] == cursor
        # appended bytes picked up mid-file, only the new records paged
        _append(p, [_jsonl_rec(s, t=100 + s) for s in range(4, 6)])
        res = obs.observe(since=cursor)
        assert [r["seq"] for r in res["flows"]] == [4, 5]

    def test_partial_line_and_garbage(self, tmp_path):
        """A torn trailing line (writer mid-append) is held until its
        newline arrives; a garbage line is counted, not fatal."""
        from cilium_tpu.observe.relay import JsonlTailObserver
        import json as _json
        p = str(tmp_path / "n0.jsonl")
        obs = JsonlTailObserver(p)
        with open(p, "w") as f:
            f.write(_json.dumps(_jsonl_rec(1)) + "\n")
            f.write('{"seq": 2, "torn')     # no newline yet
        assert obs.poll_file() == 1
        with open(p, "a") as f:             # the rest of the line lands
            f.write('": true, "time": 5}\n')
            f.write("not json at all\n")
            f.write(_json.dumps(_jsonl_rec(3)) + "\n")
        obs.poll_file()
        assert [r["seq"] for r in obs.observe()["flows"]] == [1, 2, 3]
        assert obs.parse_errors == 1

    def test_truncation_resyncs_from_top(self, tmp_path):
        from cilium_tpu.observe.relay import JsonlTailObserver
        p = str(tmp_path / "n0.jsonl")
        _append(p, [_jsonl_rec(s) for s in range(1, 4)])
        obs = JsonlTailObserver(p)
        obs.poll_file()
        # rotation: the file is replaced with a shorter one, same writer
        # session continuing its seq counter
        os_mod = __import__("os")
        os_mod.unlink(p)
        _append(p, [_jsonl_rec(4)])
        obs.poll_file()
        assert obs.newest_seq == 4
        seqs = [r["seq"] for r in obs.observe()["flows"]]
        assert seqs == [1, 2, 3, 4]

    def test_writer_restart_rebases_seq(self, tmp_path):
        """A restarted engine's ring starts over at seq 1. The tail keeps
        its own stream monotonic by rebasing — new-session records are
        kept, never dropped as duplicates."""
        from cilium_tpu.observe.relay import JsonlTailObserver
        p = str(tmp_path / "n0.jsonl")
        _append(p, [_jsonl_rec(s) for s in range(1, 4)])
        obs = JsonlTailObserver(p)
        obs.poll_file()
        _append(p, [_jsonl_rec(1, t=500), _jsonl_rec(2, t=501)])
        obs.poll_file()
        assert obs.writer_restarts == 1
        seqs = [r["seq"] for r in obs.observe()["flows"]]
        assert seqs == [1, 2, 3, 4, 5]      # rebased, strictly increasing

    def test_bounded_window_gaps_and_filters(self, tmp_path):
        from cilium_tpu.observe.relay import JsonlTailObserver
        p = str(tmp_path / "n0.jsonl")
        _append(p, [_jsonl_rec(s, allow=s % 2 == 0) for s in range(1, 11)])
        obs = JsonlTailObserver(p, capacity=4)   # retains seqs 7..10
        res = obs.observe(since=2)
        assert res["gap"] == {"gap": True, "dropped": 4, "resume_seq": 7}
        assert [r["seq"] for r in res["flows"]] == [7, 8, 9, 10]
        # the same FlowFilter surface the in-memory observer serves
        res = obs.observe(allow=(FlowFilter(verdict="DROPPED"),))
        assert all(r["verdict"] == "DROPPED" for r in res["flows"])
        assert [r["seq"] for r in res["flows"]] == [7, 9]
        res = obs.observe(allow=(FlowFilter(dports=(443,),
                                            cidrs=("10.1.0.0/16",)),))
        assert res["matched"] == 4

    def test_relay_fans_in_tailed_files(self, tmp_path):
        """Two nodes' JSONL sinks → one merged node-tagged stream: the
        multi-host transport under the same FlowRelay merge."""
        from cilium_tpu.observe.relay import FlowRelay, JsonlTailObserver
        pa = str(tmp_path / "a.jsonl")
        pb = str(tmp_path / "b.jsonl")
        _append(pa, [_jsonl_rec(s, t=100 + 2 * s) for s in range(1, 4)])
        _append(pb, [_jsonl_rec(s, t=101 + 2 * s) for s in range(1, 4)])
        relay = FlowRelay({"node-a": JsonlTailObserver(pa),
                           "node-b": JsonlTailObserver(pb)})
        res = relay.poll()
        assert len(res["flows"]) == 6
        times = [r["time"] for r in res["flows"]]
        assert times == sorted(times)
        assert {r["node"] for r in res["flows"]} == {"node-a", "node-b"}
        # live append on one node: only its new records page in
        _append(pb, [_jsonl_rec(4, t=200)])
        res = relay.poll()
        assert [(r["node"], r["seq"]) for r in res["flows"]] \
            == [("node-b", 4)]
