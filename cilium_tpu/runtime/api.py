"""Slim REST API over a unix socket (SURVEY.md §1 layer 7 "slim REST/gRPC
analog"; upstream: the cilium-agent `api/v1` go-swagger server on
/var/run/cilium/cilium.sock — §3.1 "api server up (unix socket REST)").

Stdlib-only (http.server over a Unix stream socket), JSON bodies, thread-per
-connection. The handlers read the LIVE engine — this is the difference from
the offline CLI, which reads checkpoint files; `cilium-tpu --api <sock>`
drives these routes (cli/commands.py).

Routes (all under /v1):
  GET  /v1/healthz            liveness + policy revision + degradation state
                              (OK/DEGRADED/STALE, consecutive regen failures)
  GET  /v1/status             agent summary (endpoints/identities/rules/CT)
  GET  /v1/endpoints          endpoint list
  GET  /v1/endpoints/<id>     one endpoint incl. per-direction policy size
  GET  /v1/identities         identity list
  GET  /v1/policy             rule documents
  POST /v1/policy             apply CNP-style rule documents (returns revision)
  POST /v1/policy/trace       {ep, direction, remote, dport, proto} → verdict
  GET  /v1/services           service/LB state
  GET  /v1/ct?limit=N&now=T   live conntrack entries
  GET  /v1/flows?last=N&verdict=V   flow log tail
  GET  /v1/flows/observe      vectorized filtered observe (the Hubble
                              Observe()/FlowFilter analog,
                              observe/observer.py): allow-filter params
                              verdict/reason/endpoint/identity/proto/
                              port/sport/dport/cidr/src_cidr/dst_cidr/
                              rule/direction (comma-lists OR within a
                              field, fields AND; ``not_``-prefixed params
                              build the denylist), last=N one-shot window,
                              since=SEQ follow mode with a structured
                              ``gap`` record on ring wraparound,
                              explain=1 attaches the provenance legend
                              (matched rule → id/port class + identity,
                              lpm_prefix → canonical ipcache prefix)
  GET  /v1/flows/metrics?last=N     windowed flow-metrics time-series +
                              cumulative totals (the hubble metrics analog)
  GET  /v1/trace?limit=N&name=S     sampled span ring + per-stage summary
                              (observe/trace.py; empty when tracing is off;
                              stats carry spans_dropped_total + ring_wraps —
                              the drop-oldest loss accounting)
  GET  /v1/resources          resource pressure ledger (observe/pressure.py):
                              one row per registered bounded structure —
                              capacity, occupancy, pressure, high-water,
                              time-to-exhaustion forecast — plus the
                              device-side HBM ledger (bytes per placed
                              tensor group) and any attached offline
                              verifier budget report. Backs `cilium-tpu top`
  GET  /v1/debug/bundle?clear=1     flight-recorder debug bundle
                              (observe/blackbox.py): the frozen anomaly
                              bundle when one exists (parity mismatch,
                              breaker open, watchdog restart, shed spike),
                              else a live snapshot; ?clear=1 re-arms the
                              recorder after the fetch
  GET  /v1/fqdn/cache         learned DNS names
  GET  /v1/metrics            Prometheus text (text/plain), incl. flow
                              metrics totals
  GET  /v1/config             daemon config echo (runtime-mutable subset)
  PATCH /v1/config            {"enforcement_mode": ...} (upstream: `cilium
                              config PolicyEnforcement=...`)
  GET  /v1/health             datapath health probe through real classify
  POST /v1/classify           serve a batch of flows through the ingestion
                              pipeline ({"records": [{src,dst,sport,dport,
                              proto,ep,direction},...]}); Ticket.result()
                              is bounded by config.pipeline_request_timeout_s
                              — overload shed (queue full / deadline) maps
                              to 429, breaker-open / hard-failed / timeout
                              to 503, always with a JSON error body
  POST /v1/regenerate         force a recompile
  GET  /v1/faults             fault-injection point list + fire/trip stats
  POST /v1/faults             arm ({"spec": "point=mode:..."}) or disarm
                              ({"disarm": "*"}) injection points (chaos CLI)
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import threading
from http.server import BaseHTTPRequestHandler
from typing import TYPE_CHECKING, Dict, Optional, Tuple
from urllib.parse import unquote

from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C

if TYPE_CHECKING:
    from cilium_tpu.runtime.engine import Engine


class _UnixHTTPServer(socketserver.ThreadingUnixStreamServer):
    daemon_threads = True
    allow_reuse_address = True


class APIServer:
    """Owns the unix socket + serving thread; route logic in _Handler."""

    def __init__(self, engine: "Engine", socket_path: str):
        self.engine = engine
        self.socket_path = socket_path
        self._server: Optional[_UnixHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._server is not None:
            return
        d = os.path.dirname(self.socket_path)
        if d and not os.path.isdir(d):
            # owner + group only: the socket dir is the auth boundary
            # (upstream: /var/run/cilium is root:cilium 0750). chmod after
            # makedirs because the mode= arg is masked by the umask; only
            # dirs WE create are tightened — never a pre-existing shared
            # parent like /tmp
            os.makedirs(d, mode=0o750, exist_ok=True)
            os.chmod(d, 0o750)
        if os.path.exists(self.socket_path):
            # probe before unlinking: a live server answering on the path
            # means another agent owns it — error out instead of silently
            # stealing its socket (two agents would corrupt each other's
            # state dir); only a dead leftover from a crash is removed
            probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            probe.settimeout(0.5)
            try:
                probe.connect(self.socket_path)
            except (ConnectionRefusedError, FileNotFoundError):
                # ECONNREFUSED is the only proof of death (nobody accepts
                # on the path — a crashed agent's leftover, or a stray
                # regular file); reclaim it. ENOENT means the owner removed
                # it between our exists() check and the probe — same unlink,
                # but the path may already be gone
                try:
                    os.unlink(self.socket_path)
                except FileNotFoundError:
                    pass
            except OSError as e:
                # timeout / EAGAIN / EACCES: a live-but-busy owner (e.g.
                # mid-compile with a full backlog) looks exactly like this
                # — anything we cannot prove dead must not be stolen
                raise RuntimeError(
                    f"cannot prove the server on {self.socket_path} is "
                    f"dead ({e}); refusing to steal its socket")
            else:
                raise RuntimeError(
                    f"another server is live on {self.socket_path}; "
                    "refusing to steal its socket")
            finally:
                probe.close()
        engine = self.engine

        class Handler(_Handler):
            pass

        Handler.engine = engine
        # the API mutates policy (POST /v1/policy) and enforcement mode:
        # restrict to the owning user before serving a single request. The
        # umask makes the socket 0600 AT BIND — a chmod after bind would
        # leave a window where another user can connect and sit in the
        # listen backlog until serve_forever picks the connection up
        old_umask = os.umask(0o177)
        try:
            self._server = _UnixHTTPServer(self.socket_path, Handler)
        finally:
            os.umask(old_umask)
        os.chmod(self.socket_path, 0o600)    # belt and braces
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="cilium-tpu-api", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            if os.path.exists(self.socket_path):
                os.unlink(self.socket_path)


# --------------------------------------------------------------------------- #
# document builders (shapes shared with the offline CLI so the same text
# renderers work on live and checkpoint data)
# --------------------------------------------------------------------------- #
def status_doc(engine: "Engine") -> Dict:
    import time
    now = int(time.time())
    ct = engine.ct_stats(now)
    return {
        "revision": engine.repo.revision,
        "endpoints": len(engine.endpoints),
        "identities": len(list(engine.ctx.allocator.all())),
        "rules": len(engine.repo),
        "ipcache_entries": len(engine.ctx.ipcache.snapshot()),
        "services": len(engine.ctx.services.all()),
        "conntrack": {"capacity": ct["capacity"], "live": ct["live"]},
        "enforcement_mode": engine.ctx.enforcement_mode,
        # which device serves (None on jax-free backends): what the config
        # asked for, and the platform / device_kind / count JAX reports
        "device": getattr(engine.datapath, "device_state", None),
        # flow→shard resolution surface (None on jax-free backends): host
        # steering vs the device-side ppermute exchange (rss_mode)
        "rss": getattr(engine.datapath, "rss_state", None),
        # None until the ingestion pipeline has been started
        "pipeline": engine.pipeline_stats(),
        # None until a shim feeder is attached (Engine.start_feeder)
        "feeder": engine.feeder_stats(),
        # None until the overload controller has observed an interval
        "overload": engine.overload_status(),
        # None unless multi-tenant QoS is armed (qos_enabled): tenant
        # table + live per-tenant admission queue depths/admitted shares
        "qos": engine.qos_status(),
        # in-band DNS plane (ISSUE 18): cache occupancy/bounds, proxy
        # learning/parse-error counters, refresh coalescing, identity
        # lifecycle — always present (the cache exists proxy or not)
        "fqdn": engine.fqdn_status(),
        # None until the autotune controller has run against a pipeline
        "autotune": engine.autotune_status(),
        "trace": engine.tracer.stats(),
        # verdict provenance: parity-audit counters + flight-recorder state
        "audit": engine.auditor.stats(),
        "blackbox": engine.blackbox.stats(),
        # vectorized flow-observe engine (observe/observer.py): query +
        # follow-gap accounting over the columnar flowlog ring
        "observer": engine.observer.stats(),
        # resource pressure ledger summary (observe/pressure.py): the
        # pressured list + soonest exhaustion forecast without the full
        # per-resource table (/v1/resources has that)
        "resources": engine.ledger.status(),
        # device-memory truth (ISSUE 13 satellite): the live HBM ledger
        # and the offline verifier budget report cite the same numbers
        "hbm": engine.hbm_status(),
        # None until a ClusterMesh is attached (cluster_store+node_name):
        # per-peer generation/lag, store reachability, staleness verdict,
        # conflict map, replication-lag p99 (runtime/clustermesh.status)
        "mesh": engine.mesh_status(),
    }


def endpoints_doc(engine: "Engine"):
    return [{"ep_id": ep.ep_id, "identity": ep.identity_id,
             "ips": list(ep.ips), "labels": list(ep.labels.to_strings()),
             "enforcement": ep.enforcement,
             "policy_revision": ep.policy_revision}
            for ep in sorted(engine.endpoints.values(),
                             key=lambda e: e.ep_id)]


def endpoint_doc(engine: "Engine", ep_id: int) -> Optional[Dict]:
    ep = engine.endpoints.get(ep_id)
    if ep is None:
        return None
    pol = engine.repo.resolve(ep)
    return {
        "ep_id": ep.ep_id, "identity": ep.identity_id,
        "ips": list(ep.ips), "labels": list(ep.labels.to_strings()),
        "enforcement": ep.enforcement,
        "policy_revision": pol.revision,
        "egress": {"enforced": pol.egress.enforced,
                   "entries": len(pol.egress.mapstate.items())},
        "ingress": {"enforced": pol.ingress.enforced,
                    "entries": len(pol.ingress.mapstate.items())},
    }


def identities_doc(engine: "Engine"):
    return [{"id": ident.id, "labels": list(ident.labels.to_strings()),
             "reserved": ident.id < C.CLUSTER_IDENTITY_BASE,
             "local": bool(ident.id & C.LOCAL_IDENTITY_SCOPE)}
            for ident in engine.ctx.allocator.all()]


def services_doc(engine: "Engine"):
    return [{"name": s.name, "namespace": s.namespace,
             "backends": list(s.backends),
             "frontends": [{"addr": f.addr, "port": f.port,
                            "proto": f.proto, "kind": f.kind}
                           for f in s.frontends]}
            for s in engine.ctx.services.all()]


def fqdn_doc(engine: "Engine"):
    return [{"name": name, "ips": {ip: exp for ip, exp in sorted(e.items())}}
            for name, e in engine.ctx.fqdn_cache.names()]


def ct_doc(engine: "Engine", limit: int, now: Optional[int]):
    import time
    import numpy as np
    from cilium_tpu.utils.ip import addr_to_str, words_to_addr
    arrays = engine.ct_arrays()
    if now is None:
        now = int(time.time())
    live = np.nonzero(arrays["expiry"] > now)[0][:limit]
    out = []
    for slot in live:
        w = arrays["keys"][slot]
        out.append({
            "src": addr_to_str(words_to_addr(w[0:4])),
            "dst": addr_to_str(words_to_addr(w[4:8])),
            "sport": int(w[8]) >> 16, "dport": int(w[8]) & 0xFFFF,
            "proto": C.PROTO_NAMES.get(int(w[9]) >> 8, str(int(w[9]) >> 8)),
            "expires_in": int(arrays["expiry"][slot]) - now,
            "pkts_fwd": int(arrays["pkts_fwd"][slot]),
            "pkts_rev": int(arrays["pkts_rev"][slot]),
        })
    return out


def serving_error(exc: BaseException) -> Optional[Tuple[int, Dict]]:
    """Map a pipeline serving failure to (http_status, json_body), or None
    for errors that are not part of the overload/degradation error classes
    (those stay 500s). Overload shed → 429 (retryable: the pipeline is
    healthy but this submission lost the overload race); unavailability →
    503 (the backend is sick/restarting — back off)."""
    from cilium_tpu.pipeline.guard import (PipelineDeadlineExceeded,
                                           PipelineDrop, PipelineError)
    doc = {"error": str(exc), "kind": type(exc).__name__}
    if isinstance(exc, (PipelineDrop, PipelineDeadlineExceeded)):
        return 429, doc
    # every other PipelineError (PipelineUnavailable, PipelineClosed,
    # restart rejections) and a bounded-wait timeout → 503
    if isinstance(exc, (PipelineError, TimeoutError)):
        return 503, doc
    return None


def classify_doc(engine: "Engine", body: Dict) -> Tuple[int, Dict]:
    """The REST serving path: build a batch from JSON flow records, submit
    it through the ingestion pipeline, wait bounded, return verdicts."""
    from oracle import PacketRecord
    from cilium_tpu.kernels.records import batch_from_records
    from cilium_tpu.utils.ip import parse_addr

    records = body.get("records")
    if not records or not isinstance(records, list):
        return 400, {"error": "classify requires a non-empty 'records' list"}
    names = {v.upper(): k for k, v in C.PROTO_NAMES.items()}
    recs = []
    for i, r in enumerate(records):
        missing = [k for k in ("src", "dst", "dport", "ep") if k not in r]
        if missing:
            return 400, {"error": f"record {i} missing {missing}"}
        proto = r.get("proto", "TCP")
        if isinstance(proto, str):
            proto = int(proto) if proto.isdigit() \
                else names.get(proto.upper())
            if proto is None:
                return 400, {"error": f"record {i}: unknown protocol"}
        try:
            s16, s6 = parse_addr(r["src"])
            d16, d6 = parse_addr(r["dst"])
        except Exception as e:   # noqa: BLE001 — caller-supplied addresses
            return 400, {"error": f"record {i}: bad address ({e})"}
        direction = C.DIR_INGRESS if r.get("direction") == "ingress" \
            else C.DIR_EGRESS
        try:
            recs.append(PacketRecord(
                s16, d16, int(r.get("sport", 0)), int(r["dport"]), proto,
                int(r.get("flags", C.TCP_SYN)), s6 or d6, int(r["ep"]),
                direction))
        except (TypeError, ValueError) as e:
            return 400, {"error": f"record {i}: bad numeric field ({e})"}
    try:
        now = int(body["now"]) if "now" in body else None
        deadline_ms = body.get("deadline_ms")
        if deadline_ms is not None:
            deadline_ms = float(deadline_ms)
    except (TypeError, ValueError) as e:
        return 400, {"error": f"bad now/deadline_ms ({e})"}
    snapshot = engine.active.snapshot
    batch = batch_from_records(recs, snapshot.ep_slot_of)
    try:
        ticket = engine.submit(batch, now=now, deadline_ms=deadline_ms)
        out = ticket.result(
            timeout=engine.config.pipeline_request_timeout_s)
    except Exception as exc:   # noqa: BLE001 — error class mapped below
        mapped = serving_error(exc)
        if mapped is None:
            raise
        return mapped
    verdicts = []
    for i in range(len(recs)):
        verdicts.append({
            "allow": bool(out["allow"][i]),
            "reason": C.DropReason(int(out["reason"][i])).name,
            "ct_state": C.CTStatus(int(out["status"][i])).name,
            "remote_identity": int(out["remote_identity"][i]),
        })
    return 200, {"count": len(verdicts), "verdicts": verdicts}


def trace_doc(engine: "Engine", body: Dict) -> Tuple[int, Dict]:
    from cilium_tpu.model.ipcache import lpm_lookup
    missing = [k for k in ("ep", "remote", "dport") if k not in body]
    if missing:
        return 400, {"error": f"trace requires {missing}"}
    ep = engine.endpoints.get(int(body.get("ep", -1)))
    if ep is None:
        return 404, {"error": f"endpoint {body.get('ep')} not found"}
    direction = C.DIR_EGRESS if body.get("direction", "egress") == "egress" \
        else C.DIR_INGRESS
    proto = body.get("proto", C.PROTO_TCP)
    if isinstance(proto, str):
        names = {v.upper(): k for k, v in C.PROTO_NAMES.items()}
        proto = int(proto) if proto.isdigit() else names.get(proto.upper())
        if proto is None:
            return 400, {"error": "unknown protocol"}
    remote_id = lpm_lookup(engine.ctx.ipcache.snapshot(), body["remote"])
    pol = engine.repo.resolve(ep)
    dirpol = pol.direction(direction)
    if not dirpol.enforced:
        return 200, {"verdict": "ALLOWED", "remote_identity": remote_id,
                     "reason": "direction not enforced (default mode)"}
    res = dirpol.lookup(remote_id, proto, int(body["dport"]))
    verdict = {C.VERDICT_DENY: ("DENIED", "explicit deny rule"),
               C.VERDICT_MISS: ("DENIED", "no rule matched (default deny)"),
               C.VERDICT_REDIRECT:
                   ("ALLOWED", "L7 redirect (http rules apply per request)"),
               C.VERDICT_ALLOW: ("ALLOWED", "allow rule matched")}
    v, reason = verdict[res.decision]
    doc = {"verdict": v, "reason": reason, "remote_identity": remote_id}
    if res.key is not None:
        doc["matched_key"] = {
            "identity": res.key.identity, "proto": res.key.proto,
            "port_lo": res.key.port_lo, "port_hi": res.key.port_hi}
        doc["derived_from"] = list(res.entry.derived_from)
    return 200, doc


# --------------------------------------------------------------------------- #
class _Handler(BaseHTTPRequestHandler):
    engine: "Engine" = None        # injected per-server subclass
    protocol_version = "HTTP/1.1"

    # unix sockets have no client address; BaseHTTPRequestHandler expects one
    def address_string(self):
        return "unix"

    def log_message(self, fmt, *args):   # quiet by default
        pass

    # -- plumbing -----------------------------------------------------------
    def _send_json(self, code: int, doc) -> None:
        body = json.dumps(doc, default=str).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_text(self, code: int, text: str) -> None:
        body = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", "text/plain; version=0.0.4")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _body(self) -> Dict:
        n = int(self.headers.get("Content-Length", 0))
        if n == 0:
            return {}
        return json.loads(self.rfile.read(n))

    def _route(self) -> Tuple[str, Dict[str, str]]:
        path, _, query = self.path.partition("?")
        params = {}
        for part in query.split("&"):
            if "=" in part:
                k, _, v = part.partition("=")
                # the observe CLI percent-encodes filter values (CIDRs
                # carry '/'); decode so filters see the literal value
                k, v = unquote(k), unquote(v)
                if k.startswith("not_") and k in params:
                    # repeatable --not flags: same-key denies accumulate
                    # (the filter parsers comma-split multi-values)
                    params[k] += "," + v
                else:
                    params[k] = v
        return path.rstrip("/"), params

    # -- methods ------------------------------------------------------------
    def do_GET(self):          # noqa: N802 (http.server API)
        eng = self.engine
        path, q = self._route()
        try:
            if path == "/v1/faults":
                # exempt from api.handler faults: the chaos driver must be
                # able to observe/disarm while the fault storm is on
                return self._send_json(200, FAULTS.stats())
            FAULTS.fire("api.handler")
            if path == "/v1/healthz":
                health = eng.health()
                return self._send_json(200, {
                    "status": ("ok" if health["state"] == C.HEALTH_OK
                               else "degraded"),
                    "revision": eng.repo.revision, **health})
            if path == "/v1/status":
                return self._send_json(200, status_doc(eng))
            if path == "/v1/endpoints":
                return self._send_json(200, endpoints_doc(eng))
            if path.startswith("/v1/endpoints/"):
                try:
                    ep_id = int(path.rsplit("/", 1)[1])
                except ValueError:
                    return self._send_json(400, {"error": "bad endpoint id"})
                doc = endpoint_doc(eng, ep_id)
                if doc is None:
                    return self._send_json(404, {"error": "not found"})
                return self._send_json(200, doc)
            if path == "/v1/identities":
                return self._send_json(200, identities_doc(eng))
            if path == "/v1/policy":
                return self._send_json(200, [
                    r.raw for r in eng.repo.all_rules() if r.raw is not None])
            if path == "/v1/services":
                return self._send_json(200, services_doc(eng))
            if path == "/v1/fqdn/cache":
                return self._send_json(200, fqdn_doc(eng))
            if path == "/v1/ct":
                return self._send_json(200, ct_doc(
                    eng, int(q.get("limit", 64)),
                    int(q["now"]) if "now" in q else None))
            if path == "/v1/flows/observe":
                from cilium_tpu.observe.observer import parse_filters
                try:
                    allow, deny = parse_filters(q)
                except ValueError as e:
                    return self._send_json(400, {"error": str(e)})
                res = eng.observer.observe(
                    allow, deny,
                    last=int(q.get("last", 0)),
                    since=int(q["since"]) if "since" in q else None,
                    limit=int(q.get("limit", 4096)))
                if q.get("explain") in ("1", "true"):
                    res["legend"] = eng.explain_provenance(res["flows"])
                return self._send_json(200, res)
            if path == "/v1/flows/metrics":
                return self._send_json(200, {
                    "windows": eng.flowmetrics.series(
                        int(q.get("last", 0))),
                    "totals": eng.flowmetrics.totals(),
                })
            if path == "/v1/resources":
                return self._send_json(200, eng.resources())
            if path == "/v1/debug/bundle":
                return self._send_json(200, eng.debug_bundle(
                    clear=q.get("clear") in ("1", "true")))
            if path == "/v1/trace":
                return self._send_json(200, {
                    "stats": eng.tracer.stats(),
                    "summary": eng.tracer.summary(),
                    "spans": eng.tracer.spans(
                        limit=int(q.get("limit", 100)),
                        name=q.get("name")),
                })
            if path == "/v1/flows":
                filters = {}
                if "verdict" in q:
                    filters["verdict"] = q["verdict"]
                if "endpoint" in q:
                    filters["endpoint_id"] = int(q["endpoint"])
                if "since" in q:      # live-follow cursor (seq-based)
                    return self._send_json(200, eng.flowlog.since(
                        int(q["since"]), **filters))
                return self._send_json(200, eng.flowlog.tail(
                    int(q.get("last", 50)), **filters))
            if path == "/v1/metrics":
                return self._send_text(200, eng.render_metrics())
            if path == "/v1/config":
                import dataclasses
                return self._send_json(200, dataclasses.asdict(eng.config))
            if path == "/v1/health":
                return self._send_json(200, eng.health_probe())
            return self._send_json(404, {"error": "no such route"})
        except Exception as exc:   # route errors must not kill the server
            return self._send_json(500, {"error": repr(exc)})

    def do_POST(self):         # noqa: N802
        eng = self.engine
        path, _q = self._route()
        try:
            if path == "/v1/faults":
                # chaos driver: arm/disarm injection points in the LIVE
                # agent ({"spec": "point=mode:..."} / {"disarm": "*"|point})
                body = self._body()
                if "disarm" in body:
                    FAULTS.disarm(None if body["disarm"] in ("*", None)
                                  else body["disarm"])
                    return self._send_json(200, {"ok": True})
                try:
                    n = FAULTS.load_spec(body.get("spec", ""))
                except ValueError as e:
                    return self._send_json(400, {"error": str(e)})
                return self._send_json(200, {"ok": True, "armed": n})
            FAULTS.fire("api.handler")
            if path == "/v1/policy":
                body = self._body()
                rev = eng.apply_policy(body)
                eng.regenerate()
                return self._send_json(200, {"revision": rev})
            if path == "/v1/policy/trace":
                code, doc = trace_doc(eng, self._body())
                return self._send_json(code, doc)
            if path == "/v1/classify":
                code, doc = classify_doc(eng, self._body())
                return self._send_json(code, doc)
            if path == "/v1/regenerate":
                compiled = eng.regenerate(force=True)
                return self._send_json(200, {"revision": compiled.revision})
            return self._send_json(404, {"error": "no such route"})
        except Exception as exc:
            return self._send_json(500, {"error": repr(exc)})

    def do_PATCH(self):        # noqa: N802
        eng = self.engine
        path, _q = self._route()
        try:
            FAULTS.fire("api.handler")
            if path == "/v1/config":
                body = self._body()
                # validate the WHOLE request before mutating anything — a
                # 400 must mean "nothing changed" (enforcement mode is
                # security-critical state)
                unknown = set(body) - {"enforcement_mode"}
                if unknown:
                    return self._send_json(
                        400, {"error": f"not runtime-mutable: "
                                       f"{sorted(unknown)}"})
                mode = body.get("enforcement_mode")
                if mode is not None:
                    if mode not in C.ENFORCEMENT_MODES:
                        return self._send_json(
                            400, {"error": f"bad enforcement mode {mode!r}"})
                    # the runtime-mutable subset (upstream: `cilium config
                    # PolicyEnforcement=...`): change + recompile
                    eng.ctx.enforcement_mode = mode
                    eng.regenerate(force=True)
                return self._send_json(200, {"ok": True})
            return self._send_json(404, {"error": "no such route"})
        except Exception as exc:
            return self._send_json(500, {"error": repr(exc)})


# --------------------------------------------------------------------------- #
# client (used by the CLI's --api/live mode; stdlib http.client over AF_UNIX)
# --------------------------------------------------------------------------- #
class UnixAPIClient:
    def __init__(self, socket_path: str, timeout: float = 10.0):
        self.socket_path = socket_path
        self.timeout = timeout

    def request(self, method: str, path: str, body=None):
        import http.client
        import socket

        class _Conn(http.client.HTTPConnection):
            def __init__(conn, sock_path, timeout):
                super().__init__("localhost", timeout=timeout)
                conn._sock_path = sock_path

            def connect(conn):
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.settimeout(conn.timeout)
                s.connect(conn._sock_path)
                conn.sock = s

        conn = _Conn(self.socket_path, self.timeout)
        payload = json.dumps(body).encode() if body is not None else None
        headers = {"Content-Type": "application/json"} if payload else {}
        conn.request(method, path, body=payload, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        ctype = resp.headers.get("Content-Type", "")
        if "json" in ctype:
            return resp.status, json.loads(data)
        return resp.status, data.decode()

    def get(self, path: str):
        return self.request("GET", path)

    def post(self, path: str, body=None):
        return self.request("POST", path, body)

    def patch(self, path: str, body=None):
        return self.request("PATCH", path, body)
