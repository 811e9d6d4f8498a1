"""CLI subcommands (analog of upstream ``cilium-dbg``: endpoint/policy/
service/ct inspection + ``policy trace``, the parity debugging tool).

All inspection commands operate on a checkpoint state dir
(``--state-dir``, the /var/run/cilium analog) through
``checkpoint.load_host`` — pure host code, NO jax import, no device claim.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from cilium_tpu.utils import constants as C


def register(sub: "argparse._SubParsersAction") -> None:
    p = sub.add_parser("version", help="print framework version")
    p.set_defaults(func=_cmd_version)

    p = sub.add_parser("status", help="agent state summary from a state dir")
    _add_state_dir(p)
    p.set_defaults(func=_cmd_status)

    p = sub.add_parser("endpoint", help="endpoint inspection")
    esub = p.add_subparsers(dest="subcmd", required=True)
    pl = esub.add_parser("list", help="list endpoints")
    _add_state_dir(pl)
    pl.set_defaults(func=_cmd_endpoint_list)
    pg = esub.add_parser("get", help="one endpoint incl. policy summary")
    _add_state_dir(pg)
    pg.add_argument("ep_id", type=int)
    pg.set_defaults(func=_cmd_endpoint_get)

    p = sub.add_parser("identity", help="identity inspection")
    isub = p.add_subparsers(dest="subcmd", required=True)
    il = isub.add_parser("list", help="list security identities")
    _add_state_dir(il)
    il.set_defaults(func=_cmd_identity_list)

    p = sub.add_parser("policy", help="policy inspection + trace")
    psub = p.add_subparsers(dest="subcmd", required=True)
    pg = psub.add_parser("get", help="dump the rule documents")
    _add_state_dir(pg)
    pg.set_defaults(func=_cmd_policy_get)
    pt = psub.add_parser(
        "trace", help="trace one (endpoint, flow) through the policy ladder "
        "(upstream: cilium policy trace)")
    _add_state_dir(pt)
    pt.add_argument("--ep", type=int, required=True, help="local endpoint id")
    pt.add_argument("--direction", choices=["egress", "ingress"],
                    default="egress")
    pt.add_argument("--remote", required=True,
                    help="remote IP (resolved via ipcache LPM)")
    pt.add_argument("--dport", type=int, required=True)
    pt.add_argument("--proto", default="TCP",
                    help="TCP|UDP|SCTP|ICMP|ICMPv6 or a number")
    pt.set_defaults(func=_cmd_policy_trace)

    p = sub.add_parser("service", help="service/LB inspection")
    ssub = p.add_subparsers(dest="subcmd", required=True)
    sl = ssub.add_parser("list", help="list services, frontends, backends")
    _add_state_dir(sl)
    sl.set_defaults(func=_cmd_service_list)

    p = sub.add_parser("fqdn", help="FQDN/DNS-cache inspection")
    fsub = p.add_subparsers(dest="subcmd", required=True)
    fc = fsub.add_parser("cache", help="list learned DNS names and IPs")
    _add_state_dir(fc)
    fc.set_defaults(func=_cmd_fqdn_cache)

    p = sub.add_parser("ct", help="conntrack inspection")
    csub = p.add_subparsers(dest="subcmd", required=True)
    cl = csub.add_parser("list", help="list live CT entries from ct.npz")
    _add_state_dir(cl)
    cl.add_argument("--now", type=int, default=None,
                    help="wall-clock for liveness (default: max created)")
    cl.add_argument("--limit", type=int, default=64)
    cl.set_defaults(func=_cmd_ct_list)

    p = sub.add_parser(
        "monitor", help="flow log viewer (cilium monitor / hubble observe)")
    p.add_argument("--flowlog-path",
                   help="JSONL sink written by the engine "
                        "(DaemonConfig.flowlog_path)")
    p.add_argument("--api", metavar="SOCKET",
                   help="live mode: read the in-memory flow ring of a "
                        "running engine over its REST socket")
    p.add_argument("--last", type=int, default=50)
    p.add_argument("--verdict", choices=["FORWARDED", "DROPPED"])
    p.add_argument("--endpoint", type=int)
    p.add_argument("--ip", help="match src or dst IP")
    p.add_argument("--port", type=int, help="match src or dst port")
    p.add_argument("--follow", "-f", action="store_true",
                   help="keep reading appended records (Ctrl-C to stop)")
    p.add_argument("-o", "--output", choices=["text", "json"],
                   default="text")
    p.set_defaults(func=_cmd_monitor)

    p = sub.add_parser(
        "observe", help="vectorized filtered flow observe with match "
                        "provenance (hubble observe analog; "
                        "/v1/flows/observe)")
    p.add_argument("--api", metavar="SOCKET", required=True,
                   help="the running engine's REST socket (the observer "
                        "reads the in-memory columnar ring; there is no "
                        "offline mode — use `monitor` for the JSONL sink)")
    p.add_argument("--last", type=int, default=50,
                   help="one-shot: newest N matching records")
    p.add_argument("--verdict", choices=["FORWARDED", "DROPPED"])
    p.add_argument("--reason", help="drop reason name(s) or int(s), "
                                    "comma-separated (e.g. POLICY_DENY)")
    p.add_argument("--endpoint", help="local endpoint id(s)")
    p.add_argument("--identity", help="remote security identity id(s)")
    p.add_argument("--proto", help="protocol name(s)/number(s) (TCP,UDP,6)")
    p.add_argument("--port", help="src OR dst port(s)")
    p.add_argument("--sport", help="src port(s)")
    p.add_argument("--dport", help="dst port(s)")
    p.add_argument("--cidr", help="src OR dst address in CIDR(s)")
    p.add_argument("--src-cidr", dest="src_cidr")
    p.add_argument("--dst-cidr", dest="dst_cidr")
    p.add_argument("--rule", help="matched_rule coordinate(s) — show every "
                                  "flow a specific policy cell decided")
    p.add_argument("--direction", choices=["egress", "ingress"])
    p.add_argument("--not", dest="deny", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="denylist filter (repeatable): any observe param, "
                        "e.g. --not verdict=FORWARDED --not dport=53")
    p.add_argument("--follow", "-f", action="store_true",
                   help="seq-cursor streaming; ring wraparound surfaces "
                        "as an explicit gap record, never silent loss")
    p.add_argument("-o", "--output", choices=["compact", "json"],
                   default="compact",
                   help="compact: one line per flow with the 'because "
                        "rule R / prefix P / CT S' provenance rendering")
    p.set_defaults(func=_cmd_observe)

    p = sub.add_parser("metrics", help="print the Prometheus text file the "
                                       "engine exports; `metrics flows` "
                                       "shows the windowed flow-metrics "
                                       "time-series (hubble metrics analog)")
    p.add_argument("what", nargs="?", choices=["flows"],
                   help="'flows': windowed verdict/drop/proto/port/identity "
                        "series from /v1/flows/metrics (needs --api)")
    p.add_argument("--metrics-path",
                   help="DaemonConfig.metrics_path file")
    p.add_argument("--api", metavar="SOCKET",
                   help="live mode: scrape a running engine's REST socket")
    p.add_argument("--last", type=int, default=0,
                   help="flows mode: only the newest N windows")
    p.add_argument("-o", "--output", choices=["text", "json"],
                   default="text")
    p.set_defaults(func=_cmd_metrics)

    p = sub.add_parser(
        "trace", help="sampled serving-path spans from a live agent: "
                      "per-stage p50/p99 summary + recent spans "
                      "(observe/trace.py; enable with "
                      "CILIUM_TPU_TRACE_SAMPLE_RATE)")
    p.add_argument("--api", metavar="SOCKET", required=True,
                   help="the running engine's REST socket (spans live "
                        "in-memory; there is no offline mode)")
    p.add_argument("--limit", type=int, default=20,
                   help="recent spans to fetch")
    p.add_argument("--name", help="filter spans by stage name "
                                  "(e.g. pipeline.dispatch)")
    p.add_argument("--spans", action="store_true",
                   help="print individual spans, not just the summary")
    p.add_argument("-o", "--output", choices=["text", "json"],
                   default="text")
    p.set_defaults(func=_cmd_trace)

    p = sub.add_parser(
        "top", help="live resource-pressure view of a running agent "
                    "(observe/pressure.py ledger): one row per bounded "
                    "structure — occupancy bar, pressure, high-water, "
                    "time-to-exhaustion — plus the device HBM ledger. "
                    "Refreshes until interrupted; --once prints a single "
                    "frame (scriptable)")
    p.add_argument("--api", metavar="SOCKET", required=True,
                   help="the running engine's REST socket")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh period in seconds")
    p.add_argument("--once", action="store_true",
                   help="print one frame and exit")
    p.add_argument("-o", "--output", choices=["text", "json"],
                   default="text")
    p.set_defaults(func=_cmd_top)

    p = sub.add_parser(
        "debug-bundle",
        help="fetch the flight-recorder debug bundle from a live agent "
             "(observe/blackbox.py): the frozen anomaly bundle — parity "
             "mismatch, breaker open, watchdog restart, shed spike — or a "
             "live snapshot when nothing froze; carries the guard/regen "
             "event ring, verdict summaries, span tail, audit mismatch "
             "rows + revision, and live engine state")
    p.add_argument("--api", metavar="SOCKET", required=True,
                   help="the running engine's REST socket")
    p.add_argument("--out", metavar="FILE",
                   help="write the JSON bundle to FILE (default: stdout)")
    p.add_argument("--clear", action="store_true",
                   help="re-arm the recorder after the fetch (the next "
                        "anomaly freezes a fresh bundle)")
    p.add_argument("-o", "--output", choices=["text", "json"],
                   default="json")
    p.set_defaults(func=_cmd_debug_bundle)

    p = sub.add_parser(
        "classify", help="serve one flow through a live agent's ingestion "
                         "pipeline (POST /v1/classify; the serving path "
                         "with guard semantics: 429 on overload shed, 503 "
                         "on breaker-open/hard-failed/timeout)")
    p.add_argument("--api", metavar="SOCKET", required=True)
    p.add_argument("--ep", type=int, required=True, help="local endpoint id")
    p.add_argument("--remote", required=True, help="remote IP")
    p.add_argument("--dport", type=int, required=True)
    p.add_argument("--sport", type=int, default=0)
    p.add_argument("--proto", default="TCP")
    p.add_argument("--direction", choices=["egress", "ingress"],
                   default="egress")
    p.add_argument("--src", help="source IP (default: the endpoint's "
                                 "first IP — required if it has none)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-submission staleness bound (shed past it)")
    p.add_argument("-o", "--output", choices=["text", "json"],
                   default="text")
    p.set_defaults(func=_cmd_classify)

    p = sub.add_parser(
        "verify", help="compile every datapath config combo and check the "
                       "memory budget (XLA-as-verifier; the test/verifier "
                       "CI-step analog)")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--max-hbm-bytes", type=int, default=None,
                   help="fail combos whose argument+temp memory exceeds this")
    p.add_argument("--quick", action="store_true",
                   help="skip the LB axis (faster pre-merge check)")
    p.add_argument("--report", metavar="FILE",
                   help="write the sweep + HBM budget summary as JSON "
                        "(offline verification and the live ledger cite "
                        "the same numbers)")
    p.set_defaults(func=_cmd_verify)

    p = sub.add_parser(
        "faults", help="fault injection: list points, arm/disarm on a live "
                       "agent, run the scripted chaos scenario")
    fsub = p.add_subparsers(dest="subcmd", required=True)
    fl = fsub.add_parser("list", help="list injection points (+ live stats "
                                      "with --api)")
    fl.add_argument("--api", metavar="SOCKET",
                    help="read fire/trip stats from a running agent")
    fl.add_argument("-o", "--output", choices=["text", "json"],
                    default="text")
    fl.set_defaults(func=_cmd_faults_list)
    fa = fsub.add_parser("arm", help="arm injection points on a live agent")
    fa.add_argument("--api", metavar="SOCKET", required=True)
    fa.add_argument("spec", help="CILIUM_TPU_FAULTS grammar, e.g. "
                                 "'regen.compile=fail:10'")
    fa.set_defaults(func=_cmd_faults_arm)
    fd = fsub.add_parser("disarm", help="disarm injection points on a live "
                                        "agent")
    fd.add_argument("--api", metavar="SOCKET", required=True)
    fd.add_argument("point", nargs="?", default="*",
                    help="point to disarm (default: all)")
    fd.set_defaults(func=_cmd_faults_disarm)
    fc = fsub.add_parser(
        "chaos", help="run the scripted chaos scenario and print the "
                      "verdict-continuity report (exit 1 on any classify "
                      "error or missed recovery). In-process mode runs "
                      "every phase (regen storm/recovery, peer flap, "
                      "pipeline dispatch storm, stall-storm watchdog "
                      "restart, circuit breaker open/probe/close, "
                      "checkpoint corruption); --api mode runs the regen "
                      "storm + recovery against the live agent only")
    fc.add_argument("--api", metavar="SOCKET",
                    help="target a running agent over its REST socket: "
                         "regen storm/recovery phases only (default: a "
                         "self-contained in-process engine, all phases)")
    fc.add_argument("--failures", type=int, default=10,
                    help="length of the regen.compile failure storm")
    fc.add_argument("--seed", type=int, default=7,
                    help="RNG seed for probabilistic fault phases")
    fc.add_argument("--datapath", choices=["jit", "fake"], default="jit",
                    help="in-process mode: device path (jit) or the "
                         "oracle-backed fake")
    fc.add_argument("-o", "--output", choices=["text", "json"],
                    default="text")
    fc.set_defaults(func=_cmd_faults_chaos)

    p = sub.add_parser(
        "map", help="compiled policy-map inspection (cilium bpf policy get)")
    msub = p.add_subparsers(dest="subcmd", required=True)
    mg = msub.add_parser("get", help="dump one endpoint's MapState entries")
    _add_state_dir(mg)
    mg.add_argument("--ep", type=int, required=True)
    mg.add_argument("--direction", choices=["egress", "ingress"],
                    default=None, help="default: both")
    mg.set_defaults(func=_cmd_map_get)

    p = sub.add_parser(
        "mesh", help="clustermesh inspection (cilium clustermesh status): "
                     "per-peer generation/lag, store reachability, "
                     "staleness verdict, conflicting prefix claims, "
                     "replication-lag p99 (runtime/clustermesh.py)")
    hsub = p.add_subparsers(dest="subcmd", required=True)
    hs = hsub.add_parser(
        "status", help="the mesh health/lag surface of a live agent "
                       "(the 'mesh' key of /v1/status)")
    hs.add_argument("--api", metavar="SOCKET", required=True,
                    help="the running engine's REST socket")
    hs.add_argument("-o", "--output", choices=["text", "json"],
                    default="text")
    hs.set_defaults(func=_cmd_mesh_status)


def _add_state_dir(p):
    p.add_argument("--state-dir",
                   help="checkpoint dir written by the engine "
                        "(the /var/run/cilium analog)")
    p.add_argument("--api", metavar="SOCKET",
                   help="live mode: query a running engine's REST API on "
                        "this unix socket instead of reading state files "
                        "(DaemonConfig.api_socket)")
    p.add_argument("-o", "--output", choices=["text", "json"], default="text")


def _load(args):
    if not getattr(args, "state_dir", None):
        raise SystemExit("one of --state-dir or --api is required")
    from cilium_tpu.runtime.checkpoint import load_host
    return load_host(args.state_dir)


def _live(args, method: str, path: str, body=None):
    """Fetch one route from a running engine (--api SOCKET live mode)."""
    from cilium_tpu.runtime.api import UnixAPIClient
    status, doc = UnixAPIClient(args.api).request(method, path, body)
    if status != 200:
        print(f"API error {status}: {doc}", file=sys.stderr)
        raise SystemExit(1)
    return doc


def _live_emit(args, method: str, path: str, body=None, text_fn=None) -> int:
    doc = _live(args, method, path, body)
    if args.output == "json" or text_fn is None:
        print(json.dumps(doc, indent=2, default=str))
    else:
        text_fn(doc)
    return 0


def _emit(args, doc, text_fn) -> int:
    if args.output == "json":
        print(json.dumps(doc, indent=2, default=str))
    else:
        text_fn(doc)
    return 0


def _proto_num(text: str) -> int:
    if text.isdigit():
        return int(text)
    for num, name in C.PROTO_NAMES.items():
        if name.upper() == text.upper():
            return num
    raise SystemExit(f"unknown protocol {text!r}")


# --------------------------------------------------------------------------- #
def _cmd_version(args) -> int:
    import cilium_tpu
    print(json.dumps({"version": cilium_tpu.__version__}))
    return 0


def _cmd_status(args) -> int:
    def text(d):
        print(f"Policy revision:  {d['revision']}")
        print(f"Endpoints:        {d['endpoints']}")
        print(f"Identities:       {d['identities']}")
        print(f"Rules:            {d['rules']}")
        print(f"IPCache entries:  {d['ipcache_entries']}")
        print(f"Services:         {d['services']}")
        if d["conntrack"]:
            print(f"Conntrack:        {d['conntrack']['live']}/"
                  f"{d['conntrack']['capacity']} live")
        print(f"Enforcement:      {d['enforcement_mode']}")
        dev = d.get("device")
        if dev:
            print(f"Device:           {dev['platform']} ({dev['device_kind']}"
                  f" x{dev['count']}, serving on {dev['serving']};"
                  f" configured {dev['configured']})")
        pl = d.get("pipeline")
        if pl:
            fl = pl.get("flush_reasons", {})
            br = pl.get("breaker") or {}
            print("Pipeline:")
            print(f"  state:          {pl.get('state', 'ok')}"
                  f" (breaker {br.get('state', 'closed')},"
                  f" restarts {pl.get('restarts', 0)}"
                  f"/{pl.get('max_restarts', '-')})")
            print(f"  queue depth:    {pl.get('queue_depth')}"
                  f" (inflight {pl.get('inflight')},"
                  f" staged rows {pl.get('staged_rows')})")
            print(f"  dispatched:     {pl.get('dispatched_batches')} batches"
                  f" ({pl.get('submitted')} submissions, fill"
                  f" {pl.get('fill_ratio_avg')})")
            print(f"  flush reasons:  "
                  + " ".join(f"{k}={v}" for k, v in sorted(fl.items())))
            print(f"  queue wait:     p50={pl.get('queue_wait_p50_ms')}ms"
                  f" p99={pl.get('queue_wait_p99_ms')}ms")
            print(f"  drops/faults:   {pl.get('admission_drops')} admission,"
                  f" {pl.get('dispatch_faults')} dispatch faults,"
                  f" {pl.get('dispatch_errors')} errors")
            shed = pl.get("shed_reasons") or {}
            if pl.get("shed_total") or pl.get("unavailable_total"):
                print(f"  shed:           {pl.get('shed_total', 0)} deadline ("
                      + " ".join(f"{k}={v}" for k, v in sorted(shed.items()))
                      + f"), {pl.get('unavailable_total', 0)} unavailable")
        at = d.get("autotune")
        if at:
            print(f"Autotune:         flush_ms={at.get('flush_ms')}"
                  f" min_bucket={at.get('min_bucket')}"
                  f" adjustments={at.get('adjustments_total')}")
        tr = d.get("trace")
        if tr and tr.get("enabled"):
            print(f"Tracing:          rate={tr.get('sample_rate')}"
                  f" sampled={tr.get('sampled_total')}"
                  f" ring={tr.get('spans_in_ring')}/{tr.get('capacity')}")

    if args.api:
        return _live_emit(args, "GET", "/v1/status", text_fn=text)
    st = _load(args)
    ct_doc = None
    if st.ct is not None:
        expiry = st.ct["expiry"]
        now = int(st.ct["created"].max()) if expiry.size else 0
        ct_doc = {"capacity": int(expiry.shape[0]),
                  "live": int((expiry > now).sum())}
    doc = {
        "revision": st.revision,
        "endpoints": len(st.endpoints),
        "identities": len(list(st.ctx.allocator.all())),
        "rules": len(st.repo),
        "ipcache_entries": len(st.ctx.ipcache.snapshot()),
        "services": len(st.ctx.services.all()),
        "conntrack": ct_doc,
        "enforcement_mode": st.ctx.enforcement_mode,
    }
    return _emit(args, doc, text)


def _cmd_endpoint_list(args) -> int:
    def text(d):
        for e in d:
            print(f"{e['ep_id']:<6} id={e['identity']:<8} "
                  f"ips={','.join(e['ips']) or '-':<24} "
                  f"labels={','.join(e['labels'])}")

    if args.api:
        return _live_emit(args, "GET", "/v1/endpoints", text_fn=text)
    st = _load(args)
    doc = [{"ep_id": ep.ep_id, "identity": ep.identity_id,
            "ips": list(ep.ips), "labels": list(ep.labels.to_strings()),
            "enforcement": ep.enforcement}
           for ep in sorted(st.endpoints.values(), key=lambda e: e.ep_id)]
    return _emit(args, doc, text)


def _cmd_endpoint_get(args) -> int:
    if args.api:
        return _live_emit(args, "GET", f"/v1/endpoints/{args.ep_id}")
    st = _load(args)
    ep = st.endpoints.get(args.ep_id)
    if ep is None:
        print(f"endpoint {args.ep_id} not found", file=sys.stderr)
        return 1
    pol = st.repo.resolve(ep)
    doc = {
        "ep_id": ep.ep_id, "identity": ep.identity_id,
        "ips": list(ep.ips), "labels": list(ep.labels.to_strings()),
        "enforcement": ep.enforcement,
        "policy_revision": pol.revision,
        "egress": {"enforced": pol.egress.enforced,
                   "entries": len(pol.egress.mapstate.items())},
        "ingress": {"enforced": pol.ingress.enforced,
                    "entries": len(pol.ingress.mapstate.items())},
    }
    return _emit(args, doc, lambda d: print(json.dumps(d, indent=2)))


def _cmd_identity_list(args) -> int:
    def text(d):
        for e in d:
            kind = ("reserved" if e["reserved"]
                    else "cidr" if e["local"] else "cluster")
            print(f"{e['id']:<10} {kind:<9} {','.join(e['labels'])}")

    if args.api:
        return _live_emit(args, "GET", "/v1/identities", text_fn=text)
    st = _load(args)
    doc = []
    for ident in st.ctx.allocator.all():
        doc.append({"id": ident.id,
                    "labels": list(ident.labels.to_strings()),
                    "reserved": ident.id < C.CLUSTER_IDENTITY_BASE,
                    "local": bool(ident.id & C.LOCAL_IDENTITY_SCOPE)})
    return _emit(args, doc, text)


def _cmd_policy_get(args) -> int:
    if args.api:
        return _live_emit(args, "GET", "/v1/policy")
    st = _load(args)
    doc = [r.raw for r in st.repo.all_rules() if r.raw is not None]
    return _emit(args, doc, lambda d: print(json.dumps(d, indent=2)))


def _key_str(key) -> str:
    ident = "ANY" if key.identity == C.IDENTITY_ANY else str(key.identity)
    proto = C.PROTO_NAMES.get(key.proto, str(key.proto))
    if key.is_port_wild:
        ports = "*"
    elif key.port_lo == key.port_hi:
        ports = str(key.port_lo)
    else:
        ports = f"{key.port_lo}-{key.port_hi}"
    return f"id={ident} proto={proto} port={ports}"


def _cmd_policy_trace(args) -> int:
    if args.api:
        return _live_emit(args, "POST", "/v1/policy/trace", body={
            "ep": args.ep, "direction": args.direction,
            "remote": args.remote, "dport": args.dport,
            "proto": args.proto})
    st = _load(args)
    ep = st.endpoints.get(args.ep)
    if ep is None:
        print(f"endpoint {args.ep} not found", file=sys.stderr)
        return 1
    from cilium_tpu.model.ipcache import lpm_lookup
    direction = C.DIR_EGRESS if args.direction == "egress" else C.DIR_INGRESS
    proto = _proto_num(args.proto)
    remote_id = lpm_lookup(st.ctx.ipcache.snapshot(), args.remote)
    pol = st.repo.resolve(ep)
    dirpol = pol.direction(direction)
    res = dirpol.lookup(remote_id, proto, args.dport) if dirpol.enforced \
        else None
    if not dirpol.enforced:
        verdict, reason = "ALLOWED", "direction not enforced (default mode)"
    elif res.decision == C.VERDICT_DENY:
        verdict, reason = "DENIED", "explicit deny rule"
    elif res.decision == C.VERDICT_MISS:
        verdict, reason = "DENIED", "no rule matched (default deny)"
    elif res.decision == C.VERDICT_REDIRECT:
        verdict = "ALLOWED"
        reason = "L7 redirect (http rules apply per request)"
    else:
        verdict, reason = "ALLOWED", "allow rule matched"
    doc = {
        "endpoint": ep.ep_id,
        "direction": args.direction,
        "remote": args.remote,
        "remote_identity": remote_id,
        "dport": args.dport,
        "proto": C.PROTO_NAMES.get(proto, str(proto)),
        "enforced": dirpol.enforced,
        "verdict": verdict,
        "reason": reason,
        "matched_key": _key_str(res.key)
        if res is not None and res.key is not None else None,
        "derived_from": list(res.entry.derived_from)
        if res is not None and res.entry is not None else [],
        "l7_rules": [repr(r) for r in sorted(res.entry.l7_rules, key=repr)]
        if res is not None and res.entry is not None
        and res.entry.l7_rules else [],
    }

    def text(d):
        print(f"Tracing {d['direction']} from endpoint {d['endpoint']} "
              f"to {d['remote']} (identity {d['remote_identity']}) "
              f"port {d['dport']}/{d['proto']}")
        print(f"  enforced:    {d['enforced']}")
        if d["matched_key"]:
            print(f"  matched key: {d['matched_key']}")
        for src in d["derived_from"]:
            print(f"    derived from: {src}")
        for r in d["l7_rules"]:
            print(f"    l7: {r}")
        print(f"Final verdict: {d['verdict']} ({d['reason']})")
    return _emit(args, doc, text)


def _cmd_service_list(args) -> int:
    if args.api:
        return _live_emit(args, "GET", "/v1/services")
    st = _load(args)
    doc = []
    for svc in st.ctx.services.all():
        doc.append({
            "name": f"{svc.namespace}/{svc.name}",
            "frontends": [f"{f.addr}:{f.port}/"
                          f"{C.PROTO_NAMES.get(f.proto, f.proto)} ({f.kind})"
                          for f in svc.frontends],
            "backends": [f"{b.addr}:{b.port} (w={b.weight})"
                         for b in svc.lb_backends] or list(svc.backends),
        })

    def text(d):
        for s in d:
            print(s["name"])
            for f in s["frontends"]:
                print(f"  frontend {f}")
            for b in s["backends"]:
                print(f"  backend  {b}")
    return _emit(args, doc, text)


def _cmd_fqdn_cache(args) -> int:
    if args.api:
        return _live_emit(args, "GET", "/v1/fqdn/cache")
    st = _load(args)
    doc = [{"name": name, "ips": {ip: exp for ip, exp in sorted(e.items())}}
           for name, e in st.ctx.fqdn_cache.names()]

    def text(d):
        for e in d:
            print(e["name"])
            for ip, exp in e["ips"].items():
                print(f"  {ip}  expires={exp}")
    return _emit(args, doc, text)


def _cmd_ct_list(args) -> int:
    if args.api:
        path = f"/v1/ct?limit={args.limit}"
        if args.now is not None:
            path += f"&now={args.now}"
        return _live_emit(args, "GET", path)
    import numpy as np
    from cilium_tpu.utils.ip import addr_to_str, words_to_addr
    st = _load(args)
    if st.ct is None:
        print("no ct.npz in state dir", file=sys.stderr)
        return 1
    keys = st.ct["keys"]
    expiry = st.ct["expiry"]
    now = args.now if args.now is not None else (
        int(st.ct["created"].max()) if expiry.size else 0)
    live = np.nonzero(expiry > now)[0]
    entries = []
    for slot in live[: args.limit]:
        w = keys[slot]
        entries.append({
            "src": addr_to_str(words_to_addr(w[0:4])),
            "dst": addr_to_str(words_to_addr(w[4:8])),
            "sport": int(w[8]) >> 16,
            "dport": int(w[8]) & 0xFFFF,
            "proto": C.PROTO_NAMES.get(int(w[9]) >> 8, str(int(w[9]) >> 8)),
            "dir": C.DIR_NAMES[int(w[9]) & 0xFF],
            "expires_in": int(expiry[slot]) - now,
            "pkts_fwd": int(st.ct["pkts_fwd"][slot]),
            "pkts_rev": int(st.ct["pkts_rev"][slot]),
            "rev_nat": int(st.ct["rev_nat"][slot])
            if "rev_nat" in st.ct else 0,
        })
    doc = {"live": int(live.size), "now": now, "entries": entries}

    def text(d):
        print(f"{d['live']} live entries (now={d['now']}):")
        for e in d["entries"]:
            rn = f" rnat={e['rev_nat']}" if e["rev_nat"] else ""
            print(f"  {e['proto']:<5} {e['src']}:{e['sport']} -> "
                  f"{e['dst']}:{e['dport']} [{e['dir']}] "
                  f"ttl={e['expires_in']}s fwd={e['pkts_fwd']} "
                  f"rev={e['pkts_rev']}{rn}")
    return _emit(args, doc, text)


def _flow_matches(r: dict, args) -> bool:
    if r.get("gap"):
        return True        # loss is always shown, filters never hide it
    if args.verdict and r.get("verdict") != args.verdict:
        return False
    if args.endpoint is not None and r.get("endpoint_id") != args.endpoint:
        return False
    if args.ip and args.ip not in (r.get("src_ip"), r.get("dst_ip")):
        return False
    if args.port is not None and args.port not in (r.get("src_port"),
                                                   r.get("dst_port")):
        return False
    return True


def _flow_line(r: dict) -> str:
    if r.get("gap"):
        return (f"** gap: {r['dropped']} records lost to ring wraparound "
                f"(resume at seq {r['resume_seq']}) **")
    mark = "->" if r.get("verdict") == "FORWARDED" else "xx"
    why = ("" if r.get("verdict") == "FORWARDED"
           else f" ({r.get('drop_reason_desc')})")
    return (f"[{r.get('time')}] ep{r.get('endpoint_id')} "
            f"{r.get('direction'):<7} {r.get('proto'):<5} "
            f"{r.get('src_ip')}:{r.get('src_port')} {mark} "
            f"{r.get('dst_ip')}:{r.get('dst_port')} "
            f"{r.get('ct_state'):<11} {r.get('verdict')}{why}")


def _cmd_monitor(args) -> int:
    import time as _time

    def emit(records):
        if args.output == "json":
            for r in records:
                print(json.dumps(r), flush=args.follow)
        else:
            for r in records:
                print(_flow_line(r), flush=args.follow)

    if args.api:
        from cilium_tpu.runtime.api import UnixAPIClient
        client = UnixAPIClient(args.api)
        qualifiers = ""
        if args.verdict:
            qualifiers += f"&verdict={args.verdict}"
        if args.endpoint is not None:
            qualifiers += f"&endpoint={args.endpoint}"
        status, records = client.get(f"/v1/flows?last={args.last}"
                                     + qualifiers)
        if status != 200:
            print(f"API error {status}: {records}", file=sys.stderr)
            return 1
        emit([r for r in records if _flow_matches(r, args)])
        if not args.follow:
            return 0
        # live follow: poll the seq cursor (hubble observe --follow analog)
        cursor = max((r.get("seq", 0) for r in records), default=0)
        try:
            while True:
                _time.sleep(0.3)
                status, fresh = client.get(
                    f"/v1/flows?since={cursor}" + qualifiers)
                if status != 200:
                    print(f"API error {status}: {fresh}", file=sys.stderr)
                    return 1
                if fresh:
                    # gap markers carry no seq; a filtered-empty page must
                    # still advance past the gap or the cursor would reset
                    # to 0 (a fresh attach) and disable future gap checks
                    new_cur = max((r["seq"] for r in fresh if "seq" in r),
                                  default=0)
                    for r in fresh:
                        if r.get("gap"):
                            new_cur = max(new_cur, r["resume_seq"] - 1)
                    cursor = max(cursor, new_cur)
                    emit([r for r in fresh if _flow_matches(r, args)])
        except KeyboardInterrupt:
            return 0
    if not args.flowlog_path:
        print("one of --flowlog-path or --api is required", file=sys.stderr)
        return 1
    if not os.path.exists(args.flowlog_path):
        print(f"no flow log at {args.flowlog_path}", file=sys.stderr)
        return 1

    with open(args.flowlog_path) as f:
        records = []
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if _flow_matches(r, args):
                records.append(r)
        emit(records[-args.last:])
        if not args.follow:
            return 0
        try:
            while True:
                line = f.readline()
                if not line:
                    _time.sleep(0.2)
                    continue
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if _flow_matches(r, args):
                    emit([r])
        except KeyboardInterrupt:
            return 0


#: observe CLI flags that map 1:1 onto /v1/flows/observe query params
_OBSERVE_PARAMS = ("verdict", "reason", "endpoint", "identity", "proto",
                   "port", "sport", "dport", "cidr", "src_cidr", "dst_cidr",
                   "rule", "direction")


def _observe_query(args) -> str:
    from urllib.parse import quote
    parts = []
    for name in _OBSERVE_PARAMS:
        val = getattr(args, name, None)
        if val is not None:
            parts.append(f"{name}={quote(str(val), safe='')}")
    for kv in args.deny:
        if "=" not in kv:
            raise ValueError(f"--not expects KEY=VALUE, got {kv!r}")
        k, v = kv.split("=", 1)
        parts.append(f"not_{k}={quote(v, safe='')}")
    return "&".join(parts)


def _observe_line(r: dict, legend: dict) -> str:
    """The one-line 'verdict because rule R / prefix P / CT S' rendering:
    the flow plus the evidence behind its verdict, resolved through the
    legend the API attaches (explain=1)."""
    if r.get("gap"):
        return _flow_line(r)
    mr = int(r.get("matched_rule", -1))
    lp = int(r.get("lpm_prefix", -1))
    rinfo = legend.get("rules", {}).get(str(mr), {})
    pinfo = legend.get("prefixes", {}).get(str(lp), {})
    rule_s = (rinfo.get("label") or f"#{mr}") if mr >= 0 else "none"
    pfx_s = (pinfo.get("prefix") or f"#{lp}") if lp >= 0 else "miss(world)"
    return (f"{_flow_line(r)} because rule {rule_s} / prefix {pfx_s} "
            f"/ CT {r.get('ct_state_pre')}")


def _cmd_observe(args) -> int:
    import time as _time
    from cilium_tpu.runtime.api import UnixAPIClient
    client = UnixAPIClient(args.api)
    try:
        qualifiers = _observe_query(args)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 1
    base = "/v1/flows/observe?explain=1"
    if qualifiers:
        base += "&" + qualifiers

    def emit(doc):
        legend = doc.get("legend", {})
        records = ([doc["gap"]] if doc.get("gap") else []) + doc["flows"]
        for r in records:
            if args.output == "json":
                print(json.dumps(r), flush=args.follow)
            else:
                print(_observe_line(r, legend), flush=args.follow)

    status, doc = client.get(base + f"&last={args.last}")
    if status != 200:
        print(f"API error {status}: {doc}", file=sys.stderr)
        return 1
    emit(doc)
    if not args.follow:
        return 0
    # follow mode: seq-cursor polling; the server surfaces any wraparound
    # past the cursor as a structured gap record — loss is never silent
    cursor = doc["cursor"]
    try:
        while True:
            _time.sleep(0.3)
            status, doc = client.get(base + f"&since={cursor}")
            if status != 200:
                print(f"API error {status}: {doc}", file=sys.stderr)
                return 1
            cursor = doc["cursor"]
            if doc["flows"] or doc.get("gap"):
                emit(doc)
    except KeyboardInterrupt:
        return 0


def _flowmetrics_text(doc) -> None:
    for w in doc.get("windows", []):
        total = w["forwarded"] + w["dropped"]
        drops = " ".join(f"{k}={v}" for k, v in
                         sorted(w["drop_reasons"].items()))
        ports = ",".join(f"{p['port']}:{p['count']}"
                         for p in w["top_ports"][:5])
        print(f"[{w['window_start']}+{w['window_s']}s] "
              f"flows={total} fwd={w['forwarded']} drop={w['dropped']}"
              + (f" reasons[{drops}]" if drops else "")
              + (f" ports[{ports}]" if ports else ""))
    t = doc.get("totals", {})
    print(f"totals: fwd={t.get('forwarded')} drop={t.get('dropped')} "
          f"batches={t.get('batches')}")


def _cmd_metrics(args) -> int:
    if args.what == "flows":
        if not args.api:
            print("metrics flows reads the live windowed series; "
                  "--api SOCKET is required", file=sys.stderr)
            return 1
        path = "/v1/flows/metrics"
        if args.last:
            path += f"?last={args.last}"
        return _live_emit(args, "GET", path, text_fn=_flowmetrics_text)
    if args.output == "json":
        # the Prometheus exposition is text by definition; silently
        # handing unparseable text to a -o json caller would be worse
        print("-o json applies to `metrics flows`; the Prometheus "
              "exposition is text-only", file=sys.stderr)
        return 1
    if args.api:
        from cilium_tpu.runtime.api import UnixAPIClient
        status, text = UnixAPIClient(args.api).get("/v1/metrics")
        if status != 200:
            print(f"API error {status}: {text}", file=sys.stderr)
            return 1
        sys.stdout.write(text)
        return 0
    if not args.metrics_path:
        print("one of --metrics-path or --api is required", file=sys.stderr)
        return 1
    if not os.path.exists(args.metrics_path):
        print(f"no metrics file at {args.metrics_path}", file=sys.stderr)
        return 1
    with open(args.metrics_path) as f:
        sys.stdout.write(f.read())
    return 0


def _cmd_trace(args) -> int:
    path = f"/v1/trace?limit={args.limit}"
    if args.name:
        path += f"&name={args.name}"
    doc = _live(args, "GET", path)
    if args.output == "json":
        print(json.dumps(doc, indent=2, default=str))
        return 0
    st = doc.get("stats", {})
    if not st.get("enabled"):
        print("tracing is disabled (set trace_sample_rate, e.g. "
              "CILIUM_TPU_TRACE_SAMPLE_RATE=0.015625 for 1/64)")
    print(f"sampled={st.get('sampled_total')} "
          f"in_ring={st.get('spans_in_ring')}/{st.get('capacity')} "
          f"rate={st.get('sample_rate')} "
          f"dropped={st.get('spans_dropped_total', 0)} "
          f"wraps={st.get('ring_wraps', 0)}")
    if st.get("spans_dropped_total"):
        print(f"** {st['spans_dropped_total']} spans lost to ring "
              f"wraparound ({st.get('ring_wraps', 0)} full wraps) — the "
              "percentiles below cover only the surviving tail; the "
              "columns under 'since start' cover every span **")
    summary = doc.get("summary", {})
    if summary:
        print(f"{'stage':<28} {'thread':<20} {'count':>7} {'p50 ms':>10} "
              f"{'p99 ms':>10} {'max ms':>10} | since start: "
              f"{'count':>8} {'wall ms':>12}")
        for name, s in summary.items():
            a = s.get("since_start", {})
            print(f"{name:<28} {s.get('thread', ''):<20} {s['count']:>7} "
                  f"{s['p50_ms']:>10.3f} {s['p99_ms']:>10.3f} "
                  f"{s['max_ms']:>10.3f} | "
                  f"{'':<13}{a.get('count', 0):>8} "
                  f"{a.get('total_ms', 0.0):>12.3f}")
    if args.spans:
        for sp in doc.get("spans", []):
            attrs = sp.get("attrs")
            print(f"  trace={sp['trace_id']:<8} {sp['name']:<28} "
                  f"{sp['duration_ms']:.3f}ms"
                  + f" {sp.get('kind', '')} on {sp.get('thread', '?')}"
                  + (f" in {sp['parent']}" if sp.get("parent") else "")
                  + (f" {attrs}" if attrs else ""))
    return 0


def _cmd_mesh_status(args) -> int:
    """Exit 0 on a healthy mesh, 1 when no mesh is attached, 2 when the
    mesh is MESH_STALE (scriptable: a monitoring probe can alert on it)."""
    doc = _live(args, "GET", "/v1/status")
    mesh = doc.get("mesh")
    if mesh is None:
        print("clustermesh is not attached (set cluster_store + "
              "node_name)", file=sys.stderr)
        return 1
    rc = 2 if mesh.get("state") == C.MESH_STALE else 0
    if args.output == "json":
        print(json.dumps(mesh, indent=2, default=str))
        return rc
    print(f"node={mesh['node']} generation={mesh['generation']} "
          f"state={mesh['state']} store_ok={mesh['store_ok']} "
          f"last_good_pass_age={mesh['last_good_pass_age_s']}s "
          f"budget={mesh['staleness_budget_s']}s")
    print(f"remote_entries={mesh['remote_entries']} "
          f"replication_lag_p99={mesh['replication_lag_p99_s']}s")
    peers = mesh.get("peers", {})
    if peers:
        print(f"{'peer':<24} {'generation':>10} {'entries':>8} "
              f"{'lag s':>9}")
        for name, pe in sorted(peers.items()):
            print(f"{name:<24} {pe['generation']:>10} "
                  f"{pe['entries']:>8} {pe['lag_s']:>9.3f}")
    else:
        print("no live peers")
    for prefix, conf in sorted(mesh.get("conflicts", {}).items()):
        print(f"conflict {prefix}: winner={conf['winner']} "
              f"losers={','.join(conf['losers'])}")
    return rc


def _cmd_debug_bundle(args) -> int:
    """Fetch (and optionally persist) the flight-recorder bundle. Exit 0
    always on a successful fetch — a live snapshot is a valid answer; the
    ``frozen`` field says whether an anomaly captured it."""
    path = "/v1/debug/bundle"
    if args.clear:
        path += "?clear=1"
    doc = _live(args, "GET", path)
    payload = json.dumps(doc, indent=2, default=str)
    state = (f"frozen: {doc.get('reason')}" if doc.get("frozen")
             else "live snapshot")
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
        print(f"debug bundle ({state}) written to {args.out}")
        return 0
    if args.output == "text":
        print(f"bundle: {state} "
              f"(freezes_total={doc.get('freezes_total')})")
        for e in doc.get("events", [])[-20:]:
            attrs = {k: v for k, v in e.items()
                     if k not in ("t", "mono", "kind")}
            print(f"  [{e.get('t'):.3f}] {e.get('kind'):<16} {attrs}")
        eng = doc.get("engine", {})
        aud = eng.get("audit") or {}
        print(f"audit: checked={aud.get('checked_rows')} "
              f"mismatched={aud.get('mismatched_rows')} "
              f"skipped={aud.get('skipped_batches')}")
        return 0
    print(payload)
    return 0


def _cmd_classify(args) -> int:
    """The CLI serving path. Exit codes mirror the guard's error classes: 0
    served, 2 overload shed (retry), 3 unavailable (back off), 1 other."""
    from cilium_tpu.runtime.api import UnixAPIClient
    src = args.src
    if src is None:
        status, ep = UnixAPIClient(args.api).get(f"/v1/endpoints/{args.ep}")
        if status != 200:
            print(f"API error {status}: {ep}", file=sys.stderr)
            return 1
        if not ep.get("ips"):
            print(f"endpoint {args.ep} has no IPs; pass --src",
                  file=sys.stderr)
            return 1
        src = ep["ips"][0]
    body = {"records": [{
        "src": src, "dst": args.remote, "sport": args.sport,
        "dport": args.dport, "proto": args.proto, "ep": args.ep,
        "direction": args.direction}]}
    if args.deadline_ms is not None:
        body["deadline_ms"] = args.deadline_ms
    status, doc = UnixAPIClient(args.api).post("/v1/classify", body)
    if args.output == "json":
        print(json.dumps({"status": status, **(doc if isinstance(doc, dict)
                                               else {"body": doc})},
                         indent=2, default=str))
    elif status == 200:
        v = doc["verdicts"][0]
        mark = "ALLOWED" if v["allow"] else "DENIED"
        print(f"{mark} {src}:{args.sport} -> {args.remote}:{args.dport} "
              f"({args.proto} {args.direction}) reason={v['reason']} "
              f"ct={v['ct_state']} remote_id={v['remote_identity']}")
    else:
        kind = doc.get("kind", "") if isinstance(doc, dict) else ""
        print(f"serving error {status} {kind}: "
              f"{doc.get('error', doc) if isinstance(doc, dict) else doc}",
              file=sys.stderr)
    if status == 200:
        return 0
    if status == 429:
        return 2
    if status == 503:
        return 3
    return 1


def _cmd_verify(args) -> int:
    import dataclasses
    from cilium_tpu.compile.verifier import budget_doc, verify_configs
    reports = verify_configs(batch=args.batch,
                             max_hbm_bytes=args.max_hbm_bytes,
                             quick=args.quick)
    bad = 0
    for r in reports:
        mem = (f"arg={r.argument_bytes} temp={r.temp_bytes} "
               f"out={r.output_bytes}" if r.ok else r.error)
        print(f"{'OK  ' if r.ok else 'FAIL'} {r.name:<24} {mem}")
        bad += not r.ok
    budget = budget_doc(reports, max_hbm_bytes=args.max_hbm_bytes)
    print(f"{len(reports) - bad}/{len(reports)} combos verifier-accepted")
    if budget["worst_combo"]:
        print(f"hbm budget: worst={budget['worst_combo']} "
              f"arg+temp={budget['worst_total_bytes']}"
              + (f" (budget {args.max_hbm_bytes})"
                 if args.max_hbm_bytes else ""))
    if getattr(args, "report", None):
        with open(args.report, "w") as f:
            json.dump({"budget": budget,
                       "reports": [dataclasses.asdict(r)
                                   for r in reports]}, f, indent=2)
        print(f"verify report written to {args.report}")
    return 1 if bad else 0


_BAR_W = 24


def _pressure_bar(pressure: float) -> str:
    filled = max(0, min(_BAR_W, int(round(pressure * _BAR_W))))
    return "[" + "#" * filled + "." * (_BAR_W - filled) + "]"


def _fmt_qty(v: float) -> str:
    """Compact quantity: 1.2M rows / 3.4G bytes read the same way."""
    for unit, div in (("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.1f}{unit}"
    return f"{v:.0f}" if float(v).is_integer() else f"{v:.1f}"


def _fmt_eta(eta_s) -> str:
    if eta_s is None:
        return "-"
    if eta_s >= 3600:
        return f"{eta_s / 3600:.1f}h"
    if eta_s >= 60:
        return f"{eta_s / 60:.1f}m"
    return f"{eta_s:.0f}s"


def _top_frame(doc: dict) -> str:
    lines = [f"{'resource':<22} {'pressure':<{_BAR_W + 2}} {'occ':>8} "
             f"{'cap':>8} {'high':>8} {'eta':>7}  fc"]
    rows = doc.get("resources", {})
    order = sorted(rows, key=lambda r: -rows[r]["pressure"])
    for name in order:
        d = rows[name]
        lines.append(
            f"{name:<22} {_pressure_bar(d['pressure'])} "
            f"{_fmt_qty(d['occupancy']):>8} {_fmt_qty(d['capacity']):>8} "
            f"{_fmt_qty(d['high_water']):>8} {_fmt_eta(d['eta_s']):>7}  "
            f"{'!' if d.get('forecast') else ''}")
    lines.append(
        f"max_pressure={doc.get('max_pressure')} "
        f"pressured={','.join(doc.get('pressured', [])) or '-'} "
        f"forecasts={doc.get('forecasts_total', 0)} "
        f"polls={doc.get('polls_total', 0)}")
    hbm = (doc.get("hbm") or {}).get("ledger")
    if hbm:
        groups = " ".join(f"{k}={_fmt_qty(v)}B"
                          for k, v in sorted(hbm["groups"].items()) if v)
        lines.append(f"hbm: device={_fmt_qty(hbm['device_bytes'])}B "
                     f"({groups}) places={hbm['places_total']} "
                     f"patches={hbm['patches_total']}")
    return "\n".join(lines)


def _cmd_top(args) -> int:
    """The live capacity view (`cilium-tpu top`): one row per ledger
    resource, worst pressure first. Exit 0; --once makes it scriptable.
    Ctrl-C anywhere in the refresh loop (including mid-fetch against a
    slow agent) is the normal clean exit."""
    import time as _time
    try:
        while True:
            doc = _live(args, "GET", "/v1/resources")
            if args.output == "json":
                print(json.dumps(doc, indent=2, default=str))
            else:
                if not args.once:
                    sys.stdout.write("\x1b[2J\x1b[H")   # clear + home
                print(_top_frame(doc))
            if args.once:
                return 0
            _time.sleep(max(0.1, args.interval))
    except KeyboardInterrupt:
        return 0


def _cmd_map_get(args) -> int:
    if getattr(args, "api", None):
        print("map get reads compiled MapState detail from a checkpoint; "
              "use --state-dir (or `endpoint get --api` for live policy "
              "sizes)", file=sys.stderr)
        return 1
    st = _load(args)
    ep = st.endpoints.get(args.ep)
    if ep is None:
        print(f"endpoint {args.ep} not found", file=sys.stderr)
        return 1
    pol = st.repo.resolve(ep)
    directions = ([C.DIR_EGRESS, C.DIR_INGRESS] if args.direction is None
                  else [C.DIR_EGRESS if args.direction == "egress"
                        else C.DIR_INGRESS])
    doc = []
    for d in directions:
        dirpol = pol.direction(d)
        for key, entry in dirpol.mapstate.items():
            doc.append({
                "direction": C.DIR_NAMES[d],
                "key": _key_str(key),
                "action": ("DENY" if entry.deny
                           else "REDIRECT" if entry.is_redirect else "ALLOW"),
                "l7_rules": len(entry.l7_rules or ()),
                "derived_from": list(entry.derived_from),
            })

    def text(dl):
        for e in dl:
            l7 = f" l7={e['l7_rules']}" if e["l7_rules"] else ""
            print(f"{e['direction']:<8} {e['key']:<40} {e['action']}{l7}")
    return _emit(args, doc, text)


# --------------------------------------------------------------------------- #
# fault injection / chaos (runtime/faults.py — supervised degradation proof)
# --------------------------------------------------------------------------- #
def _cmd_faults_list(args) -> int:
    if args.api:
        doc = _live(args, "GET", "/v1/faults")
    else:
        # the local singleton: same schema as the live route, and it
        # reflects a CILIUM_TPU_FAULTS set in this process's environment
        from cilium_tpu.runtime.faults import FAULTS
        doc = FAULTS.stats()

    def text(d):
        for point in sorted(d):
            st = d[point]
            armed = f"armed={st.get('mode')}" if st.get("armed") else "idle"
            print(f"{point:<24} {armed:<12} fired={st.get('fired', 0):<6} "
                  f"trips={st.get('trips', 0):<6} {st.get('description', '')}")
    return _emit(args, doc, text)


def _cmd_faults_arm(args) -> int:
    doc = _live(args, "POST", "/v1/faults", {"spec": args.spec})
    print(json.dumps(doc))
    return 0


def _cmd_faults_disarm(args) -> int:
    doc = _live(args, "POST", "/v1/faults", {"disarm": args.point})
    print(json.dumps(doc))
    return 0


class _ChaosReport:
    """Phase-by-phase pass/fail accumulator for the chaos scenario."""

    def __init__(self):
        self.phases = []

    def record(self, phase: str, ok: bool, detail: str) -> bool:
        self.phases.append({"phase": phase, "ok": bool(ok), "detail": detail})
        return bool(ok)

    @property
    def ok(self) -> bool:
        return all(p["ok"] for p in self.phases)


_CHAOS_POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8"],
                "toPorts": [{"ports": [{"port": "443",
                                        "protocol": "TCP"}]}]}],
}]


def _chaos_inprocess(failures: int, seed: int, datapath_kind: str,
                     report: _ChaosReport) -> None:
    """Self-contained chaos scenario: build an engine, then prove verdict
    continuity under a regen failure storm, ipcache convergence under peer
    flaps, and cold-start fallback from a corrupted checkpoint."""
    import shutil
    import tempfile

    from cilium_tpu.kernels.records import batch_from_records
    from cilium_tpu.runtime import checkpoint as ckpt
    from cilium_tpu.runtime.clustermesh import ClusterMesh
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.runtime.faults import FAULTS, FaultInjected
    from cilium_tpu.utils.ip import parse_addr
    from oracle import PacketRecord

    FAULTS.reset()

    def mk_engine():
        # guard knobs sized for the drill: quick breaker cooldown and
        # restart backoff; the stall timeout stays wide here (first
        # dispatches JIT-compile) and is shrunk at runtime for the
        # stall-storm phase, after the shapes are warm
        cfg = DaemonConfig(ct_capacity=4096, auto_regen=False,
                           pipeline_breaker_cooldown_s=0.4,
                           pipeline_max_restarts=5,
                           pipeline_restart_backoff_s=0.05)
        dp = None
        if datapath_kind == "fake":
            from cilium_tpu.runtime.datapath import FakeDatapath
            dp = FakeDatapath(cfg)
        return Engine(cfg, datapath=dp)

    def mk_batch(slot_of):
        s16, _ = parse_addr("192.168.1.10")
        recs = []
        for dst, dport in (("10.1.2.3", 443),    # allowed
                           ("10.1.2.3", 80),     # denied port
                           ("8.8.8.8", 443)):    # denied CIDR
            d16, _ = parse_addr(dst)
            recs.append(PacketRecord(s16, d16, 40000 + dport, dport,
                                     C.PROTO_TCP, C.TCP_SYN, False, 1,
                                     C.DIR_EGRESS))
        return batch_from_records(recs, slot_of)

    eng = mk_engine()
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.apply_policy(_CHAOS_POLICY)
    slot_of = eng.active.snapshot.ep_slot_of
    base = eng.classify(mk_batch(slot_of), now=100)
    baseline = [bool(a) for a in base["allow"]]

    # -- phase 1: regen.compile failure storm -------------------------------
    # every classify re-enters the failing compile (dirty engine) and must
    # still answer from the last-good snapshot, bit-identical to baseline
    FAULTS.arm("regen.compile", mode="fail", times=failures)
    classify_errors = divergences = 0
    for i in range(failures):
        eng._mark_dirty()                        # noqa: SLF001 — chaos driver
        try:
            out = eng.classify(mk_batch(slot_of), now=200 + i)
        except Exception:
            classify_errors += 1
            continue
        if [bool(a) for a in out["allow"]] != baseline:
            divergences += 1
    h = eng.health()
    report.record(
        "regen-storm",
        classify_errors == 0 and divergences == 0
        and h["state"] == C.HEALTH_DEGRADED
        and h["consecutive_regen_failures"] == failures,
        f"{failures} injected compile failures: {classify_errors} classify "
        f"errors, {divergences} verdict divergences, health={h['state']} "
        f"consecutive={h['consecutive_regen_failures']}")

    # -- phase 2: recovery --------------------------------------------------
    FAULTS.disarm("regen.compile")
    eng.regenerate(force=True)
    h = eng.health()
    report.record(
        "regen-recovery",
        h["state"] == C.HEALTH_OK
        and h["consecutive_regen_failures"] == 0,
        f"post-storm regenerate: health={h['state']} "
        f"consecutive={h['consecutive_regen_failures']}")

    # -- phase 3: clustermesh peer flap (+ skewed peer clock) ---------------
    store = tempfile.mkdtemp(prefix="cilium-tpu-chaos-mesh-")
    try:
        mesh = ClusterMesh(eng, store, "local", stale_after_s=300.0)
        peer = os.path.join(store, "peer1.json")

        def publish_peer(gen):
            doc = {"format_version": 1, "node": "peer1", "generation": gen,
                   "published_at": 0.0,          # peer clock wildly behind
                   "entries": {"10.99.0.5/32": {"labels": ["k8s:app=db"]}}}
            with open(peer + ".tmp", "w") as f:
                json.dump(doc, f)
            os.replace(peer + ".tmp", peer)

        publish_peer(1)
        mesh.sync()
        present0 = eng.ctx.ipcache.get("10.99.0.5/32") is not None
        FAULTS.arm("clustermesh.peer_read", mode="prob", prob=0.5, seed=seed)
        rounds, lost = 12, 0
        for gen in range(2, 2 + rounds):
            publish_peer(gen)
            mesh.sync()
            if eng.ctx.ipcache.get("10.99.0.5/32") is None:
                lost += 1
        FAULTS.disarm("clustermesh.peer_read")
        mesh.sync()
        present1 = eng.ctx.ipcache.get("10.99.0.5/32") is not None
        report.record(
            "peer-flap",
            present0 and present1 and lost == 0,
            f"{rounds} sync rounds at 50% peer-read failure (peer clock "
            f"skewed to epoch): entry lost in {lost} rounds, "
            f"converged={present1}")
    finally:
        shutil.rmtree(store, ignore_errors=True)

    # -- phase 3.5: pipeline dispatch storm ---------------------------------
    # pipelined ingestion under a 50% dispatch-fault storm: every submission
    # must still resolve, in order, with verdicts bit-identical to the
    # serial baseline (the scheduler retries trips — delay, never drop)
    FAULTS.arm("pipeline.dispatch", mode="prob", prob=0.5, seed=seed)
    n_sub = 24
    tickets = [eng.submit(mk_batch(slot_of), now=300 + i)
               for i in range(n_sub)]
    drained = eng.drain(timeout=60)
    pl_errors = pl_divergences = 0
    for t in tickets:
        try:
            out = t.result(timeout=5)
        except Exception:
            pl_errors += 1
            continue
        if [bool(a) for a in out["allow"]] != baseline:
            pl_divergences += 1
    FAULTS.disarm("pipeline.dispatch")
    pstats = eng.pipeline_stats() or {}
    report.record(
        "pipeline-storm",
        drained and pl_errors == 0 and pl_divergences == 0
        and pstats.get("dispatch_faults", 0) > 0,
        f"{n_sub} pipelined submissions at 50% dispatch faults: "
        f"{pstats.get('dispatch_faults', 0)} trips retried, {pl_errors} "
        f"errors, {pl_divergences} verdict divergences, drained={drained}")

    # -- phase 3.6: stall-storm → watchdog-supervised restart ---------------
    # a hang-mode fault wedges the worker inside dispatch (the device-stall
    # simulation); the watchdog must reject the wedged window, restart the
    # worker, and keep serving — post-restart verdicts bit-identical to
    # baseline, no ticket blocked forever
    pl = eng.start_pipeline()
    pl.set_stall_timeout_s(0.75)         # shapes are warm; stall fast
    FAULTS.arm("pipeline.dispatch", mode="hang", delay_s=4.0, times=1)
    tickets = [eng.submit(mk_batch(slot_of), now=500 + i) for i in range(8)]
    drained = eng.drain(timeout=30)
    FAULTS.disarm("pipeline.dispatch")   # release the fenced-off worker
    st_rejected = st_divergences = st_unresolved = 0
    for t in tickets:
        if not t.done():
            st_unresolved += 1
            continue
        try:
            out = t.result(timeout=1)
        except Exception:
            st_rejected += 1
            continue
        if [bool(a) for a in out["allow"]] != baseline:
            st_divergences += 1
    # post-restart serving: the fresh worker must answer bit-identical to
    # the serial baseline (give the restart backoff a moment to finish)
    import time as _t
    for _ in range(40):
        if (eng.pipeline_stats() or {}).get("state") == "ok":
            break
        _t.sleep(0.05)
    post_ok = 0
    for i in range(3):
        try:
            out = eng.submit(mk_batch(slot_of), now=550 + i).result(
                timeout=20)
            post_ok += [bool(a) for a in out["allow"]] == baseline
        except Exception:
            pass
    pstats = eng.pipeline_stats() or {}
    pl.set_stall_timeout_s(30.0)
    report.record(
        "stall-storm",
        drained and st_unresolved == 0 and st_rejected >= 1
        and st_divergences == 0 and pstats.get("restarts", 0) >= 1
        and post_ok == 3 and pstats.get("state") == "ok",
        f"hang-wedged dispatch: {pstats.get('restarts', 0)} watchdog "
        f"restart(s), {st_rejected} wedged tickets rejected, "
        f"{st_unresolved} stuck, {st_divergences} divergences, "
        f"{post_ok}/3 post-restart submissions matched baseline, "
        f"state={pstats.get('state')}")

    # -- phase 3.7: circuit breaker open → half-open probe → close ----------
    # fail-always dispatch: the first submission burns at most `threshold`
    # attempts before the breaker opens; subsequent submissions fail fast
    # (no retry burn); disarming + cooldown lets the half-open probe close
    # the breaker and serving resumes bit-identical
    from cilium_tpu.pipeline import PipelineUnavailable
    FAULTS.arm("pipeline.dispatch", mode="fail")
    faults_before = (eng.pipeline_stats() or {}).get("dispatch_faults", 0)
    first = eng.submit(mk_batch(slot_of), now=600)
    first_rejected = False
    try:
        first.result(timeout=20)
    except PipelineUnavailable:
        first_rejected = True
    except Exception:
        pass
    fast_fails = 0
    for i in range(3):                   # breaker open → instant rejection
        try:
            eng.submit(mk_batch(slot_of), now=601 + i)
        except PipelineUnavailable:
            fast_fails += 1
    pstats = eng.pipeline_stats() or {}
    opened = pstats.get("breaker", {}).get("state") == "open"
    burned = pstats.get("dispatch_faults", 0) - faults_before
    h_open = eng.health()
    FAULTS.disarm("pipeline.dispatch")
    _t.sleep(0.5)                        # past the 0.4s cooldown
    probe_ok = False
    try:
        out = eng.submit(mk_batch(slot_of), now=610).result(timeout=20)
        probe_ok = [bool(a) for a in out["allow"]] == baseline
    except Exception:
        pass
    pstats = eng.pipeline_stats() or {}
    report.record(
        "breaker",
        first_rejected and fast_fails == 3 and opened
        and burned <= eng.config.pipeline_breaker_threshold + 1
        and h_open["state"] != C.HEALTH_OK
        and probe_ok and pstats.get("breaker", {}).get("state") == "closed"
        and pstats.get("state") == "ok",
        f"fail-always dispatch: opened after {burned} attempts (cap "
        f"{eng.config.pipeline_breaker_threshold}), {fast_fails}/3 fast "
        f"fails, health={h_open['state']}, probe closed breaker and "
        f"matched baseline={probe_ok}")

    # -- phase 3.8: restart with CT survival (ROADMAP 3b) -------------------
    # an established flow must keep its verdict THROUGH a daemon restart:
    # checkpoint (versioned ct.npz), fresh engine, restore — the reply-side
    # packet classifies ESTABLISHED from the reloaded CT where a cold
    # engine would see NEW; the overlapped CT GC ticks cleanly after
    state = tempfile.mkdtemp(prefix="cilium-tpu-chaos-restart-")
    try:
        s16, _ = parse_addr("192.168.1.10")
        d16, _ = parse_addr("10.1.2.3")
        syn = PacketRecord(s16, d16, 45001, 443, C.PROTO_TCP, C.TCP_SYN,
                           False, 1, C.DIR_EGRESS)
        ack = PacketRecord(s16, d16, 45001, 443, C.PROTO_TCP, 0x10,
                           False, 1, C.DIR_EGRESS)
        b = batch_from_records([syn, ack], slot_of)
        out = eng.classify(b, now=700)
        established = bool(out["allow"][0]) and bool(out["allow"][1])
        ckpt.save(eng, state)
        fresh = mk_engine()
        restored = ckpt.restore(fresh, state)
        ct_kept = gc_ok = False
        if restored:
            b2 = batch_from_records([ack],
                                    fresh.active.snapshot.ep_slot_of)
            out2 = fresh.classify(b2, now=705)
            ct_kept = bool(out2["allow"][0]) and \
                int(out2["status"][0]) == int(C.CTStatus.ESTABLISHED)
            if hasattr(fresh.datapath, "sweep_step"):
                gc_ok = fresh.sweep_step(now=710) is not None \
                    and fresh.sweep_step(now=711) is not None
            else:
                fresh.sweep(now=710)
                gc_ok = True
        report.record(
            "ct-restart",
            established and restored is True and ct_kept and gc_ok,
            f"flow established={established}, restored={restored}, "
            f"reply ESTABLISHED through reloaded CT={ct_kept}, "
            f"post-restart GC tick ok={gc_ok}")
    finally:
        shutil.rmtree(state, ignore_errors=True)

    # -- phase 4: checkpoint torn write + corruption fallback ---------------
    state = tempfile.mkdtemp(prefix="cilium-tpu-chaos-ckpt-")
    try:
        FAULTS.arm("checkpoint.write", mode="fail", times=1)
        torn = False
        try:
            ckpt.save(eng, state)
        except FaultInjected:
            torn = True
        FAULTS.disarm("checkpoint.write")
        no_partial = not os.path.exists(os.path.join(state, "state.json"))
        ckpt.save(eng, state)                    # clean write
        with open(os.path.join(state, "state.json"), "r+") as f:
            f.write("{corrupt")                  # simulate torn write/bit rot
        fresh = mk_engine()
        restored = ckpt.restore(fresh, state)
        cold_ok = False
        if restored is False:                    # cold start must still work
            fresh.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",),
                               ep_id=1)
            fresh.apply_policy(_CHAOS_POLICY)
            out = fresh.classify(
                mk_batch(fresh.active.snapshot.ep_slot_of), now=400)
            cold_ok = [bool(a) for a in out["allow"]] == baseline
        report.record(
            "checkpoint-corruption",
            torn and no_partial and restored is False and cold_ok,
            f"torn save aborted cleanly={torn and no_partial}, corrupt "
            f"restore fell back to cold start={restored is False}, cold "
            f"engine verdicts match baseline={cold_ok}")
    finally:
        shutil.rmtree(state, ignore_errors=True)

    # -- phase 5: qos.enqueue fail-closed (multi-tenant QoS) ----------------
    # tenant classification at admission blows up: every faulted submission
    # must fail CLOSED onto the default tenant's FIFO budget — served,
    # never dropped, verdicts bit-identical — and the worker keeps running
    import numpy as np
    qcfg = DaemonConfig(ct_capacity=4096, auto_regen=False,
                        qos_enabled=True,
                        qos_tenants="gold=4:lane,bulk=1",
                        pipeline_max_restarts=5,
                        pipeline_restart_backoff_s=0.05)
    qdp = None
    if datapath_kind == "fake":
        from cilium_tpu.runtime.datapath import FakeDatapath
        qdp = FakeDatapath(qcfg)
    qeng = Engine(qcfg, datapath=qdp)
    qeng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    qeng.apply_policy(_CHAOS_POLICY)
    qslot = qeng.active.snapshot.ep_slot_of
    gold_tid = {v: k for k, v in qeng.qos.tenants().items()}["gold"]
    n_fault, n_sub = 4, 12
    FAULTS.arm("qos.enqueue", mode="fail", times=n_fault)
    qtickets = []
    for i in range(n_sub):
        qb = mk_batch(qslot)
        qb["_tenant"] = np.full(qb["valid"].shape, gold_tid,
                                dtype=np.int32)
        qtickets.append(qeng.submit(qb, now=800 + i))
    qdrained = qeng.drain(timeout=60)
    FAULTS.disarm("qos.enqueue")
    q_errors = q_div = 0
    for t in qtickets:
        try:
            out = t.result(timeout=5)
        except Exception:
            q_errors += 1
            continue
        if [bool(a) for a in out["allow"]] != baseline:
            q_div += 1
    failsafe = qeng.metrics.counters.get("qos_enqueue_failsafe_total", 0)
    fell = sum(1 for t in qtickets if t.tenant == "default")
    qstats = qeng.pipeline_stats() or {}
    report.record(
        "qos-enqueue-failsafe",
        qdrained and q_errors == 0 and q_div == 0
        and failsafe == n_fault and fell == n_fault
        and qstats.get("state") == "ok",
        f"{n_fault} injected classification faults over {n_sub} "
        f"submissions: {failsafe} fail-closed to the default tenant "
        f"({fell} tickets), {q_errors} errors, {q_div} verdict "
        f"divergences, state={qstats.get('state')}")

    # -- phase 6: dns-poison — fqdn.parse fail-open -------------------------
    # the in-band DNS learning tap's parser blows up mid-storm: every
    # faulted batch loses LEARNING only (counted in parse_errors), never
    # the reply — DNS verdicts stay bit-identical to the unfaulted
    # baseline, the cache stays empty while the fault is armed, and
    # learning resumes the moment the fault exhausts; a crafted
    # garbage-body frame afterwards is counted malformed and learns
    # nothing (the actual poisoning attempt)
    from cilium_tpu.fqdn.dnsparse import HEADER_LEN, encode_response
    from cilium_tpu.fqdn.proxy import DNSProxy

    dns_policy = _CHAOS_POLICY + [{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [{"toCIDR": ["9.9.9.9/32"],
                    "toPorts": [{"ports": [{"port": "53",
                                            "protocol": "UDP"}],
                                 "rules": {"http": [{}]}}]}],
    }]
    deng = mk_engine()
    deng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    deng.apply_policy(dns_policy)
    dslot = deng.active.snapshot.ep_slot_of
    proxy = DNSProxy(deng.ctx.fqdn_cache, metrics=deng.metrics)
    good = encode_response("poison.example.com", ["10.7.7.7"], ttl=300)
    bad = bytearray(encode_response("poison.example.com", ["10.7.7.8"],
                                    ttl=300))
    bad[HEADER_LEN:] = b"\xff" * (len(bad) - HEADER_LEN)  # valid header,
    bad = bytes(bad)                                      # garbage body

    def dns_batch(frame):
        s16, _ = parse_addr("192.168.1.10")
        d16, _ = parse_addr("9.9.9.9")
        rec = PacketRecord(s16, d16, 41053, 53, C.PROTO_UDP, 0,
                           False, 1, C.DIR_EGRESS)
        b = batch_from_records([rec], dslot)
        nrow = b["valid"].shape[0]
        b["_dns_payload"] = np.zeros((nrow, 512), dtype=np.uint8)
        b["_dns_len"] = np.zeros((nrow,), dtype=np.int32)
        b["_dns_payload"][0, :len(frame)] = np.frombuffer(
            frame, dtype=np.uint8)
        b["_dns_len"][0] = len(frame)
        return b

    def tap(frame, now):
        b = dns_batch(frame)
        out = deng.classify(b, now=now)
        proxy.observe_batch(b, out)
        return out

    base = deng.classify(dns_batch(good), now=900)
    dns_baseline = [bool(a) for a in base["allow"]]
    redirect_seen = bool(np.asarray(base["redirect"]).any()) \
        and dns_baseline[0]
    n_fault = 3
    FAULTS.arm("fqdn.parse", mode="fail", times=n_fault)
    dns_div = 0
    for i in range(n_fault):
        out = tap(good, now=901 + i)
        if [bool(a) for a in out["allow"]] != dns_baseline:
            dns_div += 1
    FAULTS.disarm("fqdn.parse")
    errs_fault = proxy.parse_errors_total
    starved = len(deng.ctx.fqdn_cache) == 0       # fault cost learning
    tap(good, now=910)                            # fault gone: learning back
    recovered = len(deng.ctx.fqdn_cache) == 1 and proxy.observed_total == 1
    tap(bad, now=911)                             # the poison frame itself
    poison_rejected = len(deng.ctx.fqdn_cache) == 1 \
        and proxy.parse_errors_total == errs_fault + 1
    report.record(
        "dns-poison",
        redirect_seen and dns_div == 0 and errs_fault == n_fault
        and starved and recovered and poison_rejected,
        f"{n_fault} injected parse faults on the DNS tap: {errs_fault} "
        f"counted, {dns_div} verdict divergences, cache starved during "
        f"fault={starved}, learning resumed after={recovered}, garbage "
        f"frame counted malformed and learned nothing={poison_rejected}")


def _chaos_live(args, report: _ChaosReport) -> None:
    """Drive the chaos scenario against a running agent over its REST
    socket (arm via POST /v1/faults — the route is exempt from the
    ``api.handler`` point so the driver keeps control during the storm)."""
    from cilium_tpu.runtime.api import UnixAPIClient
    failures = args.failures
    client = UnixAPIClient(args.api)

    code, h0 = client.get("/v1/healthz")
    if not report.record("baseline",
                         code == 200 and h0.get("state") == C.HEALTH_OK,
                         f"healthz={code} state={h0.get('state')}"):
        return
    code, doc = client.post("/v1/faults",
                            {"spec": f"regen.compile=fail:{failures}"})
    if not report.record("arm", code == 200, f"arm regen.compile: {doc}"):
        return
    regen_errors = 0
    for _ in range(failures):
        code, _doc = client.post("/v1/regenerate")
        if code != 200:                          # degraded regen still
            regen_errors += 1                    # answers with last-good
    code_p, _probe = client.get("/v1/health")    # real classify continuity
    code, h1 = client.get("/v1/healthz")
    report.record(
        "regen-storm",
        regen_errors == 0 and code_p == 200 and code == 200
        and h1.get("state") in (C.HEALTH_DEGRADED, C.HEALTH_STALE)
        and h1.get("consecutive_regen_failures") == failures,
        f"{failures} forced regens: {regen_errors} API errors, datapath "
        f"probe={code_p}, health={h1.get('state')} "
        f"consecutive={h1.get('consecutive_regen_failures')}")
    client.post("/v1/faults", {"disarm": "*"})
    code, _doc = client.post("/v1/regenerate")
    code2, h2 = client.get("/v1/healthz")
    report.record(
        "regen-recovery",
        code == 200 and code2 == 200 and h2.get("state") == C.HEALTH_OK,
        f"post-storm regenerate={code}, health={h2.get('state')}")


def _cmd_faults_chaos(args) -> int:
    report = _ChaosReport()
    if args.api:
        _chaos_live(args, report)
    else:
        _chaos_inprocess(args.failures, args.seed, args.datapath, report)
    if args.output == "json":
        print(json.dumps({"ok": report.ok, "phases": report.phases},
                         indent=2))
    else:
        for p in report.phases:
            print(f"{'PASS' if p['ok'] else 'FAIL'} {p['phase']:<22} "
                  f"{p['detail']}")
        print("chaos scenario PASSED — verdict continuity held under all "
              "injected faults" if report.ok else "chaos scenario FAILED")
    return 0 if report.ok else 1
