"""World ``httprules``: one ingress endpoint behind sets of HTTP rules, one
set a TCP port — BASELINE config 4's control plane ("L7-lite: HTTP
method/path prefix match via tokenized header tensor") as
``bench.py:build_config4`` read it until PR 31 deleted it (``git show
d48d000^:bench.py``, line 410): rule set *i* on TCP port ``first_port + i``
with three HTTP rules, no ``fromEndpoints`` (any peer), requests of which
most are aligned with their port's set.

Parameters (the configuration file's ``world`` group; the defaults are the
source's):
    n_rulesets      rule sets, one document each (200)
    first_port      set i is on TCP port ``first_port + i`` (80)
    rules           the rule forms of a set, ``{i}`` standing for the set's
                    number: ``[{"method": "GET", "path": "/api/v{i}"},
                    {"method": "POST", "path": "/submit/{i}"},
                    {"path": "/public/{i}"}]``; no method: any method
    peer_net        the peers' net ("11.0.0.0/8"); no document selects a
                    peer, so every source is the world identity
and what the source does not fix, for a configuration to list under
``assumed``:
    live_requests   how ``allowed_flows`` splits over the rule forms' right
                    requests (``GET /api/v{i}/x``, ``POST /submit/{i}/x``,
                    any method on ``/public/{i}/asset.js``), one share a
                    rule form
    denied_split    [refused, unnamed]: how ``denied_flows`` divides between
                    requests the port's set refuses (drop reason 180) and
                    requests to a port no document names (reason 130)
    long_path_share of the requests carry a path of over 64 bytes

A flow is ingress TCP and has one frame, which carries its request line as
``payload`` (``"<METHOD> <path> HTTP/1.1\\r\\nHost: x\\r\\n\\r\\n"``) and
states ``http_method`` / ``http_path`` as the shim's tokenizer will read
them: the method's number, the path cut to 64 bytes. Every flow carries a
request with one of the nine methods the tokenizer knows (a frame on a
set's port with no request line is admitted unmatched by the program; this
world sends none).

**The plain reference**, with numpy and ``bytes`` from the rule parameters
alone: a flow's cell is (the set of its port, the first rule of that set
which admits its request), none where its port has no set or no rule
admits. A rule admits a request iff the rule names no method or the
request's, and the rule's path is a byte-prefix of the request's path as
cut to 64 bytes. ``cover`` counts, for a cell, the rules of its set that
can admit a request its rule admits (1: it admits alone, so the control may
take it out). ``reasons``: a refused frame's drop reason is 180 where its
port has a set, 130 where no document names its port.

**Contrast every world built here holds** (``build`` raises otherwise), so
that a matcher which is nearly right gets frames wrong:
    across     admitted only because a rule's path is a *proper* prefix of
               the request's across a number: ``GET /api/v12/x`` on port 81,
               whose set holds ``GET /api/v1`` (a matcher that wants
               equality or a segment boundary refuses it)
    exact      the request's path is the rule's, byte for byte
    anymethod  any of the nine methods on ``/public/{i}/…``
    long       a path of over 64 bytes that a rule's prefix admits
    short      a path shorter than the rule's (``GET /api/v``: a matcher
               that compares only the bytes both have admits it)
    method     the right path under another method (``POST /api/v{i}/x``,
               ``GET /submit/{i}/x``)
    otherset   the right request of another port's set
    forbidden  ``/forbidden/zone``
    longmiss   a path of over 64 bytes that no rule's prefix admits
The heaviest ranks of ``allowed_flows`` are the admitted cases, and every
refused request of ``denied_flows`` is one of the refused ones.

``unknown_flows``: the world has no ``fromEndpoints``, so no source is
unknown to it; it returns requests to TCP ports no document names (reason
130, no cell), from another range than ``denied_flows``' unnamed ports.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.frames import PROTO_TCP, Flows, v4_words
from benchmarks.reference import REASON_POLICY, REASON_POLICY_L7

EP_ID = 1
EP_V4 = 0xC0A8000A                      # 192.168.0.10
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)  # unused: this world is v4-only
#: the shim's tokenizer numbers the methods it knows in this order
#: (``flowshim.cc: kMethods``); written out, not imported
METHODS = ("GET", "POST", "PUT", "DELETE", "HEAD", "OPTIONS", "PATCH",
           "TRACE", "CONNECT")
PATH_CUT = 64                           # the tokenizer keeps this much
PAYLOAD_WIDTH = 128                     # holds the longest request line
LONG_PATHS = (70, 96)                   # lengths of the paths over 64 bytes
NEIGHBOUR_PORTS = 1024                  # denied_flows' unnamed ports: past
#                                         the last set's
FAR_PORTS = (20000, 30000)              # unknown_flows'
HEAD_SHARE = 8                          # allowed_flows' first n/8 are the
#                                         admitted contrast cases
WORLD_SEED = 0                          # the deployment is one, whatever
#                                         the run's seed
ADMITTED = ("right", "across", "exact", "anymethod", "long")
REFUSED = ("short", "method", "otherset", "forbidden", "longmiss")

DEFAULT_RULES = [{"method": "GET", "path": "/api/v{i}"},
                 {"method": "POST", "path": "/submit/{i}"},
                 {"path": "/public/{i}"}]


def _parse_net(cidr: str) -> Tuple[int, int]:
    addr, plen = cidr.split("/")
    a, b, c, d = (int(x) for x in addr.split("."))
    return (a << 24) | (b << 16) | (c << 8) | d, 1 << (32 - int(plen))


def _stretch(path: str, length: int) -> str:
    """``path`` made ``length`` bytes long by segments after it."""
    filler = "/seg-0123456789abcdef" * 8
    return (path + filler)[:length]


def request_columns(requests) -> Dict[str, np.ndarray]:
    """(method, path) pairs → what a flow set states of each: the request
    line as ``payload`` / ``payload_len``, and ``http_method`` /
    ``http_path`` as the shim's tokenizer will read it."""
    n = len(requests)
    cols = {"payload": np.zeros((n, PAYLOAD_WIDTH), np.uint8),
            "payload_len": np.zeros((n,), np.int32),
            "http_method": np.zeros((n,), np.int32),
            "http_path": np.zeros((n, PATH_CUT), np.uint8)}
    for q, (name, path) in enumerate(requests):
        line = f"{name} {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
        cut = path.encode()[:PATH_CUT]
        cols["payload"][q, :len(line)] = np.frombuffer(line, np.uint8)
        cols["payload_len"][q] = len(line)
        cols["http_method"][q] = METHODS.index(name)
        cols["http_path"][q, :len(cut)] = np.frombuffer(cut, np.uint8)
    return cols


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        self.n_rulesets = int(params.get("n_rulesets", 200))
        self.first_port = int(params.get("first_port", 80))
        self.rules = [(r.get("method", ""), r["path"])
                      for r in params.get("rules", DEFAULT_RULES)]
        self.peer_net, self.peer_span = _parse_net(
            params.get("peer_net", "11.0.0.0/8"))
        self.live_requests = np.asarray(params["live_requests"], np.float64)
        self.denied_split = np.asarray(params["denied_split"], np.float64)
        self.long_path_share = float(params["long_path_share"])
        S, R = self.n_rulesets, len(self.rules)
        if S < 2:
            raise ValueError("one rule set has no other port's set beside it")
        if self.live_requests.shape != (R,) \
                or abs(self.live_requests.sum() - 1.0) > 1e-9:
            raise ValueError("live_requests: one share a rule form, "
                             "summing to 1")
        if self.denied_split.shape != (2,) \
                or abs(self.denied_split.sum() - 1.0) > 1e-9:
            raise ValueError("denied_split: [refused, unnamed], summing "
                             "to 1")
        if self.first_port + S + NEIGHBOUR_PORTS > FAR_PORTS[0]:
            raise ValueError("the sets' ports reach into the unnamed ranges")
        for m, p in self.rules:
            if (m and m not in METHODS) or "{i}" not in p \
                    or len(p.format(i=S)) >= PATH_CUT:
                raise ValueError(f"rule form {(m, p)!r}: a known method or "
                                 f"none, a path under {PATH_CUT} bytes with "
                                 f"{{i}} in it")
        # the rule table, from the parameters: each set's paths as bytes
        self._rule_method = np.array(
            [METHODS.index(m) if m else -1 for m, _p in self.rules])
        self._rule_path = np.array(
            [[p.format(i=i).encode() for _m, p in self.rules]
             for i in range(S)], dtype=f"S{PATH_CUT}")
        self._cover = self._count_cover()
        self._catalogue()

    # -- the deployment, through the entry points a user calls --------------
    def policy_docs(self) -> List[Dict]:
        return [{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"toPorts": [{
                "ports": [{"port": str(self.first_port + i),
                           "protocol": "TCP"}],
                "rules": {"http": [
                    dict({"method": m} if m else {}, path=p.format(i=i))
                    for m, p in self.rules]},
            }]}],
        } for i in range(self.n_rulesets)]

    def load(self, eng) -> int:
        """The endpoint and the rule documents. Returns the revision to
        wait for."""
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",),
                         ep_id=EP_ID)
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)

    # -- the plain reference --------------------------------------------------
    def _count_cover(self) -> np.ndarray:
        """[sets * rules] for each rule, how many rules of its set can admit
        a request it admits (itself among them): two rules can share a
        request iff neither's method shuts out the other's and one's path
        is a prefix of the other's."""
        S, R = self.n_rulesets, len(self.rules)
        cover = np.zeros((S * R,), np.uint8)
        for i in range(S):
            for a in range(R):
                for b in range(R):
                    ma, mb = self._rule_method[a], self._rule_method[b]
                    pa, pb = self._rule_path[i, a], self._rule_path[i, b]
                    cover[i * R + a] += (ma < 0 or mb < 0 or ma == mb) \
                        and (pa.startswith(pb) or pb.startswith(pa))
        return cover

    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): a cell for every
        rule of every set, each of which a document states; how many rules
        of its set admit what it admits."""
        return np.ones(self._cover.shape, bool), self._cover

    def _set_of(self, flows: Flows) -> np.ndarray:
        """The rule set on each flow's port, -1 where no document names
        it."""
        s = flows["dport"].astype(np.int64) - self.first_port
        ok = (~flows["is_v6"].astype(bool)) \
            & (flows["proto"] == PROTO_TCP) & (s >= 0) & (s < self.n_rulesets)
        return np.where(ok, s, -1)

    def cells(self, flows: Flows) -> np.ndarray:
        """Each flow's cell: (its port's set, the first rule of the set that
        admits its request), -1 where there is none."""
        return self._admitting(self._set_of(flows), flows["http_method"],
                               flows["http_path"])

    def _admitting(self, s, method, path) -> np.ndarray:
        R = len(self.rules)
        path = np.ascontiguousarray(path, np.uint8).view(f"S{PATH_CUT}") \
            .reshape(-1)
        at = np.maximum(s, 0)
        cell = np.full(s.shape, -1, np.int64)
        for r in reversed(range(R)):                 # the first rule wins
            hit = (s >= 0) \
                & ((self._rule_method[r] < 0)
                   | (method == self._rule_method[r])) \
                & np.char.startswith(path, self._rule_path[at, r])
            cell = np.where(hit, at * R + r, cell)
        return cell

    def reasons(self, flows: Flows) -> np.ndarray:
        """[n] the drop reason the documents give a frame of each flow if
        the table does not admit it."""
        return np.where(self._set_of(flows) >= 0, REASON_POLICY_L7,
                        REASON_POLICY)

    # -- the requests -----------------------------------------------------------
    def _candidates(self, rng) -> List[Tuple[int, str, int, str, str]]:
        """Every request this world may send, once: (set whose port it goes
        to, the case it is made for, rule form, method, path)."""
        S = self.n_rulesets
        entries = []
        for i in range(S):
            for r, (m, p) in enumerate(self.rules):
                path = p.format(i=i)
                names = [m] if m else list(METHODS)
                tail = "/x" if m else "/asset.js"
                for k, name in enumerate(names):
                    case = "right" if k == 0 else "anymethod"
                    entries.append((i, case, r, name, path + tail))
                name = names[(i + r) % len(names)]
                entries.append((i, "exact", r, name, path))
                entries.append((i, "across", r, name,
                                f"{path}{(i + 1) % 10}/x"))
                for length in LONG_PATHS:
                    entries.append((i, "long", r, name,
                                    _stretch(path, length)))
                # refused, unless the reference says otherwise
                literal = p[:p.index("{i}")]
                entries.append((i, "short", r, name, literal))
                entries.append((i, "short", r, name, path[:-1]))
                if m:
                    others = ("GET", "POST", "PUT", "DELETE")[i % 2 * 2:]
                    other = next(o for o in others if o != m)
                    entries.append((i, "method", r, other, path + tail))
                for j in ((i + 1) % S,
                          (i + 1 + int(rng.integers(0, S - 1))) % S):
                    entries.append((i, "otherset", r, name,
                                    p.format(i=j) + tail))
                entries.append((i, "longmiss", r, name,
                                _stretch(literal + "x" + path[len(literal):],
                                         LONG_PATHS[(i + r) % 2])))
            for name in METHODS[:2]:
                entries.append((i, "forbidden", 0, name, "/forbidden/zone"))
        return sorted(set(entries))

    def _catalogue(self) -> None:
        """The candidates sorted into admitted and refused by the reference
        above, the bytes each puts on the wire, and the indices the draws
        below go by."""
        S, R = self.n_rulesets, len(self.rules)
        rng = np.random.default_rng(WORLD_SEED)
        entries = self._candidates(rng)
        sets = np.array([e[0] for e in entries])
        case = np.array([e[1] for e in entries])
        req = request_columns([e[3:] for e in entries])
        admitted = self._admitting(sets, req["http_method"],
                                   req["http_path"]) >= 0
        # a case is what the reference makes of it: another set's right
        # request that a rule here admits after all (set 12's on the port of
        # set 1) is an admitted one across a number; any other request
        # meant to be refused that some rule form admits is left out
        case[admitted & (case == "otherset")] = "across"
        keep = ~admitted | np.isin(case, ADMITTED)
        if (~admitted & np.isin(case, ADMITTED)).any():
            raise ValueError("the rule forms refuse a request made to be "
                             "admitted")
        sets, case, admitted = sets[keep], case[keep], admitted[keep]
        form = np.array([e[2] for e in entries])[keep]
        self._req_set = sets
        self._req = {k: v[keep] for k, v in req.items()}
        self.case = case                             # of every request
        for c in ADMITTED + REFUSED:
            if not (case == c).any():
                raise ValueError(f"the parameters leave no request of the "
                                 f"contrast case {c!r}")
        # the right requests of (set, rule form), a row of method variants
        # each, and the long ones; the admitted contrast cases, one of each
        # in turn; every refused request
        right = np.isin(case, ("right", "anymethod"))
        self._variants = np.array([1 if m else len(METHODS)
                                   for m, _p in self.rules])
        self._right = np.zeros((S, R, int(self._variants.max())), np.int64)
        self._long = np.zeros((S, R, len(LONG_PATHS)), np.int64)
        for i in range(S):
            for r in range(R):
                here = (sets == i) & (form == r)
                q = np.nonzero(here & right)[0]
                self._right[i, r, :q.size] = q
                self._long[i, r] = np.nonzero(here & (case == "long"))[0]
        contrast = [rng.permutation(np.nonzero(case == c)[0])
                    for c in ADMITTED[1:]]
        turns = max(c.size for c in contrast)
        self._head = np.array([c[t] for t in range(turns) for c in contrast
                               if t < c.size])
        self._refused = np.nonzero(~admitted)[0]

    def _requests(self, rng, q: np.ndarray, sport_lo: int, sport_hi: int,
                  dport=None) -> Flows:
        """One flow a request ``q`` of the catalogue, from a drawn peer."""
        n = q.shape[0]
        peer = self.peer_net + rng.integers(1, self.peer_span - 1, n)
        if dport is None:
            dport = self.first_port + self._req_set[q]
        return {"src": v4_words(peer.astype(np.uint32)),
                "sport": rng.integers(sport_lo, sport_hi, n).astype(np.int32),
                "dport": np.asarray(dport).astype(np.int32),
                "proto": np.full((n,), PROTO_TCP, np.int32),
                "is_v6": np.zeros((n,), bool),
                **{k: v[q] for k, v in self._req.items()}}

    def _draw_right(self, rng, n: int) -> np.ndarray:
        """``n`` right requests: the set drawn evenly, the rule form by
        ``live_requests``, ``long_path_share`` of them with a long path."""
        i = rng.integers(0, self.n_rulesets, n)
        r = rng.choice(len(self.rules), n, p=self.live_requests)
        q = self._right[i, r, rng.integers(0, 1 << 30, n) % self._variants[r]]
        long = rng.random(n) < self.long_path_share
        return np.where(long, self._long[i, r, rng.integers(
            0, len(LONG_PATHS), n)], q)

    # -- flows ----------------------------------------------------------------
    def allowed_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """The admitted contrast cases first (a law that ranks flows in the
        order drawn makes them the heaviest), then right requests."""
        head = self._head[:min(self._head.size, n // HEAD_SHARE)]
        q = np.concatenate([head, self._draw_right(rng, n - head.size)])
        return self._requests(rng, q, sport_lo, sport_hi)

    def denied_flows(self, rng, n: int, sport_lo: int,
                     sport_hi: int) -> Flows:
        """A request its port's set refuses (each a contrast case), or a
        right request to a port just past the last set's."""
        refused = rng.random(n) < self.denied_split[0]
        q = np.where(refused,
                     self._refused[rng.integers(0, self._refused.size, n)],
                     self._draw_right(rng, n))
        past = self.first_port + self.n_rulesets \
            + rng.integers(0, NEIGHBOUR_PORTS, n)
        return self._requests(
            rng, q, sport_lo, sport_hi,
            np.where(refused, self.first_port + self._req_set[q], past))

    def unknown_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """No source is unknown to a world without ``fromEndpoints``: right
        requests to TCP ports no document names, far from the sets'."""
        return self._requests(rng, self._draw_right(rng, n), sport_lo,
                              sport_hi, rng.integers(*FAR_PORTS, n))


def build(params: Dict) -> World:
    return World(params)
