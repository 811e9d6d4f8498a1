"""Service load-balancer tensors — the lbmap analog (SURVEY.md §2
"Services/LB": upstream ``pkg/service`` programs ``pkg/maps/lbmap``; the
datapath consumes it in ``bpf/lib/lb.h`` — lb4_lookup_service →
lb4_select_backend → DNAT, reverse NAT via the revnat map).

TPU-native layout:

- **Frontend table**: open-addressed hash table over (addr[4 words], port,
  proto) → frontend index, probed exactly like the conntrack table (same
  murmur mix, fixed probe depth). Built host-side; capacity grows until every
  key fits inside the probe window, so device lookups are bounded.
- **Maglev tables**: one row per service, ``[n_services, M]`` (M prime) of
  global backend indices — consistent hashing so backend churn re-steers
  ~1/B of flows (upstream: pkg/loadbalancer Maglev). Weighted backends take
  proportionally many table slots.
- **Backend arrays**: ``be_addr [B,4]``, ``be_port [B]``.
- **Rev-NAT arrays**: per frontend VIP/port, gathered on the reply path to
  un-DNAT (upstream: lb4_rev_nat via the CT entry's rev_nat_index).

Backend selection is **stateless-deterministic**: hash of the un-translated
5-tuple mod M. The same flow always picks the same backend while the backend
set is unchanged; on backend change Maglev bounds re-steering. (Upstream
additionally pins a flow's backend in a CT_SERVICE entry; the stateless form
is the TPU-friendly equivalent and is what the oracle specifies.)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.kernels.hashing import hash_words_np
from cilium_tpu.model.services import Backend, Frontend, Service
from cilium_tpu.utils.ip import parse_addr

FE_KEY_WORDS = 6          # addr[4], port, proto
LB_PROBE_DEPTH = 8
MAGLEV_M_DEFAULT = 251    # prime; upstream's default 16381 runs in the
#                           benchmark's svc10k-maglev (10,000 rows of it)


@dataclass(frozen=True)
class LBConfig:
    maglev_m: int = MAGLEV_M_DEFAULT
    probe_depth: int = LB_PROBE_DEPTH


@dataclass(frozen=True)
class LBTables:
    """Compiled LB state. Device-facing arrays + host metadata.

    Rev-NAT ids are STABLE across snapshots (allocated by the
    ServiceRegistry, never reused): CT entries store ``rnat_id + 1`` and the
    reply path resolves it against ``rnat_addr/rnat_port/rnat_valid``, which
    are indexed by id — a service deleted between snapshots leaves its row
    invalid, so stale CT entries fail closed (no rewrite) instead of
    rewriting to another service's VIP."""
    tab_keys: np.ndarray        # [cap, 6] uint32 — 0-key = empty
    tab_val: np.ndarray         # [cap] int32 frontend idx (-1 empty)
    fe_service: np.ndarray      # [F] int32 → maglev row
    fe_rnat_id: np.ndarray      # [F] int32 stable rev-NAT id
    rnat_addr: np.ndarray       # [R, 4] uint32 (the VIP), indexed by id
    rnat_port: np.ndarray       # [R] int32
    rnat_valid: np.ndarray      # [R] bool
    maglev: np.ndarray          # [S, M] int32 global backend idx (-1 = none)
    be_addr: np.ndarray         # [B, 4] uint32
    be_port: np.ndarray         # [B] int32
    probe_depth: int
    # host-side metadata (CLI / oracle / trace)
    frontends: Tuple[Frontend, ...]
    fe_names: Tuple[str, ...]   # "namespace/name" per frontend
    backends: Tuple[Backend, ...]
    # Maglev row r was populated from backends[row_base[r]:row_base[r+1]]
    # (its entries are those indices): what a later build reuses a row by
    row_base: np.ndarray = field(
        default_factory=lambda: np.zeros((1,), dtype=np.int64))
    rows_built: int = 0         # rows this build populated, not reused

    @property
    def n_frontends(self) -> int:
        return len(self.frontends)

    @property
    def n_services(self) -> int:
        """Maglev rows populated for a service (the table has one row of
        -1 where there is none)."""
        return len(self.row_base) - 1

    def tensors(self) -> Dict[str, np.ndarray]:
        return {
            "lb_tab_keys": self.tab_keys,
            "lb_tab_val": self.tab_val,
            "lb_fe_service": self.fe_service,
            "lb_fe_rnat_id": self.fe_rnat_id,
            "lb_rnat_addr": self.rnat_addr,
            "lb_rnat_port": self.rnat_port,
            "lb_rnat_valid": self.rnat_valid,
            "lb_maglev": self.maglev,
            "lb_be_addr": self.be_addr,
            "lb_be_port": self.be_port,
        }


def _is_prime(n: int) -> bool:
    if n < 2:
        return False
    for p in range(2, int(n ** 0.5) + 1):
        if n % p == 0:
            return False
    return True


def _name_hashes(names: Sequence[str], suffix: str) -> np.ndarray:
    """[n] uint32: ``hash_words_np(_str_hash_words(name + suffix))`` of every
    name, the names of one padded length hashed together."""
    data = [(name + suffix).encode() for name in names]
    words = np.array([-(-len(d) // 4) for d in data], dtype=np.int64)
    out = np.zeros((len(data),), dtype=np.uint32)
    for w in np.unique(words).tolist():
        idx = np.nonzero(words == w)[0]
        buf = b"".join(data[i].ljust(4 * w, b"\x00") for i in idx.tolist())
        out[idx] = hash_words_np(np.frombuffer(buf, dtype="<u4")
                                 .reshape(idx.size, w))
    return out


def _permutations(backends: Sequence[Backend], m: int):
    """→ (offset, skip) [n] int64 of each backend's permutation of [0, M),
    from its name's two hashes."""
    names = [f"{b.addr}:{b.port}" for b in backends]
    offsets = _name_hashes(names, "#o").astype(np.int64) % m
    skips = _name_hashes(names, "#s").astype(np.int64) % (m - 1) + 1
    return offsets, skips


def _maglev_rows_py(offsets, skips, weights, row_start, m: int) -> np.ndarray:
    """The population without the native library: the backends of a row take
    turns claiming their next unclaimed permutation slot, over Python ints."""
    out = np.full((len(row_start) - 1, m), -1, dtype=np.int32)
    for r in range(len(row_start) - 1):
        b0, b1 = int(row_start[r]), int(row_start[r + 1])
        if b0 == b1:
            continue
        row = [-1] * m
        at = offsets[b0:b1].tolist()
        skip = skips[b0:b1].tolist()
        turns = [i for i, w in enumerate(weights[b0:b1].tolist())
                 for _ in range(w)]
        filled = 0
        while filled < m:
            for i in turns:
                c = at[i]
                while row[c] >= 0:
                    c = (c + skip[i]) % m
                row[c] = i
                at[i] = (c + skip[i]) % m
                filled += 1
                if filled == m:
                    break
        out[r] = row
    return out


def _native_fill():
    """``shim_maglev_fill`` of the shim's library, or None where the library
    is not built (or was built before it had one)."""
    import ctypes
    try:
        from cilium_tpu.shim.bindings import _load_lib
        fill = _load_lib().shim_maglev_fill
    except (OSError, AttributeError):
        return None
    p64, p32 = ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32)
    fill.restype = None
    fill.argtypes = [p64, p64, p32, p64, ctypes.c_uint32, ctypes.c_uint32,
                     p32, ctypes.c_uint32]
    return lambda off, skip, w, start, rows, m, out, threads: fill(
        off.ctypes.data_as(p64), skip.ctypes.data_as(p64),
        w.ctypes.data_as(p32), start.ctypes.data_as(p64), rows, m,
        out.ctypes.data_as(p32), threads)


MAGLEV_FILL_THREADS = 8


def maglev_rows(rows: Sequence[Sequence[Backend]], m: int,
                native: bool = True,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """Standard Maglev population (the upstream pkg/loadbalancer algorithm
    shape) of one row a backend list → ``[len(rows), M]`` int32 of row-local
    backend indices (-1 throughout for an empty list): each backend gets a
    permutation of [0, M) from (offset, skip) derived from its name hash;
    backends take turns claiming their next unclaimed slot, weighted
    backends take ``weight`` consecutive turns. No Python step a slot: the
    turns run in the shim's library (``shim_maglev_fill``), over Python ints
    where it is not built; both give the one table
    (tests/test_maglev_build.py holds them to the loop this replaced).
    ``out``: a C-contiguous ``[len(rows), M]`` int32 array to populate."""
    if not _is_prime(m):
        raise ValueError(f"maglev M must be prime, got {m}")
    flat = [b for row in rows for b in row]
    row_start = np.concatenate([[0], np.cumsum(
        [len(row) for row in rows], dtype=np.int64)]).astype(np.int64)
    offsets, skips = _permutations(flat, m)
    weights = np.array([b.weight for b in flat], dtype=np.int32)
    fill = _native_fill() if native else None
    if out is None:
        out = np.empty((len(rows), m), dtype=np.int32)
    if fill is None:
        out[:] = _maglev_rows_py(offsets, skips, weights, row_start, m)
        return out
    fill(np.ascontiguousarray(offsets), np.ascontiguousarray(skips), weights,
         row_start, len(rows), m, out, MAGLEV_FILL_THREADS)
    return out


def maglev_table(backends: Sequence[Backend], m: int) -> np.ndarray:
    """One service's row: ``maglev_rows`` of one list."""
    return maglev_rows([backends], m)[0]


def _addr_words(addrs: Sequence[str]) -> np.ndarray:
    """[n, 4] uint32 device words of each address literal."""
    packed = b"".join(parse_addr(a)[0] for a in addrs)
    return np.frombuffer(packed, dtype=">u4").reshape(len(addrs), 4) \
        .astype(np.uint32)


def _fill_frontend_table(fe_keys: np.ndarray, probe_depth: int):
    """The open-addressed frontend table: key i sits in the first slot of
    its probe window that no key before it took, the capacity doubling until
    every key fits. The keys are hashed once and placed a pass at a time: a
    pass gives every key still unplaced the first free slot of its window
    (the lowest key where several want one), and keeps a placement only
    where no unplaced key before it has that slot in its window, which is
    when inserting the keys one after the other gives the same."""
    F = fe_keys.shape[0]
    hashes = hash_words_np(fe_keys).astype(np.int64) if F else \
        np.zeros((0,), np.int64)
    cap = 8
    while cap < 2 * max(F, 1):
        cap *= 2
    while True:
        slot_of = _place_keys(hashes & (cap - 1), cap, probe_depth)
        if slot_of is not None:
            break
        cap *= 2
    tab_keys = np.zeros((cap, FE_KEY_WORDS), dtype=np.uint32)
    tab_val = np.full((cap,), -1, dtype=np.int32)
    tab_keys[slot_of] = fe_keys
    tab_val[slot_of] = np.arange(F, dtype=np.int32)
    return tab_keys, tab_val


def _place_keys(base: np.ndarray, cap: int, depth: int
                ) -> Optional[np.ndarray]:
    """→ [F] the slot sequential insertion gives each key, or None where
    some key's window is full."""
    F = base.shape[0]
    slot_of = np.full((F,), -1, dtype=np.int64)
    taken = np.zeros((cap,), dtype=bool)
    todo = np.arange(F, dtype=np.int64)
    window = np.arange(depth, dtype=np.int64)
    never = np.int64(F)
    while todo.size:
        slots = (base[todo, None] + window) & (cap - 1)       # [n, depth]
        free = ~taken[slots]
        if not free.any(axis=1).all():
            # a window full of keys placed for good: each of them came
            # before this key (one after it is final only once this key is)
            return None
        want = slots[np.arange(todo.size), free.argmax(axis=1)]
        lowest = np.full((cap,), never)
        np.minimum.at(lowest, want, todo)
        won = lowest[want] == todo
        # a placement is final when no unsettled key before it could still
        # come to its slot; the unsettled are the losers, and then every
        # winner that one of the unsettled before it could displace
        unsettled = ~won
        while True:
            reach = np.full((cap,), never)
            np.minimum.at(reach, slots[unsettled].ravel(),
                          np.repeat(todo[unsettled], depth))
            more = won & ~unsettled & (reach[want] < todo)
            if not more.any():
                break
            unsettled |= more
        final = ~unsettled          # the lowest unplaced key always is
        slot_of[todo[final]] = want[final]
        taken[want[final]] = True
        todo = todo[~final]
    return slot_of


def _row_keys(lb: "LBTables"):
    """What each Maglev row of ``lb`` was populated from → its index."""
    return {lb.backends[lb.row_base[r]:lb.row_base[r + 1]]: r
            for r in range(len(lb.row_base) - 1)}


def build_lb(registry_or_services, cfg: Optional[LBConfig] = None,
             prev: Optional[LBTables] = None) -> LBTables:
    """Compile LB state. Deterministic given the service set
    (services/frontends iterated in sorted registry order).

    Accepts a ServiceRegistry (preferred: its stable rev-NAT id allocator is
    used) or a plain Service sequence (ids fall back to positional — only
    safe when the service set never changes, e.g. one-shot tests).

    ``prev`` is an earlier build's result: a service's Maglev row is taken
    from it while its backends, their weights and M stand, so a build after
    a policy-only change populates none (``rows_built``)."""
    cfg = cfg or LBConfig()
    if hasattr(registry_or_services, "all"):
        services: Sequence[Service] = registry_or_services.all()
        rnat_id_of = registry_or_services.rnat_id
    else:
        services = registry_or_services
        _pos = {}
        rnat_id_of = lambda fe: _pos.setdefault(  # noqa: E731
            (fe.addr, fe.port, fe.proto), len(_pos))
    services = [svc for svc in services if svc.frontends]
    frontends = [fe for svc in services for fe in svc.frontends]
    fe_names = [f"{svc.namespace}/{svc.name}" for svc in services
                for _fe in svc.frontends]
    fe_service = np.repeat(np.arange(len(services), dtype=np.int32),
                           [len(svc.frontends) for svc in services])
    fe_rnat_ids = [rnat_id_of(fe) for fe in frontends]
    rows = [tuple(svc.lb_backends) for svc in services]
    all_backends = tuple(b for row in rows for b in row)
    row_base = np.concatenate([[0], np.cumsum(
        [len(row) for row in rows], dtype=np.int64)]).astype(np.int64)

    F, B, S, m = len(frontends), len(all_backends), len(rows), cfg.maglev_m
    R = max(fe_rnat_ids) + 1 if fe_rnat_ids else 1
    fe_addr16 = [parse_addr(fe.addr)[0] for fe in frontends]
    fe_keys = np.zeros((max(F, 1), FE_KEY_WORDS), dtype=np.uint32)
    if F:
        fe_keys[:, :4] = np.frombuffer(b"".join(fe_addr16), dtype=">u4") \
            .reshape(F, 4)
        fe_keys[:, 4] = [fe.port for fe in frontends]
        fe_keys[:, 5] = [fe.proto for fe in frontends]
    seen_keys = {}
    for i, (addr16, fe) in enumerate(zip(fe_addr16, frontends)):
        first = seen_keys.setdefault((addr16, fe.port, fe.proto), i)
        if first != i:
            raise ValueError(
                f"duplicate service frontend {fe.addr}:{fe.port}/{fe.proto}: "
                f"declared by both {fe_names[first]} and {fe_names[i]}")
    rnat_addr = np.zeros((R, 4), dtype=np.uint32)
    rnat_port = np.zeros((R,), dtype=np.int32)
    rnat_valid = np.zeros((R,), dtype=bool)
    if F:
        rnat_addr[fe_rnat_ids] = fe_keys[:F, :4]
        rnat_port[fe_rnat_ids] = fe_keys[:F, 4]
        rnat_valid[fe_rnat_ids] = True

    be_addr = np.zeros((max(B, 1), 4), dtype=np.uint32)
    be_port = np.zeros((max(B, 1),), dtype=np.int32)
    if B:
        be_addr[:] = _addr_words([b.addr for b in all_backends])
        be_port[:] = [b.port for b in all_backends]

    # the Maglev rows, row-local indices shifted to global ones: taken from
    # ``prev`` where the row's backends stand, populated together otherwise
    fresh = list(range(S))
    if prev is not None and prev.maglev.shape[1] == m and S \
            and prev.backends == all_backends \
            and np.array_equal(prev.row_base, row_base):
        maglev, fresh = prev.maglev, []          # no service changed
    else:
        maglev = np.empty((max(S, 1), m), dtype=np.int32)
        had = _row_keys(prev) if prev is not None \
            and prev.maglev.shape[1] == m else {}
        fresh = [r for r in range(S) if rows[r] not in had]
        if len(fresh) == S:
            maglev_rows(rows, m, out=maglev[:S])        # in place
        elif fresh:
            maglev[fresh] = maglev_rows([rows[r] for r in fresh], m)
        kept = set(range(S)).difference(fresh)
        for r in range(S):
            shift = row_base[r]
            if r in kept:
                k = had[rows[r]]
                maglev[r] = prev.maglev[k]
                shift -= prev.row_base[k]
            if rows[r] and shift:
                maglev[r] += np.int32(shift)
        if not S:
            maglev[:] = -1

    tab_keys, tab_val = _fill_frontend_table(fe_keys[:F], cfg.probe_depth)

    return LBTables(
        tab_keys=tab_keys, tab_val=tab_val,
        fe_service=fe_service if F else np.zeros((1,), dtype=np.int32),
        fe_rnat_id=np.asarray(fe_rnat_ids, dtype=np.int32)
        if F else np.zeros((1,), dtype=np.int32),
        rnat_addr=rnat_addr, rnat_port=rnat_port, rnat_valid=rnat_valid,
        maglev=maglev, be_addr=be_addr, be_port=be_port,
        probe_depth=cfg.probe_depth,
        frontends=tuple(frontends), fe_names=tuple(fe_names),
        backends=all_backends, row_base=row_base, rows_built=len(fresh),
    )


# --------------------------------------------------------------------------- #
# Host mirrors (one definition of the semantics — the jnp executor in
# kernels/lb.py must agree bit-for-bit; test-enforced)
# --------------------------------------------------------------------------- #
def lb_select_words_np(batch) -> np.ndarray:
    """[N, 10] uint32 backend-selection words: the forward CT key with the
    direction bits masked off. Selection only ever runs on un-translated
    forward packets (dst = VIP) — replies carry the client address as dst and
    never match a frontend — so this just has to be deterministic per flow."""
    src, dst = batch["src"], batch["dst"]
    return np.stack([
        src[:, 0], src[:, 1], src[:, 2], src[:, 3],
        dst[:, 0], dst[:, 1], dst[:, 2], dst[:, 3],
        (batch["sport"].astype(np.uint32) << np.uint32(16))
        | batch["dport"].astype(np.uint32),
        batch["proto"].astype(np.uint32) << np.uint32(8),
    ], axis=-1).astype(np.uint32)


def lb_lookup_np(lb: LBTables, batch) -> np.ndarray:
    """Frontend index per packet (-1 = no service). Mirrors kernels/lb.py."""
    n = batch["dport"].shape[0]
    keys = np.stack([
        batch["dst"][:, 0], batch["dst"][:, 1],
        batch["dst"][:, 2], batch["dst"][:, 3],
        batch["dport"].astype(np.uint32), batch["proto"].astype(np.uint32),
    ], axis=-1).astype(np.uint32)
    cap = lb.tab_keys.shape[0]
    base = hash_words_np(keys).astype(np.int64) & (cap - 1)
    found = np.full((n,), -1, dtype=np.int32)
    for d in range(lb.probe_depth):
        s = (base + d) & (cap - 1)
        eq = (lb.tab_keys[s] == keys).all(axis=-1) & (lb.tab_val[s] >= 0)
        found = np.where((found < 0) & eq, lb.tab_val[s], found)
    return found


def lb_translate_np(lb: LBTables, batch):
    """Host mirror of the kernel's LB step → (new_dst, new_dport, rev_nat,
    no_backend, fe_idx). rev_nat is the frontend's stable rev-NAT id + 1
    (0 = untranslated)."""
    fe_idx = lb_lookup_np(lb, batch)
    hit = (fe_idx >= 0) & np.asarray(batch["valid"])
    safe_fe = np.where(hit, fe_idx, 0)
    h = hash_words_np(lb_select_words_np(batch)).astype(np.int64)
    m = lb.maglev.shape[1]
    be = lb.maglev[lb.fe_service[safe_fe], h % m]
    no_backend = hit & (be < 0)
    do = hit & (be >= 0)
    safe_be = np.where(do, be, 0)
    new_dst = np.where(do[:, None], lb.be_addr[safe_be], batch["dst"])
    new_dport = np.where(do, lb.be_port[safe_be], batch["dport"])
    rev_nat = np.where(do, lb.fe_rnat_id[safe_fe] + 1, 0).astype(np.int32)
    return new_dst, new_dport, rev_nat, no_backend, fe_idx
