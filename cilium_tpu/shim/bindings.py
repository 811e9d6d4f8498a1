"""ctypes bindings for the C++ shim + goldengen scenario IO.

The shim's batch output converts straight into the ``kernels/records``
dict-of-arrays layout, so tests and pcap replay drive the same path the
AF_XDP front end would: frames → shim parse/batch → device classify →
verdict bitmap → shim_apply_verdicts.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.kernels.records import empty_batch, reset_batch_rows
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C

_SHIM_DIR = os.path.dirname(os.path.abspath(__file__))
# CILIUM_TPU_SHIM_LIB overrides the library (e.g. the TSan build from
# `make -C cilium_tpu/shim tsan` — SURVEY §5 race detection)
LIB_PATH = os.environ.get("CILIUM_TPU_SHIM_LIB",
                          os.path.join(_SHIM_DIR, "libflowshim.so"))
GOLDENGEN_PATH = os.path.join(_SHIM_DIR, "goldengen")


class ShimRecord(ctypes.Structure):
    _pack_ = 1
    _fields_ = [
        ("src", ctypes.c_uint32 * 4),
        ("dst", ctypes.c_uint32 * 4),
        ("sport", ctypes.c_uint16),
        ("dport", ctypes.c_uint16),
        ("proto", ctypes.c_uint8),
        ("tcp_flags", ctypes.c_uint8),
        ("is_v6", ctypes.c_uint8),
        ("direction", ctypes.c_uint8),
        ("ep_id", ctypes.c_uint32),
        ("frame_idx", ctypes.c_uint32),
        ("orig_len", ctypes.c_uint32),
        ("pad", ctypes.c_uint8 * 12),
    ]


class ShimTokens(ctypes.Structure):
    _pack_ = 1
    _fields_ = [
        ("has_tokens", ctypes.c_uint8),
        ("method", ctypes.c_uint8),
        ("path_len", ctypes.c_uint16),
        ("path", ctypes.c_uint8 * 64),
        ("pad", ctypes.c_uint8 * 4),
    ]


class ShimStats(ctypes.Structure):
    _fields_ = [(n, ctypes.c_uint64) for n in (
        "frames_seen", "frames_parsed", "parse_errors", "batches_emitted",
        "records_emitted", "verdict_drops", "verdict_passes",
        "tx_full_drops", "verdict_expired")]


# must equal flowshim.cc kMaxUnverdictedBatches: both sides age out the
# oldest unverdicted batch at the same poll, keeping the verdict FIFO and
# the Python count FIFO aligned
MAX_UNVERDICTED_BATCHES = 64
# must equal flowshim.cc kNumFrames: the umem frames and the entries of
# each of the four rings that afxdp_bind sets up
AFXDP_RING_FRAMES = 4096


def _load_lib():
    if not os.path.exists(LIB_PATH):
        raise FileNotFoundError(
            f"{LIB_PATH} not built — run `make -C cilium_tpu/shim`")
    lib = ctypes.CDLL(LIB_PATH)
    lib.shim_create.restype = ctypes.c_void_p
    lib.shim_create.argtypes = [ctypes.c_uint32, ctypes.c_uint64]
    lib.shim_destroy.argtypes = [ctypes.c_void_p]
    lib.shim_register_endpoint.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.shim_feed_frame.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
    lib.shim_poll_batch.restype = ctypes.c_uint32
    lib.shim_poll_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
        ctypes.POINTER(ShimRecord), ctypes.POINTER(ShimTokens)]
    lib.shim_apply_verdicts.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.shim_get_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(ShimStats)]
    lib.shim_flow_shard.restype = ctypes.c_uint32
    lib.shim_flow_shard.argtypes = [ctypes.POINTER(ShimRecord), ctypes.c_uint32]
    lib.shim_flow_shard2.restype = ctypes.c_uint32
    lib.shim_flow_shard2.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ShimRecord), ctypes.c_uint32]
    lib.shim_set_lb.restype = ctypes.c_int
    lib.shim_set_lb.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_uint32, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_uint32, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_uint32]
    lib.shim_afxdp_bind.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.shim_afxdp_poll.restype = ctypes.c_int
    lib.shim_afxdp_poll.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64]
    lib.shim_mock_rings_init.restype = ctypes.c_int
    lib.shim_mock_rings_init.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32]
    lib.shim_mock_rx_inject.restype = ctypes.c_int
    lib.shim_mock_rx_inject.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.shim_mock_tx_drain.restype = ctypes.c_uint32
    lib.shim_mock_tx_drain.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint32]
    lib.shim_ring_fill_level.restype = ctypes.c_uint32
    lib.shim_ring_fill_level.argtypes = [ctypes.c_void_p]
    return lib




class FlowShim:
    """Python handle on the native shim (mock-driver mode unless afxdp_bind
    succeeds)."""

    def __init__(self, batch_size: int = 256, timeout_us: int = 500):
        self._lib = _load_lib()
        self._handle = self._lib.shim_create(batch_size, timeout_us)
        self.batch_size = batch_size
        self._rec_buf = (ShimRecord * batch_size)()
        self._tok_buf = (ShimTokens * batch_size)()
        # structured views over the ctypes buffers, built once — frombuffer
        # per poll would allocate a view object on the harvest hot path
        self._rec_view = np.frombuffer(self._rec_buf, dtype=self._REC_DTYPE,
                                       count=batch_size)
        self._tok_view = np.frombuffer(self._tok_buf, dtype=self._TOK_DTYPE,
                                       count=batch_size)
        self._l7_pos = np.arange(C.L7_PATH_MAXLEN)
        # record counts of harvested-but-unverdicted batches, FIFO — the
        # C++ side holds one FrameRef per emitted record, so apply_verdicts
        # must consume exactly that many per batch (short verdict arrays
        # would desync frames from verdicts; see apply_verdicts)
        self._pending_counts: list = []
        self._enforcing = False        # mirrors flowshim.cc Shim::enforcing
        self._rings_ready = False      # set by afxdp_bind/mock_rings_init
        # frames the NIC can have handed over and not got back: the smaller
        # of the rx ring and the umem (0 until rings exist). The feeder
        # sizes a harvest from it (shim/feeder.harvest_ceiling)
        self.ring_frames = 0
        self.last_poll_rows = 0        # records in the newest polled batch

    def close(self):
        if self._handle:
            self._lib.shim_destroy(self._handle)
            self._handle = None

    def register_endpoint(self, ip: str, ep_id: int) -> None:
        from cilium_tpu.utils.ip import parse_addr
        addr16, _ = parse_addr(ip)
        self._lib.shim_register_endpoint(self._handle, addr16, ep_id)

    def feed_frame(self, frame: bytes, now_us: int = 0) -> bool:
        return self._lib.shim_feed_frame(
            self._handle, frame, len(frame), now_us) == 0

    # structured-dtype mirrors of ShimRecord/ShimTokens for vectorized
    # batch conversion (a per-record Python loop caps the harvest path at
    # ~1e5 records/s; frombuffer keeps it out of the packet path)
    _REC_DTYPE = np.dtype([
        ("src", "<u4", (4,)), ("dst", "<u4", (4,)),
        ("sport", "<u2"), ("dport", "<u2"),
        ("proto", "u1"), ("tcp_flags", "u1"), ("is_v6", "u1"),
        ("direction", "u1"), ("ep_id", "<u4"), ("frame_idx", "<u4"),
        ("orig_len", "<u4"), ("pad", "u1", (12,))])
    _TOK_DTYPE = np.dtype([
        ("has_tokens", "u1"), ("method", "u1"), ("path_len", "<u2"),
        ("path", "u1", (C.L7_PATH_MAXLEN,)), ("pad", "u1", (4,))])

    def make_poll_buffer(self, rows: Optional[int] = None
                         ) -> Dict[str, np.ndarray]:
        """A reusable ``poll_batch(out=...)`` buffer: the records layout
        plus the shim-side ``_ep_raw``/``_frame_idx`` columns, ``rows``
        long (default one batch). The feeder preallocates a pool of
        harvest-sized ones and polls into ``batch_size``-row views of
        them, so the hot harvest loop never builds a fresh column dict."""
        rows = self.batch_size if rows is None else rows
        b = empty_batch(rows)
        b["_ep_raw"] = np.zeros((rows,), dtype=np.int64)
        b["_frame_idx"] = np.zeros((rows,), dtype=np.int64)
        return b

    def poll_batch(self, now_us: int = 0, force: bool = False,
                   out: Optional[Dict[str, np.ndarray]] = None
                   ) -> Optional[Dict[str, np.ndarray]]:
        """Harvest ONE batch of the batcher, at most ``batch_size`` records,
        in the kernels/records layout (None if not ready: not full, not
        timed out, not forced). Records for unknown endpoints (ep_id 0)
        stay invalid (fail closed). Each batch polled is one entry of the
        verdict FIFO: :meth:`apply_verdicts` answers them one by one, in
        poll order. ``last_poll_rows`` holds its record count.

        ``out=`` reuses a caller-owned buffer from :meth:`make_poll_buffer`
        instead of allocating — or any ``batch_size``-row view of a longer
        one: the feeder's harvest polls several batches into consecutive
        views of one buffer and submits them together. Rows [:n] are
        overwritten, rows [n:batch_size] are reset to the empty-batch
        defaults (``valid`` False, method ANY, zeroed path) so a reused
        buffer is indistinguishable from a fresh one. The caller must not
        hand the same rows back before their previous batch's consumer is
        done with them."""
        FAULTS.fire("shim.rx_ring")
        n = self._lib.shim_poll_batch(self._handle, now_us, int(force),
                                      self._rec_buf, self._tok_buf)
        if n == 0:
            return None
        self._pending_counts.append(int(n))
        self.last_poll_rows = int(n)
        if not self._enforcing \
                and len(self._pending_counts) > MAX_UNVERDICTED_BATCHES:
            self._pending_counts.pop(0)   # C++ aged out the same batch
        b = out if out is not None else self.make_poll_buffer()
        rec = self._rec_view
        tok = self._tok_view
        b["src"][:n] = rec["src"][:n]
        b["dst"][:n] = rec["dst"][:n]
        b["sport"][:n] = rec["sport"][:n]
        b["dport"][:n] = rec["dport"][:n]
        b["proto"][:n] = rec["proto"][:n]
        b["tcp_flags"][:n] = rec["tcp_flags"][:n]
        b["is_v6"][:n] = rec["is_v6"][:n].astype(bool)
        b["direction"][:n] = rec["direction"][:n]
        b["_ep_raw"][:n] = rec["ep_id"][:n]
        b["_frame_idx"][:n] = rec["frame_idx"][:n]
        b["valid"][:n] = rec["ep_id"][:n] != 0
        has = tok["has_tokens"][:n].astype(bool)
        b["http_method"][:n] = np.where(has, tok["method"][:n],
                                        C.HTTP_METHOD_ANY)
        pos = self._l7_pos
        keep = has[:, None] & (pos[None, :] < tok["path_len"][:n, None])
        b["http_path"][:n] = np.where(keep, tok["path"][:n], 0)
        if out is not None:
            if n < self.batch_size:
                # reused buffer: restore the empty-batch tail so stale
                # rows from the previous poll can never leak into this
                # batch — a reused buffer must be indistinguishable from
                # a fresh one
                reset_batch_rows(b, n, self.batch_size)
            # ep_slot is caller-mapped (poll never writes it): restore the
            # fresh-buffer zeros across ALL rows, not just the tail
            b["ep_slot"][:] = 0
        return b

    def apply_verdicts(self, allow: np.ndarray) -> None:
        """Enforce verdicts for the OLDEST unverdicted batch (one
        :meth:`poll_batch` result; a harvest of several polls takes as many
        calls, each with its own rows of the harvest's verdicts, in poll
        order). ``allow`` may cover any prefix of that batch's records
        (e.g. only the valid rows); the remainder is dropped (fail closed)
        — the C++ side holds one frame per emitted record, so the full
        count must always be consumed or later verdicts would enforce on
        the wrong frames."""
        if not self._pending_counts:
            raise RuntimeError("apply_verdicts without a harvested batch")
        self._enforcing = True
        n = self._pending_counts.pop(0)
        arr = np.zeros((n,), dtype=np.uint8)
        k = min(n, int(np.asarray(allow).shape[0]))
        arr[:k] = np.asarray(allow)[:k].astype(np.uint8)
        self._lib.shim_apply_verdicts(self._handle, arr.tobytes(), n)

    def stats(self) -> Dict[str, int]:
        s = ShimStats()
        self._lib.shim_get_stats(self._handle, ctypes.byref(s))
        return {n: getattr(s, n) for n, _ in ShimStats._fields_}

    def set_lb(self, lb) -> None:
        """Program the steering-side LB state from a compile/lb.LBTables.
        Steering then matches parallel/mesh.flow_shard_of(batch, n, lb=lb):
        service DNAT first, so forward and reply packets of a service flow
        land on the same CT shard."""
        p32 = ctypes.POINTER(ctypes.c_int32)
        pu32 = ctypes.POINTER(ctypes.c_uint32)
        # keep contiguous copies alive across the call (C++ copies them)
        tk = np.ascontiguousarray(lb.tab_keys, dtype=np.uint32)
        tv = np.ascontiguousarray(lb.tab_val, dtype=np.int32)
        fs = np.ascontiguousarray(lb.fe_service, dtype=np.int32)
        mg = np.ascontiguousarray(lb.maglev, dtype=np.int32)
        ba = np.ascontiguousarray(lb.be_addr, dtype=np.uint32)
        bp = np.ascontiguousarray(lb.be_port, dtype=np.int32)
        rc = self._lib.shim_set_lb(
            self._handle,
            tk.ctypes.data_as(pu32), tv.ctypes.data_as(p32),
            tk.shape[0], lb.probe_depth,
            fs.ctypes.data_as(p32), fs.shape[0],
            mg.ctypes.data_as(p32), mg.shape[0], mg.shape[1],
            ba.ctypes.data_as(pu32), bp.ctypes.data_as(p32), bp.shape[0])
        if rc != 0:
            raise ValueError(f"shim_set_lb failed: {rc}")

    def flow_shard(self, rec_index: int, n_shards: int) -> int:
        return self._lib.shim_flow_shard2(
            self._handle, ctypes.byref(self._rec_buf[rec_index]), n_shards)

    def afxdp_bind(self, ifname: str, queue: int = 0) -> int:
        rc = self._lib.shim_afxdp_bind(self._handle, ifname.encode(), queue)
        if rc == 0:
            self._rings_ready = True
            self.ring_frames = AFXDP_RING_FRAMES
        return rc

    @property
    def rings_ready(self) -> bool:
        """Whether rx/fill rings exist (afxdp_bind or mock_rings_init
        succeeded). Python-side truth, deliberately NOT derived from the
        fill LEVEL: with every umem descriptor parked in the rx ring the
        level legitimately reads zero — exactly the state where the ring
        drain is most needed."""
        return self._rings_ready

    # -- ring path (kernel-mapped after afxdp_bind; heap-mocked for tests) --
    def afxdp_poll(self, budget: int = 256, now_us: int = 0) -> int:
        """Drain the rx ring into the batcher (completion→fill recycle
        first). Returns descriptors drained, or -errno.

        The ``shim.rx_ring`` injection point fires here: a fault is one
        failed poll (the caller's harvest loop must tolerate it — frames
        stay queued in the ring and drain on the next poll)."""
        FAULTS.fire("shim.rx_ring")
        return self._lib.shim_afxdp_poll(self._handle, budget, now_us)

    def mock_rings_init(self, ring_size: int = 64, frame_size: int = 2048,
                        n_frames: int = 64) -> None:
        rc = self._lib.shim_mock_rings_init(self._handle, ring_size,
                                            frame_size, n_frames)
        if rc != 0:
            raise OSError(-rc, "shim_mock_rings_init failed")
        self._rings_ready = True
        self.ring_frames = min(ring_size, n_frames)

    def mock_rx_inject(self, frame: bytes) -> int:
        """Act as the NIC: fill-ring frame ← frame bytes → rx descriptor."""
        return self._lib.shim_mock_rx_inject(self._handle, frame, len(frame))

    def mock_tx_drain(self, max_n: int = 256):
        """Act as the NIC's tx side: returns [(umem_addr, len)] of frames
        the shim forwarded; marks them transmitted via the completion ring."""
        addrs = (ctypes.c_uint64 * max_n)()
        lens = (ctypes.c_uint32 * max_n)()
        n = self._lib.shim_mock_tx_drain(self._handle, addrs, lens, max_n)
        return [(addrs[i], lens[i]) for i in range(n)]

    def ring_fill_level(self) -> int:
        return self._lib.shim_ring_fill_level(self._handle)


# --------------------------------------------------------------------------- #
# Test-frame builders (Ethernet/IP/TCP/UDP crafting for the mock driver)
# --------------------------------------------------------------------------- #
def build_frame(src_ip: str, dst_ip: str, sport: int, dport: int,
                proto: int = C.PROTO_TCP, tcp_flags: int = C.TCP_SYN,
                payload: bytes = b"", vlan: Optional[int] = None) -> bytes:
    import ipaddress
    src = ipaddress.ip_address(src_ip)
    dst = ipaddress.ip_address(dst_ip)
    eth = b"\x02\x00\x00\x00\x00\x01" + b"\x02\x00\x00\x00\x00\x02"
    if vlan is not None:
        eth += struct.pack(">HH", 0x8100, vlan)
    if proto == C.PROTO_TCP:
        l4 = struct.pack(">HHIIBBHHH", sport, dport, 0, 0, 5 << 4, tcp_flags,
                         65535, 0, 0) + payload
    elif proto in (C.PROTO_UDP, C.PROTO_SCTP):
        l4 = struct.pack(">HHHH", sport, dport, 8 + len(payload), 0) + payload
    else:  # ICMP: dport as the type
        l4 = struct.pack(">BBH", dport, 0, 0) + payload
    if src.version == 4:
        total = 20 + len(l4)
        ip = struct.pack(">BBHHHBBH4s4s", 0x45, 0, total, 0, 0, 64, proto, 0,
                         src.packed, dst.packed)
        return eth + struct.pack(">H", 0x0800) + ip + l4
    ip6 = struct.pack(">IHBB16s16s", 6 << 28, len(l4), proto, 64,
                      src.packed, dst.packed)
    return eth + struct.pack(">H", 0x86DD) + ip6 + l4


def build_http_frame(src_ip: str, dst_ip: str, sport: int, dport: int,
                     method: str, path: str) -> bytes:
    payload = f"{method} {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
    return build_frame(src_ip, dst_ip, sport, dport, C.PROTO_TCP,
                       C.TCP_ACK | C.TCP_PSH, payload)


# --------------------------------------------------------------------------- #
# goldengen scenario writer/runner (3-way parity)
# --------------------------------------------------------------------------- #
def write_scenario(path: str, ipcache_entries: Dict[str, int],
                   enforced: Tuple[bool, bool],
                   mapstate_entries: Sequence[Tuple],
                   l7_sets: Sequence[Sequence[Tuple[int, bytes]]],
                   packets: Sequence) -> None:
    """mapstate_entries: (dir, deny, proto, identity, lo, hi, l7_set_1based);
    l7_sets[i]: [(method_id_or_255, path_prefix_bytes)];
    packets: oracle.PacketRecord + .now attribute via tuple (rec, now)."""
    from cilium_tpu.utils.ip import parse_prefix
    out = [b"CTPUGV01"]
    out.append(struct.pack("<I", len(ipcache_entries)))
    for prefix, ident in ipcache_entries.items():
        # goldengen compares in the 128-bit v4-mapped space, same as here
        addr16, plen, is_v6 = parse_prefix(prefix)
        out.append(struct.pack("<16sHBBI", addr16, plen, int(is_v6), 0, ident))
    out.append(struct.pack("<BB", int(enforced[0]), int(enforced[1])))
    out.append(struct.pack("<I", len(mapstate_entries)))
    for (d, deny, proto, ident, lo, hi, l7) in mapstate_entries:
        out.append(struct.pack("<BBBBIHHHH", d, int(deny), proto, 0, ident,
                               lo, hi, l7, 0))
    out.append(struct.pack("<I", len(l7_sets)))
    for rules in l7_sets:
        out.append(struct.pack("<I", len(rules)))
        for method, prefix in rules:
            out.append(struct.pack("<BB64s", method, len(prefix),
                                   prefix.ljust(64, b"\x00")))
    out.append(struct.pack("<I", len(packets)))
    for rec, now in packets:
        out.append(struct.pack(
            "<16s16sHHBBBBBBH64sI", rec.src_addr, rec.dst_addr, rec.src_port,
            rec.dst_port, rec.proto, rec.tcp_flags, int(rec.is_ipv6),
            rec.direction, int(rec.has_l7_tokens),
            rec.http_method, len(rec.http_path),
            rec.http_path.ljust(64, b"\x00"), now))
    with open(path, "wb") as f:
        f.write(b"".join(out))


def run_goldengen(scenario_path: str, out_path: str) -> np.ndarray:
    """Run the C++ generator → structured array of expected verdicts."""
    if not os.path.exists(GOLDENGEN_PATH):
        raise FileNotFoundError(
            f"{GOLDENGEN_PATH} not built — run `make -C cilium_tpu/shim`")
    subprocess.run([GOLDENGEN_PATH, scenario_path, out_path], check=True)
    raw = np.fromfile(out_path, dtype=np.uint8).reshape(-1, 8)
    return np.rec.fromarrays(
        [raw[:, 0], raw[:, 1], raw[:, 2],
         raw[:, 4:8].copy().view("<u4").reshape(-1)],
        names="allow,reason,status,remote")
