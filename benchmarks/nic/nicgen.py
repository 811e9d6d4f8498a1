"""Python side of nicgen: build it, hand it a schedule, read its logs.

The native loop (nicgen.cc) runs inside one blocking ctypes call made from
a helper thread. ctypes releases the interpreter lock for the call, so the
loop never competes with the feeder and the pipeline worker for it.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
BUILD_DIR = os.path.join(BENCH_DIR, ".build")
LIB = os.path.join(BUILD_DIR, "libnicgen.so")
SRC = os.path.join(HERE, "nicgen.cc")
SHIM_DIR = os.path.join(REPO, "cilium_tpu", "shim")

_P = ctypes.c_void_p


class NicgenRun(ctypes.Structure):
    """Mirror of ``struct NicgenRun`` in nicgen.cc (same order, same
    widths; ``build`` checks the size against the library's own)."""
    _fields_ = [
        ("shim", _P), ("inject", _P), ("tx_drain", _P), ("get_stats", _P),
        ("flow_frames", _P), ("flow_len", _P),
        ("frame_stride", ctypes.c_uint32), ("n_flows", ctypes.c_uint32),
        ("sched_flow", _P), ("due_s", _P), ("n_sched", ctypes.c_uint64),
        ("t_stop_s", ctypes.c_double), ("drain_deadline_s", ctypes.c_double),
        ("stop_cap_s", ctypes.c_double), ("ring_frames", ctypes.c_uint64),
        ("inject_t", _P),
        ("log_t", _P), ("log_drops", _P), ("log_passes", _P),
        ("log_txfull", _P), ("log_stable", _P),
        ("log_cap", ctypes.c_uint64), ("n_log", ctypes.c_uint64),
        ("n_offered", ctypes.c_uint64), ("n_accepted", ctypes.c_uint64),
        ("n_refused", ctypes.c_uint64),
        ("n_refused_in_stop", ctypes.c_uint64),
        ("n_refused_on_time", ctypes.c_uint64),
        ("n_refused_aftermath", ctypes.c_uint64),
        ("n_stop_episodes", ctypes.c_uint64),
        ("stop_s", ctypes.c_double),
        ("n_tx_drained", ctypes.c_uint64),
        ("n_samples", ctypes.c_uint64),
        ("n_gaps_over_50us", ctypes.c_uint64),
        ("max_gap_s", ctypes.c_double),
        ("base_verdicts", ctypes.c_uint64),
        ("log_overflow", ctypes.c_uint32), ("drained", ctypes.c_uint32),
        ("stop", ctypes.c_uint32),
        ("n_stalls", ctypes.c_uint32),
        ("stall_t", ctypes.c_double * 64),
        ("stall_inject_s", ctypes.c_double * 64),
        ("stall_drain_s", ctypes.c_double * 64),
        ("stall_stats_s", ctypes.c_double * 64),
    ]


def build() -> ctypes.CDLL:
    """Compile nicgen.cc (with the compiler and flags the shim's Makefile
    uses) when the library is missing or older than its source, and load
    it."""
    os.makedirs(BUILD_DIR, exist_ok=True)
    deps = [SRC, os.path.join(SHIM_DIR, "flowshim.h")]
    if not os.path.exists(LIB) or any(
            os.path.getmtime(LIB) < os.path.getmtime(d) for d in deps):
        cxx = os.environ.get("CXX", "g++")
        tmp = LIB + f".tmp{os.getpid()}"
        subprocess.run(
            [cxx, "-O2", "-Wall", "-Wextra", "-std=c++17", "-fPIC",
             "-shared", "-I", SHIM_DIR, "-o", tmp, SRC], check=True)
        os.replace(tmp, LIB)
    lib = ctypes.CDLL(LIB)
    lib.nicgen_run.restype = ctypes.c_int
    lib.nicgen_run.argtypes = [ctypes.POINTER(NicgenRun)]
    lib.nicgen_sizeof_run.restype = ctypes.c_uint32
    if lib.nicgen_sizeof_run() != ctypes.sizeof(NicgenRun):
        raise RuntimeError(
            f"NicgenRun is {ctypes.sizeof(NicgenRun)} bytes here and "
            f"{lib.nicgen_sizeof_run()} in {LIB}: the two declarations "
            "have drifted apart")
    return lib


def build_shim() -> None:
    """libflowshim.so is a build product git does not carry."""
    subprocess.run(["make", "-C", SHIM_DIR, "libflowshim.so"], check=True,
                   stdout=subprocess.DEVNULL)


def _addr(a: np.ndarray) -> int:
    return a.ctypes.data


class Nic:
    """One run of the native loop against one ``FlowShim``.

    ``flow_frames`` [n_flows, stride] uint8 and ``flow_len`` [n_flows]
    uint16 hold one frame per flow; ``sched_flow`` [n] uint32 says which
    flow each schedule entry sends; ``due_s`` [n] float64 (absolute
    ``time.monotonic()`` seconds) makes the loop open, ``None`` closes it
    on ring space. An open loop also says how many frames the rings hold
    in flight (``ring_frames``) and how long after it was last late a stop
    episode may last (``stop_cap_s``; nicgen.cc says what an episode is). Every array stays referenced here until ``join``."""

    def __init__(self, lib: ctypes.CDLL, shim, flow_frames: np.ndarray,
                 flow_len: np.ndarray, sched_flow: np.ndarray,
                 due_s: Optional[np.ndarray], t_stop_s: float,
                 drain_s: float = 20.0, log_cap: int = 1 << 20,
                 ring_frames: int = 0, stop_cap_s: float = 0.0):
        if flow_frames.dtype != np.uint8 or flow_frames.ndim != 2 \
                or not flow_frames.flags.c_contiguous:
            raise ValueError("flow_frames must be C-contiguous uint8 [n, s]")
        n_flows, stride = flow_frames.shape
        flow_len = np.ascontiguousarray(flow_len, dtype=np.uint16)
        sched_flow = np.ascontiguousarray(sched_flow, dtype=np.uint32)
        if flow_len.shape != (n_flows,):
            raise ValueError("flow_len must have one entry per flow")
        if int(flow_len.max(initial=0)) > stride:
            raise ValueError("a frame is longer than the table's stride")
        if sched_flow.size and int(sched_flow.max()) >= n_flows:
            raise ValueError("schedule names a flow the table lacks")
        if due_s is not None:
            due_s = np.ascontiguousarray(due_s, dtype=np.float64)
            if due_s.shape != sched_flow.shape:
                raise ValueError("due_s must have one entry per frame")
            if ring_frames <= 0 or not stop_cap_s > 0:
                raise ValueError("an open loop needs ring_frames and "
                                 "stop_cap_s")
        self._lib = lib
        self._keep = (flow_frames, flow_len, sched_flow, due_s, shim)
        n = sched_flow.size
        # every page the loop will write is touched here, not under it: a
        # first-touch page fault inside the loop is a stall of the
        # generator (np.zeros alone maps pages lazily)
        self.inject_t = np.full((n,), np.nan, dtype=np.float64)
        self.log_t = np.full((log_cap,), 0.0, dtype=np.float64)
        self.log_drops = np.full((log_cap,), 0, dtype=np.uint64)
        self.log_passes = np.full((log_cap,), 0, dtype=np.uint64)
        self.log_txfull = np.full((log_cap,), 0, dtype=np.uint64)
        self.log_stable = np.full((log_cap,), 0, dtype=np.uint8)
        fn = ctypes.cast
        shim_lib = shim._lib
        r = self.run = NicgenRun()
        r.shim = shim._handle
        r.inject = fn(shim_lib.shim_mock_rx_inject, _P).value
        r.tx_drain = fn(shim_lib.shim_mock_tx_drain, _P).value
        r.get_stats = fn(shim_lib.shim_get_stats, _P).value
        r.flow_frames = _addr(flow_frames)
        r.flow_len = _addr(flow_len)
        r.frame_stride = stride
        r.n_flows = n_flows
        r.sched_flow = _addr(sched_flow)
        r.due_s = _addr(due_s) if due_s is not None else None
        r.n_sched = n
        r.t_stop_s = t_stop_s
        r.drain_deadline_s = t_stop_s + drain_s
        r.stop_cap_s = stop_cap_s
        r.ring_frames = ring_frames
        r.inject_t = _addr(self.inject_t)
        r.log_t = _addr(self.log_t)
        r.log_drops = _addr(self.log_drops)
        r.log_passes = _addr(self.log_passes)
        r.log_txfull = _addr(self.log_txfull)
        r.log_stable = _addr(self.log_stable)
        r.log_cap = log_cap
        self.rc: Optional[int] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Nic":
        def call():
            self.rc = self._lib.nicgen_run(ctypes.byref(self.run))
        self._thread = threading.Thread(target=call, name="nicgen",
                                        daemon=True)
        self._thread.start()
        return self

    def abandon(self) -> None:
        self.run.stop = 1

    def join(self, timeout: float) -> Dict:
        """Wait for the loop to end; returns its logs, cut to length."""
        t = self._thread
        if t is None:
            raise RuntimeError("join before start")
        t.join(timeout)
        if t.is_alive():
            self.abandon()
            t.join(10.0)
            raise RuntimeError("nicgen did not return")
        if self.rc != 0:
            raise RuntimeError(f"nicgen_run returned {self.rc}")
        r = self.run
        n = int(r.n_log)
        return {
            "inject_t": self.inject_t[:int(r.n_offered)],
            "log_t": self.log_t[:n],
            "log_drops": self.log_drops[:n].astype(np.int64),
            "log_passes": self.log_passes[:n].astype(np.int64),
            "log_txfull": self.log_txfull[:n].astype(np.int64),
            "log_stable": self.log_stable[:n].astype(bool),
            "base_verdicts": int(r.base_verdicts),
            "n_offered": int(r.n_offered), "n_accepted": int(r.n_accepted),
            "n_refused": int(r.n_refused),
            "n_refused_in_stop": int(r.n_refused_in_stop),
            "n_refused_on_time": int(r.n_refused_on_time),
            "n_refused_aftermath": int(r.n_refused_aftermath),
            "n_stop_episodes": int(r.n_stop_episodes),
            "stop_s": float(r.stop_s),
            "n_tx_drained": int(r.n_tx_drained),
            "n_samples": int(r.n_samples),
            "n_gaps_over_50us": int(r.n_gaps_over_50us),
            "max_gap_s": float(r.max_gap_s),
            "log_overflow": bool(r.log_overflow),
            "drained": bool(r.drained),
            "n_stalls": int(r.n_stalls),
            "stalls": [
                {"t": r.stall_t[i], "inject_s": r.stall_inject_s[i],
                 "drain_s": r.stall_drain_s[i], "stats_s": r.stall_stats_s[i]}
                for i in range(min(int(r.n_stalls), 64))],
        }
