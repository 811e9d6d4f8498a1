"""nicgen against a bare ``FlowShim`` (no engine): a Python thread plays
the shim's side of the rings, so the schedule, the loss rule and the
lateness arithmetic can be checked frame by frame."""

import threading
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.frames import PROTO_TCP, frames_of, v4_words
from benchmarks.nic import nicgen

EP_V4 = 0xC0A8000A


def some_flows(n):
    return {"src": v4_words((0xAC100000 + np.arange(n)).astype(np.uint32)),
            "sport": np.full((n,), 30000, np.int32),
            "dport": (1024 + np.arange(n) % 100).astype(np.int32),
            "proto": np.full((n,), PROTO_TCP, np.int32),
            "is_v6": np.zeros((n,), bool)}


class Consumer:
    """The shim's side: harvest, then pass even source addresses and drop
    odd ones, in order. ``hold`` keeps it from polling."""

    def __init__(self, shim):
        self.shim, self.hold = shim, threading.Event()
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            if self.hold.is_set():
                time.sleep(0.001)
                continue
            now = int(time.monotonic() * 1e6)
            self.shim.afxdp_poll(256, now_us=now)
            b = self.shim.poll_batch(now_us=now, force=True)
            if b is None:
                time.sleep(0.0002)
                continue
            n = self.shim._pending_counts[0]
            self.shim.apply_verdicts(b["src"][:n, 3] % 2 == 0)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(10)
        assert not self._t.is_alive()


@pytest.fixture
def shim():
    from cilium_tpu.shim.bindings import FlowShim
    s = FlowShim()
    s.register_endpoint("192.168.0.10", 1)
    s.mock_rings_init(ring_size=256, frame_size=2048, n_frames=256)
    yield s
    s.close()


def test_struct_matches_the_library():
    lib = nicgen.build()
    assert lib.nicgen_sizeof_run() > 0     # build() already compared them


def test_open_loop_follows_the_schedule(shim):
    lib = nicgen.build()
    flows = some_flows(64)
    table, lens = frames_of(flows, EP_V4, (0, 0, 0, 0))
    n = 2000
    sched = (np.arange(n) % 64).astype(np.uint32)
    base = shim.stats()
    t0 = time.monotonic() + 0.5
    due = t0 + np.arange(n) / 4000.0                 # 4,000 frames/s
    with Consumer(shim):
        log = nicgen.Nic(lib, shim, table, lens, sched, due,
                         t_stop_s=due[-1] + 0.05).start().join(30)
    assert log["drained"] and not log["log_overflow"]
    assert log["n_offered"] == log["n_accepted"] == n
    assert log["n_refused"] == 0
    inj = log["inject_t"]
    late = inj - due
    assert (late >= 0).all()                          # never before it is due
    assert np.percentile(late, 99) < 0.005            # and not long after
    assert (np.diff(inj) >= 0).all()                  # in schedule order
    vt = harness.verdict_times(log)
    assert np.isfinite(vt).all() and (np.diff(vt) >= 0).all()
    assert (vt >= inj).all()                          # verdict after entry
    # counters: even sources passed, odd dropped, all taken off the tx ring
    assert log["log_passes"][-1] - base["verdict_passes"] == n // 2
    assert log["log_drops"][-1] - base["verdict_drops"] == n // 2
    assert log["n_tx_drained"] == n // 2
    assert log["log_stable"].any()
    assert harness.verdicts_by(log, vt[-1] + 1.0) == n
    assert harness.verdicts_by(log, t0 - 1.0) == 0


def test_open_loop_loses_what_the_ring_refuses(shim):
    lib = nicgen.build()
    flows = some_flows(8)
    table, lens = frames_of(flows, EP_V4, (0, 0, 0, 0))
    n = 600                                           # ring holds 256
    sched = np.zeros((n,), np.uint32)
    t0 = time.monotonic() + 0.5
    due = t0 + np.arange(n) * 1e-5
    with Consumer(shim) as c:
        c.hold.set()                                  # nobody polls the ring
        nic = nicgen.Nic(lib, shim, table, lens, sched, due,
                         t_stop_s=due[-1] + 0.05, drain_s=5.0).start()
        time.sleep(max(0.0, due[-1] + 0.2 - time.monotonic()))
        c.hold.clear()
        log = nic.join(30)
    assert log["n_offered"] == n
    assert log["n_accepted"] == 256 and log["n_refused"] == n - 256
    inj = log["inject_t"]
    assert (inj[:256] >= 0).all() and (inj[256:] == -1).all()
    assert log["drained"]                             # the 256 got verdicts
    assert harness.verdict_times(log).shape == (256,)


def test_late_frames_are_offered_and_timed_from_due(shim):
    """A frame the loop comes to after its due time (its thread was off
    the processor) is offered then, never left out: the lateness shows in
    inject_t - due, and the frame's latency is counted from when it was
    due, so it holds the stall."""
    lib = nicgen.build()
    table, lens = frames_of(some_flows(8), EP_V4, (0, 0, 0, 0))
    n = 400
    sched = np.zeros((n,), np.uint32)
    t0 = time.monotonic() + 0.5
    due = t0 + np.arange(n) / 4000.0
    due[100:200] = due[100] - 0.2             # the generator "stalled"
    with Consumer(shim):
        log = nicgen.Nic(lib, shim, table, lens, sched, due,
                         t_stop_s=due[-1] + 0.05).start().join(30)
    inj = log["inject_t"]
    assert log["n_offered"] == log["n_accepted"] == n
    assert log["n_refused"] == 0 and log["drained"]
    assert (inj >= 0).all()
    assert (inj[100:200] - due[100:200] > 0.19).all()
    assert (harness.verdict_times(log)[100:200] - due[100:200] > 0.19).all()


def test_saturate_loses_nothing(shim):
    lib = nicgen.build()
    flows = some_flows(16)
    table, lens = frames_of(flows, EP_V4, (0, 0, 0, 0))
    sched = (np.arange(4000000) % 16).astype(np.uint32)
    with Consumer(shim):
        log = nicgen.Nic(lib, shim, table, lens, sched, None,
                         t_stop_s=time.monotonic() + 0.5).start().join(30)
    assert log["drained"] and log["n_refused"] == 0
    assert 256 < log["n_accepted"] == log["n_offered"] < sched.size
    assert (log["inject_t"] >= 0).all()
    assert harness.verdicts_by(log, time.monotonic()) == log["n_accepted"]


def test_schedule_is_validated(shim):
    lib = nicgen.build()
    table, lens = frames_of(some_flows(4), EP_V4, (0, 0, 0, 0))
    with pytest.raises(ValueError):
        nicgen.Nic(lib, shim, table, lens, np.array([7], np.uint32), None,
                   t_stop_s=0.0)
