"""nicgen against a bare ``FlowShim`` (no engine): a Python thread plays
the shim's side of the rings, so the schedule, the loss rule, whose loss
each refusal is and the lateness arithmetic can be checked frame by
frame."""

import re
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


RING = 256
ON_TIME, IN_STOP = -1.0, -2.0         # inject_t of a refused frame


class Consumer:
    """The shim's side: harvest, then pass even source addresses and drop
    odd ones, in order. ``hold`` keeps it from polling; ``slow`` makes it
    take 16 frames every 10 ms (1,600 frames/s at most)."""

    def __init__(self, shim):
        self.shim, self.hold = shim, threading.Event()
        self.slow = threading.Event()
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            if self.hold.is_set():
                time.sleep(0.001)
                continue
            slow = self.slow.is_set()
            now = int(time.monotonic() * 1e6)
            self.shim.afxdp_poll(16 if slow else 256, now_us=now)
            b = self.shim.poll_batch(now_us=now, force=True)
            if b is None:
                time.sleep(0.0002)
                continue
            n = self.shim._pending_counts[0]
            self.shim.apply_verdicts(b["src"][:n, 3] % 2 == 0)
            if slow:
                time.sleep(0.010)

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
    s.mock_rings_init(ring_size=RING, frame_size=2048, n_frames=RING)
    yield s
    s.close()


def open_nic(lib, shim, table, lens, sched, due, cap_s=10.0, **kw):
    """An open loop on the test ring; a stop episode ends at half the ring
    in flight unless the test asks for a cap that comes first."""
    return nicgen.Nic(lib, shim, table, lens, sched, due, ring_frames=RING,
                      stop_cap_s=cap_s, **kw)


def on_a_steady_generator(scenario, tries=12):
    """``scenario() -> (log, spans)`` again while the loop's own thread
    lost over 1 ms inside one of ``spans`` (pairs of monotonic seconds):
    this machine stopping the generator there makes frames late that the
    scenario sent on time, and voids the trial. The loop keeps the times of
    its first 64 stalls."""
    for _ in range(tries):
        log, spans = scenario()
        if log["n_stalls"] <= 64 and not any(
                a - 0.004 <= st["t"] <= b + 0.004
                for st in log["stalls"] for a, b in spans):
            return log
    pytest.skip(f"the generator's thread was stopped inside the scenario "
                f"in {tries} of {tries} trials on this machine")


def test_struct_matches_the_library():
    lib = nicgen.build()
    assert lib.nicgen_sizeof_run() > 0     # build() already compared them
    # the same fields in the same order: sizes alone would let two swap
    with open(nicgen.SRC) as f:
        body = re.search(r"struct NicgenRun \{(.*?)\n\};", f.read(),
                         re.S).group(1)
    body = re.sub(r"//[^\n]*", "", body)
    names = [re.search(r"(\w+)(\[\d+\])?\s*$", stmt).group(1)
             for stmt in body.split(";") if stmt.strip()]
    assert names == [f[0] for f in nicgen.NicgenRun._fields_]


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
        log = open_nic(lib, shim, table, lens, sched, due,
                       t_stop_s=due[-1] + 0.05).start().join(30)
    assert log["drained"] and not log["log_overflow"]
    assert log["n_offered"] == log["n_accepted"] == n
    assert log["n_refused"] == 0 and log["n_stop_episodes"] == 0
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


def refusal_counts(log):
    return {k: log[k] for k in (
        "n_refused", "n_refused_in_stop", "n_refused_on_time",
        "n_refused_aftermath", "n_stop_episodes")}


def test_open_loop_loses_what_the_ring_refuses(shim):
    """(b) The generator on time, nobody polling the ring: every refusal is
    the program's."""
    lib = nicgen.build()
    flows = some_flows(8)
    table, lens = frames_of(flows, EP_V4, (0, 0, 0, 0))
    n = 600                                           # ring holds 256
    sched = np.zeros((n,), np.uint32)

    def scenario():
        t0 = time.monotonic() + 0.5
        due = t0 + np.arange(n) * 1e-5
        with Consumer(shim) as c:
            c.hold.set()                              # nobody polls the ring
            nic = open_nic(lib, shim, table, lens, sched, due,
                           t_stop_s=due[-1] + 0.05, drain_s=5.0).start()
            time.sleep(max(0.0, due[-1] + 0.2 - time.monotonic()))
            c.hold.clear()
            return nic.join(30), [(due[0], due[-1])]

    log = on_a_steady_generator(scenario)
    assert log["n_offered"] == n and log["n_accepted"] == RING
    assert refusal_counts(log) == {
        "n_refused": n - RING, "n_refused_in_stop": 0,
        "n_refused_on_time": n - RING, "n_refused_aftermath": 0,
        "n_stop_episodes": 0}
    inj = log["inject_t"]
    assert (inj[:RING] >= 0).all() and (inj[RING:] == ON_TIME).all()
    assert log["drained"]                             # the 256 got verdicts
    assert harness.verdict_times(log).shape == (RING,)


def test_refusals_of_late_frames_are_the_hosts(shim):
    """(a) Every frame was due 50 ms before the loop starts (a stop of the
    generator's thread, as `test_late_frames_are_offered_and_timed_from_due`
    plays one) and nobody polls the ring: 256 go in, the rest are refused in
    the stop, and none is the program's."""
    lib = nicgen.build()
    table, lens = frames_of(some_flows(8), EP_V4, (0, 0, 0, 0))
    n = 600
    sched = np.zeros((n,), np.uint32)
    with Consumer(shim) as c:
        c.hold.set()
        due = np.full((n,), time.monotonic() - 0.05)
        nic = open_nic(lib, shim, table, lens, sched, due,
                       t_stop_s=time.monotonic() + 0.3, drain_s=5.0).start()
        time.sleep(0.2)
        c.hold.clear()
        log = nic.join(30)
    assert log["n_offered"] == n and log["n_accepted"] == RING
    assert refusal_counts(log) == {
        "n_refused": n - RING, "n_refused_in_stop": n - RING,
        "n_refused_on_time": 0, "n_refused_aftermath": 0,
        "n_stop_episodes": 1}
    inj = log["inject_t"]
    assert (inj[:RING] >= 0).all() and (inj[RING:] == IN_STOP).all()
    assert log["drained"]


LEAD_S = 0.1      # for the loop's thread to start, so that on-time is on time


def late_burst_then(groups, rate=4000.0, n_late=400):
    """A schedule: ``n_late`` frames due 50 ms before it is made (256 fill
    the ring, the rest are refused late), then for each ``(offset_s, n)``
    of ``groups`` ``n`` frames on time at ``rate``, from ``LEAD_S +
    offset_s`` after it is made. → a function of the time it is made
    giving due times, and each group's slice."""
    sizes = [n_late] + [n for _o, n in groups]
    edges = np.cumsum([0] + sizes)
    parts = [slice(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]

    def due(now):
        d = np.empty((int(edges[-1]),))
        d[parts[0]] = now - 0.05
        for (offset, n), part in zip(groups, parts[1:]):
            d[part] = now + LEAD_S + offset + np.arange(n) / rate
        return d
    return due, parts


def test_the_aftermath_of_a_stop_ends_at_half_the_ring(shim):
    """(c) A late burst fills the ring. While the consumer takes 1,600
    frames/s of 4,000 offered, the ring stays over half full and what it
    refuses of the on-time frames still counts in the stop. Then the
    consumer empties the ring, which ends the episode; held again, it lets
    the ring fill, and those refusals are the program's."""
    lib = nicgen.build()
    table, lens = frames_of(some_flows(8), EP_V4, (0, 0, 0, 0))
    # 0.06 s of on-time frames against the slow consumer; a pause in which
    # the ring empties; 0.1 s against a held one
    due_of, (burst, slow, held) = late_burst_then([(0.0, 240), (0.3, 400)])
    sched = np.zeros((held.stop,), np.uint32)

    def scenario():
        with Consumer(shim) as c:
            c.hold.set()
            due = due_of(time.monotonic())
            nic = open_nic(lib, shim, table, lens, sched, due,
                           t_stop_s=due[-1] + 0.05, drain_s=5.0).start()
            c.slow.set()
            time.sleep(max(0.0, due[slow][0] - 0.01 - time.monotonic()))
            c.hold.clear()                    # slow, through the first group
            time.sleep(max(0.0, due[slow][-1] + 0.02 - time.monotonic()))
            c.slow.clear()                    # the ring empties
            time.sleep(max(0.0, due[held][0] - 0.1 - time.monotonic()))
            c.hold.set()                      # the server alone stands
            time.sleep(max(0.0, due[-1] + 0.1 - time.monotonic()))
            c.hold.clear()
            return nic.join(30), [(due[slow][0], due[slow][-1]),
                                  (due[held][0], due[-1])]

    log = on_a_steady_generator(scenario)
    inj = log["inject_t"]
    assert log["n_offered"] == held.stop
    assert (inj[burst][:RING] >= 0).all()
    assert (inj[burst][RING:] == IN_STOP).all()       # 144 late refusals
    # the ring never fell to half: accepted and refused frames alternate,
    # and every refusal is still the stop's
    assert (inj[slow] >= 0).sum() > 30
    assert (inj[slow] == IN_STOP).sum() > 30
    assert not (inj[slow] == ON_TIME).any()
    # the second hold met an empty ring and a closed episode
    assert (inj[held][:RING] >= 0).all()
    assert (inj[held][RING:] == ON_TIME).all()
    aftermath = int((inj[slow] == IN_STOP).sum())
    assert refusal_counts(log) == {
        "n_refused": 144 + aftermath + 400 - RING,
        "n_refused_in_stop": 144 + aftermath,
        "n_refused_on_time": 400 - RING,
        "n_refused_aftermath": aftermath, "n_stop_episodes": 1}
    assert log["drained"]


def test_a_stop_episode_ends_at_its_cap(shim):
    """(d) The consumer never drains after a late burst: an episode capped
    at 0.2 s holds the refusals of its 0.2 s (fewer than the 256 late
    frames the ring took), and the rest are the program's."""
    lib = nicgen.build()
    table, lens = frames_of(some_flows(8), EP_V4, (0, 0, 0, 0))
    cap = 0.2
    due_of, (burst, after) = late_burst_then([(0.0, 400)], rate=1000.0)
    sched = np.zeros((after.stop,), np.uint32)

    def scenario():
        with Consumer(shim) as c:
            c.hold.set()
            due = due_of(time.monotonic())
            nic = open_nic(lib, shim, table, lens, sched, due, cap_s=cap,
                           t_stop_s=due[-1] + 0.05, drain_s=5.0).start()
            time.sleep(max(0.0, due[-1] + 0.1 - time.monotonic()))
            c.hold.clear()
            return dict(nic.join(30), due=due), [(due[0], due[-1])]

    log = on_a_steady_generator(scenario)
    inj, due = log["inject_t"], log["due"]
    assert (inj[burst][:RING] >= 0).all()
    assert (inj[burst][RING:] == IN_STOP).all()
    assert (inj[after] < 0).all()                     # the ring stayed full
    # the last late refusal came within the loop's first iterations
    end = inj[RING - 1] + cap
    d = due[after]
    assert (inj[after][d < end - 0.005] == IN_STOP).all()
    assert (inj[after][d > end + 0.005] == ON_TIME).all()
    in_stop = int((inj[after] == IN_STOP).sum())
    assert 60 < in_stop < 200             # the cap's last 0.1 s at 1,000/s
    assert refusal_counts(log) == {
        "n_refused": 144 + 400, "n_refused_in_stop": 144 + in_stop,
        "n_refused_on_time": 400 - in_stop,
        "n_refused_aftermath": in_stop, "n_stop_episodes": 1}


def test_the_aftermath_forgives_no_more_than_the_ring_took_late(shim):
    """A late burst of 300: the ring takes 256 of them and refuses 44. The
    consumer never drains and the cap is far off, so the episode stays
    open; of the on-time frames refused after it the first 256 are set
    against the 256 late frames that sit in the ring, and the rest are the
    program's."""
    lib = nicgen.build()
    table, lens = frames_of(some_flows(8), EP_V4, (0, 0, 0, 0))
    due_of, (burst, after) = late_burst_then([(0.0, 800)], n_late=300)
    sched = np.zeros((after.stop,), np.uint32)

    def scenario():
        with Consumer(shim) as c:
            c.hold.set()
            due = due_of(time.monotonic())
            nic = open_nic(lib, shim, table, lens, sched, due,
                           t_stop_s=due[-1] + 0.05, drain_s=5.0).start()
            time.sleep(max(0.0, due[-1] + 0.1 - time.monotonic()))
            c.hold.clear()
            return nic.join(30), [(due[0], due[-1])]

    log = on_a_steady_generator(scenario)
    inj = log["inject_t"]
    assert (inj[burst][:RING] >= 0).all()
    assert (inj[burst][RING:] == IN_STOP).all()
    assert (inj[after][:RING] == IN_STOP).all()
    assert (inj[after][RING:] == ON_TIME).all()
    assert refusal_counts(log) == {
        "n_refused": 44 + 800, "n_refused_in_stop": 44 + RING,
        "n_refused_on_time": 800 - RING, "n_refused_aftermath": RING,
        "n_stop_episodes": 1}


def test_a_burst_the_ring_just_holds_still_opens_an_episode(shim):
    """A stop whose burst the ring can just hold refuses no late frame, and
    the on-time frames right behind it find the ring full: that is the
    stop's aftermath all the same (two of twelve 40 s runs on the chip lost
    5 and 68 frames so; PERF.md, PR 27). A stop the ring was already full
    for forgives its own late frames and nothing after them."""
    lib = nicgen.build()
    table, lens = frames_of(some_flows(8), EP_V4, (0, 0, 0, 0))
    n = 700
    sched = np.zeros((n,), np.uint32)

    def scenario():
        now = time.monotonic()
        due = np.empty((n,))
        due[:RING] = now - 0.05               # the burst: just the ring
        due[RING:400] = now + LEAD_S + np.arange(144) * 1e-5
        # long after the cap, the ring still full
        due[400:] = now + LEAD_S + 0.5 + np.arange(300) * 1e-5
        due[500:600] -= 0.05                  # a stop on a ring long full
        with Consumer(shim) as c:
            c.hold.set()
            nic = open_nic(lib, shim, table, lens, sched, due, cap_s=0.3,
                           t_stop_s=due[-1] + 0.05, drain_s=5.0).start()
            time.sleep(max(0.0, due[-1] + 0.2 - time.monotonic()))
            c.hold.clear()
            return nic.join(30), [(due[RING], due[399]), (due[400], due[-1])]

    log = on_a_steady_generator(scenario)
    inj = log["inject_t"]
    assert log["n_accepted"] == RING and (inj[:RING] >= 0).all()
    assert (inj[RING:400] == IN_STOP).all()   # behind the burst
    assert (inj[400:500] == ON_TIME).all()    # the episode is over
    # the second stop put no frame into the ring: its own 100 are the
    # host's, and not one frame after them
    assert (inj[500:600] == IN_STOP).all()
    assert (inj[600:] == ON_TIME).all()
    assert refusal_counts(log) == {
        "n_refused": n - RING, "n_refused_in_stop": 144 + 100,
        "n_refused_on_time": 200, "n_refused_aftermath": 144,
        "n_stop_episodes": 2}


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
        log = open_nic(lib, shim, table, lens, sched, due,
                       t_stop_s=due[-1] + 0.05).start().join(30)
    inj = log["inject_t"]
    assert log["n_offered"] == log["n_accepted"] == n
    assert log["n_refused"] == 0 and log["drained"]
    assert log["n_stop_episodes"] == 0        # late, but nothing refused
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
