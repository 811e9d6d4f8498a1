"""Mesh self-healing tests (ISSUE 19): device-loss detection, the fenced
re-mesh onto survivors, CT salvage (device gather → archive floor → cold)
with the bounded established-fingerprint grace window, and hysteretic
re-admission (the whole loss → degraded → heal cycles are the ``slow``
cases, run by ``make chaos``).

Layers covered here:

- the dead-device classifier (``runtime/datapath.dead_device_of``): real
  runtime signatures vs transient dispatch errors, ordinal attribution;
- the shared established-fingerprint filter (``shim/feeder``): stamp /
  lookup discipline both consumers (feeder priority classing, the engine
  grace window) rely on;
- the CT archive helpers (``runtime/checkpoint``): atomic timestamped
  writes, retention pruning, age accounting, corrupt-file fail-closed;
- the engine protocol (``Engine.remesh_step`` / ``_remesh_to`` over
  ``Pipeline.remesh`` + ``JITDatapath.remesh``): loss → park → fenced
  shrink (wedged window rejected, queued submissions survive) → degraded
  serving → probe-canary heal with hysteresis, plus every operator
  surface the cycle feeds (health detail, mesh_width ledger row,
  counters, flight-recorder freeze kinds);
- the ct-snapshot controller tick: archive flow, CHECKPOINT_STALE
  folding, the ``device.collective`` chaos point, and the archive as the
  re-mesh's salvage floor when the device gather dies.
"""

import os
import time
import zipfile

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.pipeline.guard import DeviceLost, PipelineError
from cilium_tpu.runtime import checkpoint as ckpt
from cilium_tpu.runtime.datapath import dead_device_of
from cilium_tpu.runtime.faults import FAULTS, FaultInjected
from cilium_tpu.shim.feeder import EstablishedFingerprints
from cilium_tpu.utils import constants as C
from tests.test_datapath import pkt
from tests.test_sharded_pipeline import jit_pipeline_engine


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def _mk(slot_of, n, start, dst_octet=2):
    recs = [pkt("192.168.1.10", f"10.0.{dst_octet}.{(i % 200) + 1}",
                52000 + start + i, 443) for i in range(n)]
    return batch_from_records(recs, slot_of)


def _replies(slot_of, n, start, dst_octet=2):
    recs = [pkt(f"10.0.{dst_octet}.{(i % 200) + 1}", "192.168.1.10",
                443, 52000 + start + i, flags=C.TCP_ACK,
                direction=C.DIR_INGRESS) for i in range(n)]
    return batch_from_records(recs, slot_of)


# --------------------------------------------------------------------------- #
# dead-device classifier
# --------------------------------------------------------------------------- #
class TestDeadDeviceClassifier:
    def test_attributed_signature(self):
        e = RuntimeError("DEVICE_UNAVAILABLE: chip fell off ici dev=3")
        assert dead_device_of(e) == 3

    def test_unattributed_signature(self):
        assert dead_device_of(RuntimeError("hardware failure")) == -1

    def test_drill_signature(self):
        assert dead_device_of(
            FaultInjected("injected fault at device.fail: dev=1")) == 1

    def test_transient_is_none(self):
        assert dead_device_of(ValueError("bad batch geometry")) is None

    def test_mention_of_devices_is_not_a_loss(self):
        # case-sensitive literal tokens only: a user exception that
        # merely talks about devices must stay breaker territory
        assert dead_device_of(
            RuntimeError("all devices are fine, dev=2 ok")) is None


# --------------------------------------------------------------------------- #
# the shared established-fingerprint filter
# --------------------------------------------------------------------------- #
class TestEstablishedFingerprints:
    def _buf(self, n):
        b = {k: np.zeros((n,), np.int32)
             for k in ("sport", "dport", "proto", "direction")}
        b["src"] = np.zeros((n, 4), np.uint32)
        b["dst"] = np.zeros((n, 4), np.uint32)
        b["valid"] = np.ones((n,), bool)
        b["src"][:, 3] = 0xC0A8010A
        b["dst"][:, 3] = 0x0A000200 + np.arange(n)
        b["sport"][:] = 40000 + np.arange(n)
        b["dport"][:] = 443
        b["proto"][:] = 6
        return b

    def test_only_allowed_established_rows_stamp(self):
        fp = EstablishedFingerprints(slots=1 << 12)
        b = self._buf(4)
        out = {"allow": np.array([True, True, False, True]),
               "status": np.array([int(C.CTStatus.ESTABLISHED),
                                   int(C.CTStatus.NEW),
                                   int(C.CTStatus.ESTABLISHED),
                                   int(C.CTStatus.REPLY)], np.int32)}
        fp.note(b, out)
        hits = fp.hits(b)
        # allowed-EST and allowed-REPLY stamp; allowed-NEW and denied-EST
        # do not — the filter only ever vouches for proven flows
        assert hits.tolist() == [True, False, False, True]

    def test_unknown_flow_never_hits(self):
        fp = EstablishedFingerprints(slots=1 << 12)
        assert not fp.hits(self._buf(8)).any()

    def test_note_never_raises(self):
        fp = EstablishedFingerprints(slots=1 << 12)
        fp.note({}, {})                 # missing columns: swallowed

    def test_slots_must_be_pow2(self):
        with pytest.raises(ValueError):
            EstablishedFingerprints(slots=48)


# --------------------------------------------------------------------------- #
# CT archive helpers
# --------------------------------------------------------------------------- #
class TestCTArchive:
    def _arrays(self, cap=64, live=5):
        from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
        a = make_ct_arrays(CTConfig(capacity=cap))
        a["expiry"][:live] = 10_000 + np.arange(live)
        return a

    def test_roundtrip_and_prune(self, tmp_path):
        d = str(tmp_path)
        assert ckpt.newest_ct_archive(d) is None
        assert ckpt.ct_archive_age_s(d) is None
        paths = [ckpt.save_ct_archive(d, self._arrays(live=i + 1), keep=2)
                 for i in range(3)]
        kept = ckpt.list_ct_archives(d)
        assert len(kept) == 2                      # pruned to keep
        assert ckpt.newest_ct_archive(d) == paths[-1]
        got = ckpt.load_ct_archive(paths[-1])
        assert got is not None
        assert int((got["expiry"] > 0).sum()) == 3
        assert "__ct_format__" not in got          # normalized out
        assert ckpt.ct_archive_age_s(d) >= 0.0

    def test_corrupt_archive_loads_as_none(self, tmp_path):
        d = str(tmp_path)
        p = ckpt.save_ct_archive(d, self._arrays(), keep=2)
        with open(p, "wb") as f:
            f.write(b"not a zip at all")
        assert ckpt.load_ct_archive(p) is None
        # a valid zip that is not a CT checkpoint also fails closed
        with zipfile.ZipFile(p, "w") as z:
            z.writestr("garbage.npy", b"xx")
        assert ckpt.load_ct_archive(p) is None


# --------------------------------------------------------------------------- #
# the engine protocol: loss -> fenced shrink -> degraded -> heal
# --------------------------------------------------------------------------- #
class TestEngineRemesh:
    @pytest.mark.parametrize("n", [2, pytest.param(4, marks=pytest.mark.slow)])
    def test_loss_remesh_degraded_then_heal(self, n):
        """A device dies under traffic: exactly one re-mesh onto the
        survivors, the flows established before the loss keep their reply
        verdicts through it (salvaged CT, or the grace window for the lost
        shard's), exactly one re-mesh back at heal, full width again."""
        eng = jit_pipeline_engine(n, remesh_heal_hysteresis_s=0.0)
        slot_of = eng.active.snapshot.ep_slot_of

        def replies_allowed():
            t = eng.submit(_replies(slot_of, 32, 0))
            assert eng.drain(timeout=30)
            return int(np.asarray(t.result(5)["allow"]).sum())

        try:
            t = eng.submit(_mk(slot_of, 32, 0))
            assert eng.drain(timeout=30)
            assert int(np.asarray(t.result(5)["allow"]).sum()) == 32
            # replies ride CT and stamp the established-fingerprint filter
            assert replies_allowed() == 32
            rev0 = eng.active.revision

            FAULTS.arm("device.fail", mode="fail", message="dev=1")
            trip = eng.submit(_mk(slot_of, 16, 1000))
            deadline = time.monotonic() + 30
            while (eng.pipeline_stats() or {}).get("state") \
                    != "device-lost" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert eng.pipeline_stats()["state"] == "device-lost"
            # queued while parked: must survive the fenced re-mesh
            queued = eng.submit(_mk(slot_of, 8, 2000))

            doc = eng.remesh_step()
            assert doc["remesh"]["from"] == n
            assert doc["remesh"]["to"] == n - 1
            assert doc["remesh"]["reason"] == "device-loss"
            assert eng.drain(timeout=30)
            # the wedged in-flight window is rejected attributably...
            with pytest.raises(PipelineError):
                trip.result(timeout=5)
            # ...but the queued submission rode through onto survivors
            assert int(np.asarray(queued.result(5)["allow"]).sum()) == 8
            # the steering fence: a NEW revision (stale pre-binned
            # ``_shard`` stamps hashed mod the old width must not be
            # trusted against the 3-wide mesh)
            assert eng.active.revision > rev0

            # operator surfaces while degraded
            h = eng.health()
            assert h["state"] == C.HEALTH_DEGRADED
            assert h["devices"]["detail"] == C.DEVICE_LOST
            assert h["devices"]["dead"] == [1]
            width = eng._res_datapath()["mesh_width"]
            assert width[0] == n and width[1] == n - 1
            assert width[2] == pytest.approx(1 / n)
            mh = eng.datapath.mesh_health()
            assert mh["live_ordinals"] == [0, 2, 3][:n - 1]
            assert mh["devices"][1]["state"] == "dead"
            # degraded serving with the fault STILL armed (the dead
            # chip cannot hurt a mesh it is no longer part of)
            t2 = eng.submit(_mk(slot_of, 16, 3000))
            assert eng.drain(timeout=30)
            assert int(np.asarray(t2.result(5)["allow"]).sum()) == 16
            # established before the loss, still answered after it: the
            # survivors' by salvaged CT, the lost shard's by the grace flip
            assert replies_allowed() == 32
            assert eng.metrics.counters.get(
                "ct_salvage_grace_hits_total", 0) > 0

            # heal: disarm = the probe canary passes; hysteresis 0
            FAULTS.disarm("device.fail")
            doc = eng.remesh_step()
            assert doc["remesh"]["from"] == n - 1
            assert doc["remesh"]["to"] == n
            assert doc["remesh"]["reason"] == "heal"
            assert eng.drain(timeout=30)
            t3 = eng.submit(_mk(slot_of, 16, 4000))
            assert eng.drain(timeout=30)
            assert int(np.asarray(t3.result(5)["allow"]).sum()) == 16
            assert replies_allowed() == 32
            assert eng.health()["state"] == C.HEALTH_OK
            assert eng.datapath.mesh_health()["live"] == n

            ctr = eng.metrics.counters
            assert ctr['device_loss_total{device="1"}'] == 1
            assert ctr[f'datapath_remesh_total{{from="{n}",to="{n - 1}"}}'] == 1
            assert ctr[f'datapath_remesh_total{{from="{n - 1}",to="{n}"}}'] == 1
            assert ctr["pipeline_remesh_total"] == 2
            # each re-meshed generation restarted canary-first, and the
            # canary never leaked into submission accounting
            assert ctr.get("pipeline_canary_ok_total", 0) >= 2
            # the flight recorder narrated the loss (first freeze wins:
            # the loss bundle is the root-cause record)
            bb = eng.blackbox.stats()
            assert bb["frozen"]
            assert bb["frozen_reason"].startswith("device-loss")
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_grace_window_covers_lost_shard_then_expires(self):
        eng = jit_pipeline_engine(4, remesh_heal_hysteresis_s=0.0,
                                  remesh_grace_s=60.0)
        slot_of = eng.active.snapshot.ep_slot_of
        n = 64
        try:
            eng.submit(_mk(slot_of, n, 0))
            assert eng.drain(timeout=30)
            # warm pass: replies ride CT (REPLY) and stamp the
            # established-fingerprint filter — BEFORE any loss
            t = eng.submit(_replies(slot_of, n, 0))
            assert eng.drain(timeout=30)
            out = t.result(5)
            assert int(np.asarray(out["allow"]).sum()) == n
            assert (np.asarray(out["status"])[:n]
                    == int(C.CTStatus.REPLY)).all()

            FAULTS.arm("device.fail", mode="fail", message="dev=1")
            try:
                eng.submit(_mk(slot_of, 4, 9000)).result(timeout=30)
            except PipelineError:
                pass                       # the tripping window
            deadline = time.monotonic() + 30
            while (eng.pipeline_stats() or {}).get("state") \
                    != "device-lost" and time.monotonic() < deadline:
                time.sleep(0.02)
            doc = eng.remesh_step()
            assert doc["remesh"]["to"] == 3
            lost = doc["remesh"]["ct_lost"]
            assert lost > 0                # the dropped shard held flows
            assert eng.drain(timeout=30)

            # inside the window: EVERY reply still passes — survivors by
            # salvaged CT, the lost shard's flows by the grace flip
            t = eng.submit(_replies(slot_of, n, 0))
            assert eng.drain(timeout=30)
            assert int(np.asarray(t.result(5)["allow"]).sum()) == n
            hits = eng.metrics.counters.get("ct_salvage_grace_hits_total",
                                            0)
            assert hits > 0
            assert eng.remesh_status()["salvage_grace_remaining_s"] > 0

            # window closed: the flip stops, the uncovered flows fail
            # closed again (no forward traffic cold-learned them back)
            eng._salvage_until = 0.0
            t = eng.submit(_replies(slot_of, n, 0))
            assert eng.drain(timeout=30)
            allowed = int(np.asarray(t.result(5)["allow"]).sum())
            assert allowed < n
            assert allowed >= n - lost     # only lost-shard flows denied
            assert eng.remesh_status()["salvage_grace_remaining_s"] == 0.0

            # cold-learn: forward packets (policy-allowed) re-create the
            # entries on the survivor mesh; replies pass again with NO
            # grace window
            eng.submit(_mk(slot_of, n, 0))
            assert eng.drain(timeout=30)
            t = eng.submit(_replies(slot_of, n, 0))
            assert eng.drain(timeout=30)
            assert int(np.asarray(t.result(5)["allow"]).sum()) == n
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_heal_hysteresis_defers_and_flap_resets(self):
        eng = jit_pipeline_engine(4, remesh_heal_hysteresis_s=600.0)
        slot_of = eng.active.snapshot.ep_slot_of
        try:
            eng.submit(_mk(slot_of, 8, 0))
            assert eng.drain(timeout=30)
            FAULTS.arm("device.fail", mode="fail", message="dev=2")
            try:
                eng.submit(_mk(slot_of, 4, 500)).result(timeout=30)
            except PipelineError:
                pass
            deadline = time.monotonic() + 30
            while (eng.pipeline_stats() or {}).get("state") \
                    != "device-lost" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert eng.remesh_step()["remesh"]["to"] == 3
            assert eng.drain(timeout=30)

            # probe passes but the streak is younger than the
            # hysteresis: no re-admission yet
            FAULTS.disarm("device.fail")
            doc = eng.remesh_step()
            assert doc["remesh"] is None
            assert doc["heal_ok_s"] >= 0
            assert eng.datapath.mesh_health()["live"] == 3
            # a flap (fresh loss signal) zeroes the streak
            eng._on_device_loss(2, "flap drill")
            assert eng._heal_ok_since is None
        finally:
            eng.stop()

    def test_no_survivors_refuses_remesh(self):
        eng = jit_pipeline_engine(2)
        try:
            for o in (0, 1):
                eng.datapath.note_device_loss(o, reason="drill")
            doc = eng.remesh_step()
            assert doc["remesh"] == "no-survivors"
            assert eng.datapath.mesh_health()["live"] == 2  # unchanged
        finally:
            eng.stop()

    def test_remesh_disabled_is_inert(self):
        eng = jit_pipeline_engine(2, remesh_enabled=False)
        try:
            eng.datapath.note_device_loss(1, reason="drill")
            assert eng.remesh_step() is None
        finally:
            eng.stop()


# --------------------------------------------------------------------------- #
# the ct-snapshot controller tick + the archive as salvage floor
# --------------------------------------------------------------------------- #
class TestCTSnapshotController:
    def test_snapshot_age_gauge_and_stale_health(self, tmp_path):
        eng = jit_pipeline_engine(2, ct_snapshot_dir=str(tmp_path),
                                  checkpoint_max_age_s=300.0)
        slot_of = eng.active.snapshot.ep_slot_of
        try:
            # no archive yet: DEGRADED with CHECKPOINT_STALE, gauge -1
            h = eng.health()
            assert h["state"] == C.HEALTH_DEGRADED
            assert h["checkpoint"]["detail"] == C.CHECKPOINT_STALE
            eng.submit(_mk(slot_of, 16, 0))
            assert eng.drain(timeout=30)
            doc = eng.ct_snapshot_step()
            assert doc["entries"] == 16
            assert eng.metrics.gauges["checkpoint_age_seconds"] >= 0.0
            assert eng.health()["state"] == C.HEALTH_OK
            # age the archive past the budget (mtime is the clock so the
            # age survives restarts): stale again
            old = time.time() - 10_000
            os.utime(doc["path"], (old, old))
            h = eng.health()
            assert h["state"] == C.HEALTH_DEGRADED
            assert h["checkpoint"]["detail"] == C.CHECKPOINT_STALE
            assert h["checkpoint"]["age_s"] > 300.0
        finally:
            eng.stop()

    def test_collective_fault_fails_tick_but_keeps_gauge(self, tmp_path):
        eng = jit_pipeline_engine(2, ct_snapshot_dir=str(tmp_path))
        try:
            FAULTS.arm("device.collective", mode="fail")
            with pytest.raises(FaultInjected):
                eng.ct_snapshot_step()     # controller supervision backs off
            # the finally kept the age gauge honest: no archive = -1
            assert eng.metrics.gauges["checkpoint_age_seconds"] == -1.0
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_archive_is_the_salvage_floor_when_gather_dies(self, tmp_path):
        eng = jit_pipeline_engine(4, remesh_heal_hysteresis_s=0.0,
                                  ct_snapshot_dir=str(tmp_path))
        slot_of = eng.active.snapshot.ep_slot_of
        try:
            eng.submit(_mk(slot_of, 32, 0))
            assert eng.drain(timeout=30)
            assert eng.ct_snapshot_step()["entries"] == 32
            # the chip died holding the collective: device gather fails,
            # the re-mesh falls back to the bounded-staleness archive
            FAULTS.arm("device.collective", mode="fail")
            eng.datapath.note_device_loss(1, reason="drill")
            doc = eng.remesh_step()
            assert doc["remesh"]["salvage_source"] == "archive"
            assert doc["remesh"]["ct_salvaged"] > 0
            assert eng.datapath.remesh_stats["remesh_gather_failures"] == 1
            FAULTS.disarm("device.collective")
            # the salvaged floor actually serves: established flows from
            # the archive still hit CT on the survivor mesh
            t = eng.submit(_replies(slot_of, 32, 0))
            assert eng.drain(timeout=30)
            out = t.result(5)
            n_reply = int((np.asarray(out["status"])
                           == int(C.CTStatus.REPLY)).sum())
            assert n_reply > 0
        finally:
            eng.stop()
