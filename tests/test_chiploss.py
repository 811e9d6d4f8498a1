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

    def _tuples(self, family, direction, n=96, seed=7):
        """``n`` rows of distinct flows: v4, v6 or every third row v6, in
        the forward orientation (``"fwd"``), the reverse (``"rev"``) or
        row by row alternating (``"both"``); every fifth row invalid."""
        rng = np.random.default_rng(seed)
        b = self._buf(n)
        v6 = {"v4": np.zeros(n, bool), "v6": np.ones(n, bool),
              "mixed": np.arange(n) % 3 == 0}[family]
        words = rng.integers(1, 1 << 32, size=(2, n, 4), dtype=np.uint64)
        for col, w in zip(("src", "dst"), words):
            b[col][v6] = w[v6].astype(np.uint32)
        b["proto"][::4] = 17
        rev = {"fwd": np.zeros(n, bool), "rev": np.ones(n, bool),
               "both": np.arange(n) % 2 == 1}[direction]
        for x, y in (("src", "dst"), ("sport", "dport")):
            fwd = b[x].copy()
            b[x][rev], b[y][rev] = b[y][rev], fwd[rev]
        b["direction"][rev] = 1
        b["valid"][::5] = False
        return b

    @pytest.mark.parametrize("direction", ["fwd", "rev", "both"])
    @pytest.mark.parametrize("family", ["v4", "v6", "mixed"])
    def test_carried_hashes_give_the_same_table(self, family, direction):
        """A table fed ``note`` and asked ``hits`` with the batch's ``_fp``
        column is, array for array, the table fed by hashing: over invalid
        rows, denied rows, rows not yet established, a second batch that
        overwrites slots of the first, and the replies' orientation."""
        from cilium_tpu.shim.feeder import flow_hashes
        n = 96
        est = np.full(n, int(C.CTStatus.ESTABLISHED), np.int32)
        est[1::7] = int(C.CTStatus.NEW)
        est[2::7] = int(C.CTStatus.REPLY)
        allow = np.ones(n, bool)
        allow[3::6] = False
        out = {"allow": allow, "status": est}
        hashed = EstablishedFingerprints(slots=1 << 6)    # slots collide
        carried = EstablishedFingerprints(slots=1 << 6)
        seen = []
        for seed in (7, 8):
            b = self._tuples(family, direction, n, seed)
            with_fp = dict(b, _fp=flow_hashes(b))
            hashed.note(b, out)
            carried.note(with_fp, out)
            np.testing.assert_array_equal(carried._tab, hashed._tab)
            seen.append((b, with_fp))
        assert hashed._tab.any()
        for b, with_fp in seen:
            np.testing.assert_array_equal(carried.hits(with_fp),
                                          hashed.hits(b))
            # the other orientation of the same flows reads the same slots
            back = {**b, "src": b["dst"], "dst": b["src"],
                    "sport": b["dport"], "dport": b["sport"],
                    "direction": 1 - b["direction"]}
            np.testing.assert_array_equal(
                carried.hits(dict(back, _fp=flow_hashes(back))),
                hashed.hits(b))
        m = out["allow"] & (est != int(C.CTStatus.NEW)) & seen[1][0]["valid"]
        hit = hashed.hits(seen[1][0])
        assert hit[m].any() and not hit[~m].any()   # (a slot's last writer)
        # hashing is counted where it is done, carried hashes are not
        assert carried.hashed_rows == 0
        assert hashed.hashed_rows == 2 * int(m.sum()) + 5 * n

    def test_a_carried_zero_is_a_hash_and_not_an_absence(self):
        """``_fp`` holds no "absent" value: a batch that carries the
        column stamps what it says, 0 included, so only a batch WITHOUT
        the column may be handed in for rows that were never hashed."""
        fp = EstablishedFingerprints(slots=1 << 4)
        b = self._buf(2)
        out = {"allow": np.ones(2, bool),
               "status": np.full(2, int(C.CTStatus.ESTABLISHED), np.int32)}
        fp.note(dict(b, _fp=np.zeros(2, np.uint32)), out)
        assert fp._tab[0] == 1 and not fp._tab[1:].any()
        assert not fp.hits(b).any()


# --------------------------------------------------------------------------- #
# the salvage filter reads the hash the batch carries (PR 41)
# --------------------------------------------------------------------------- #
class TestSalvageReadsTheCarriedHash:
    """``Engine._ct_salvage_apply`` over batches that carry ``_fp`` and
    over the same batches without it: the same rows flip while the grace
    window is open, the same table is left behind, and only a batch
    without the column is hashed on the worker."""

    N = 24

    def _engine(self, how):
        if how == "sharded":
            # tiny-pods-mesh4.json's size: four flow shards, host RSS, so
            # every submission is steered into per-shard segments
            return jit_pipeline_engine(4, batch_size=1024,
                                       ct_capacity=65536,
                                       pipeline_flush_ms=5000.0)
        from tests.test_sharded_pipeline import fake_serial_engine
        return fake_serial_engine(batch_size=64, pipeline_min_bucket=16,
                                  pipeline_flush_ms=5000.0)

    def _phase(self, slot_of, how, replies, start, n):
        """``n`` flows' packets as the variant submits them: one
        bucket-shaped batch (``direct``), or chunks of ten rows with an
        invalid tail, which stage and coalesce."""
        mk = _replies if replies else _mk
        if how == "direct":
            b = mk(slot_of, n, start)
            pad = {k: np.zeros((32 - n,) + v.shape[1:], v.dtype)
                   for k, v in b.items()}
            return [{k: np.concatenate([b[k], pad[k]]) for k in b}]
        chunks = []
        for i in range(0, n, 10):
            m = min(10, n - i)
            recs = [pkt(f"10.0.2.{((start + i + j) % 200) + 1}",
                        "192.168.1.10", 443, 52000 + start + i + j,
                        flags=C.TCP_ACK, direction=C.DIR_INGRESS)
                    if replies else
                    pkt("192.168.1.10", f"10.0.2.{((start + i + j) % 200) + 1}",
                        52000 + start + i + j, 443) for j in range(m)]
            chunks.append(batch_from_records(recs, slot_of, pad_to=m + 2))
        return chunks

    def _serve(self, eng, how, carry):
        """Warm the filter, lose the conntrack state (its entries expire),
        open the window, and ask again for the flows stamped and for as
        many never seen. ``carry(i)``: whether the i-th submission brings
        its ``_fp``. → every submission's answers."""
        from cilium_tpu.shim.feeder import flow_hashes
        slot_of = eng.active.snapshot.ep_slot_of
        n, outs, k = self.N, [], 0
        t0 = 1_000_000
        for now, replies, start, rows, grace in (
                (t0, False, 0, n, False), (t0 + 1, True, 0, n, False),
                (t0 + 10 ** 7, True, 0, n, True),
                (t0 + 10 ** 7, True, 7000, n, True)):
            if grace:
                eng._salvage_until = time.monotonic() + 600
            tickets = []
            for b in self._phase(slot_of, how, replies, start, rows):
                if carry(k):
                    b = dict(b, _fp=flow_hashes(b))
                tickets.append((eng.submit(b, now=now), b))
                k += 1
            assert eng.drain(timeout=60)
            outs.append([(b["valid"].copy(),
                          {key: np.asarray(t.result(5)[key]).copy()
                           for key in ("allow", "reason", "status")})
                         for t, b in tickets])
        return outs

    @pytest.mark.parametrize("how,carry", [
        ("direct", "all"), ("staged", "all"), ("sharded", "all"),
        ("staged", "mixed")])
    def test_the_same_rows_flip_with_and_without_the_column(self, how,
                                                            carry):
        carries = {"all": lambda i: True, "mixed": lambda i: i % 2 == 0}
        got, tabs, engines = [], [], []
        try:
            for fn in (carries[carry], lambda i: False):
                eng = self._engine(how)
                engines.append(eng)
                got.append(self._serve(eng, how, fn))
                tabs.append(eng._salvage_fp._tab.copy())
            with_fp, without = engines
            for a, b in zip(*got):
                for (va, oa), (vb, ob) in zip(a, b):
                    np.testing.assert_array_equal(va, vb)
                    for key in oa:
                        np.testing.assert_array_equal(oa[key], ob[key])
            n = self.N
            warm, stamped, fresh = got[0][1], got[0][2], got[0][3]
            for v, o in warm:
                assert (o["status"][v] == int(C.CTStatus.REPLY)).all()
            # the flows stamped before the loss ride the window, and flows
            # the filter never saw stay refused
            assert sum(int(o["allow"][v].sum()) for v, o in stamped) == n
            assert sum(int(o["allow"].sum()) for v, o in fresh) == 0
            for eng in engines:
                assert eng.metrics.counters[
                    "ct_salvage_grace_hits_total"] == n
            np.testing.assert_array_equal(tabs[0], tabs[1])
            assert tabs[0].any() and tabs[0][0] == 0
            reasons = with_fp.pipeline_stats()["flush_reasons"]
            if how == "direct":
                assert reasons["direct"] == 4
            else:                     # one coalesced bucket a phase
                assert reasons["direct"] == 0
                assert sum(reasons.values()) == 4
            hashed = [e.pipeline_stats()["verdict_rows"]["flow_hash_rows"]
                      for e in engines]
            # a bucket whose riders all brought the column is never hashed
            # on the worker; one rider without it and the bucket is hashed
            # as before, whole: "absent" is never read as the hash 0
            assert hashed[1] > 0
            assert hashed[0] == (0 if carry == "all" else hashed[1])
        finally:
            for eng in engines:
                eng.stop()


# --------------------------------------------------------------------------- #
# CT archive helpers
# --------------------------------------------------------------------------- #
class TestCTArchive:
    def _arrays(self, cap=64, live=5):
        from cilium_tpu.compile.ct_layout import (
            CTConfig, logical_ct_arrays, make_ct_arrays)
        a = logical_ct_arrays(make_ct_arrays(CTConfig(capacity=cap)))
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
