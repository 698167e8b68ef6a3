"""Crash-consistency engine tests: CRC-framed WAL, seeded power-loss
injection, ack-after-commit, cold-restart replay drills.

Reference test model: the store/kv crash tests plus the teuthology
thrash-with-kill suites (``src/test/objectstore/store_test.cc``,
``qa/tasks/thrashosds`` with ``powercycle``; SURVEY.md §6.4): after
any crash, replay must resurface every acknowledged write and must
NOT resurface a torn, never-acknowledged one.
"""

import json
import os
import shutil
import threading
import time

import pytest

from ceph_tpu.mon import MonitorDBStore
from ceph_tpu.mon.store import StoreTransaction
from ceph_tpu.os_store import (CRASH_POINTS, CrashInjector,
                               SimulatedPowerLoss, StoreError, WALStore,
                               walog)
from ceph_tpu.os_store.objectstore import Transaction
from ceph_tpu.vstart import MiniCluster


# ---------------------------------------------------------------------------
# unit: record framing + torn-tail recovery rule
# ---------------------------------------------------------------------------
class TestWalogFraming:
    def test_roundtrip(self):
        recs = [b"", b"x", b"hello" * 100, os.urandom(333)]
        buf = b"".join(walog.encode_record(r) for r in recs)
        out, off, tail = walog.scan_records(buf)
        assert out == recs
        assert off == len(buf)
        assert tail["status"] == "clean" and tail["lost_bytes"] == 0

    def test_parts_frame_as_their_join(self):
        parts = (b"front", b"", os.urandom(1000), b"tail")
        assert walog.encode_record(*parts) == \
            walog.encode_record(b"".join(parts))

    def test_torn_tail_at_every_byte_offset(self):
        """The power-loss contract, exhaustively: cut the last record
        at EVERY byte boundary — header, length field, CRC, payload —
        and recovery must keep exactly the intact prefix."""
        prefix = [b"first", b"second" * 7]
        last = b"the-final-record-" + bytes(range(64))
        good = b"".join(walog.encode_record(r) for r in prefix)
        full = good + walog.encode_record(last)
        for cut in range(len(good) + 1, len(full)):
            out, off, tail = walog.scan_records(full[:cut])
            assert out == prefix, cut
            assert off == len(good), cut
            assert tail["status"] == "torn", (cut, tail)
            assert tail["lost_bytes"] == cut - len(good), cut
        out, off, tail = walog.scan_records(full)
        assert out == prefix + [last] and tail["status"] == "clean"

    def test_bad_magic_is_corrupt(self):
        buf = walog.encode_record(b"ok") + b"ZZ" + b"\0" * 20
        out, off, tail = walog.scan_records(buf)
        assert out == [b"ok"]
        assert tail["status"] == "corrupt"
        assert "magic" in tail["error"]

    def test_crc_flip_is_corrupt(self):
        rec = bytearray(walog.encode_record(b"payload-bytes"))
        rec[-1] ^= 0xFF          # flip a payload bit, CRC now lies
        out, off, tail = walog.scan_records(bytes(rec))
        assert out == [] and off == 0
        assert tail["status"] == "corrupt"
        assert "crc" in tail["error"]

    def test_crc_matches_scrub_kernel(self):
        # the framed CRC must stay bit-compatible with the scrub path
        from ceph_tpu.scrub.crc32c_jax import crc32c as scrub_crc
        for data in (b"", b"123456789", os.urandom(1000)):
            assert walog.crc32c(data) == scrub_crc(data)

    def test_truncate_tail(self, tmp_path):
        p = str(tmp_path / "log")
        with open(p, "wb") as f:
            f.write(walog.encode_record(b"keep") + b"\xce\x01tear")
        _, off, tail = walog.scan_path(p)
        assert tail["status"] != "clean"
        walog.truncate_tail(p, off)
        out, off2, tail2 = walog.scan_path(p)
        assert out == [b"keep"] and tail2["status"] == "clean"


# ---------------------------------------------------------------------------
# unit: the WAL record's payload (Transaction.to_record) in a WALStore
# ---------------------------------------------------------------------------
# a record as the hex-JSON form before the binary one wrote it
_HEX_JSON_RECORD = (
    b'[["create_collection","1.0",""],'
    b'["write","1.0","o",0,{"b":"00ff6f6c64"}],'
    b'["setattrs","1.0","o",{"d":{"_":{"b":"7b7d"}}}],'
    b'["omap_setkeys","1.0","o",{"d":{"k":{"b":"76"}}}]]')


class TestWALRecordForm:
    DATA = bytes(range(256)) * 16

    def _write(self, path, n=2):
        s = WALStore(path)
        s.mkfs()
        s.apply_transaction(Transaction().create_collection("1.0"))
        for i in range(n):
            s.apply_transaction(
                Transaction().write("1.0", f"o{i}", 0, self.DATA)
                .setattrs("1.0", f"o{i}", {"\x00k": bytes([i])}))
        s.umount()

    def test_mount_replays_binary_log(self, tmp_path):
        path = str(tmp_path / "s.wal")
        self._write(path)
        payloads, _, tail = walog.scan_path(path)
        assert tail["status"] == "clean" and len(payloads) == 3
        assert all(not p.startswith(b"[") for p in payloads)
        assert self.DATA in payloads[1]          # raw, not hex
        s = WALStore(path)
        s.mount()
        assert s.replay_stats["records"] == 3
        for i in range(2):
            assert s.read("1.0", f"o{i}") == self.DATA
            assert s.getattr("1.0", f"o{i}", "\x00k") == bytes([i])
        s.umount()

    @pytest.mark.parametrize("keep", [
        walog.HEADER_SIZE - 1, walog.HEADER_SIZE + 3,
        walog.HEADER_SIZE + 40, 0.5, -1])
    def test_torn_binary_record_truncated(self, tmp_path, keep):
        path = str(tmp_path / "s.wal")
        self._write(path, n=1)
        good = os.path.getsize(path)
        rec = walog.encode_record(
            *Transaction().write("1.0", "torn", 0, self.DATA).to_record())
        cut = int(len(rec) * keep) if isinstance(keep, float) else keep
        with open(path, "ab") as f:
            f.write(rec[:cut])
        s = WALStore(path)
        s.mount()
        assert s.replay_stats["tail"]["status"] == "torn"
        assert s.read("1.0", "o0") == self.DATA
        assert not s.exists("1.0", "torn")
        s.umount()
        assert os.path.getsize(path) == good       # the tail is gone

    def test_hex_json_record_still_replays(self, tmp_path):
        """A log an older store wrote mounts; new writes append binary
        records after its hex-JSON ones, and both replay."""
        from ceph_tpu.tools import objectstore_tool
        path = str(tmp_path / "s.wal")
        with open(path, "wb") as f:
            f.write(walog.encode_record(_HEX_JSON_RECORD))
        s = WALStore(path)
        s.mount()
        assert s.read("1.0", "o") == b"\x00\xffold"
        assert s.getattr("1.0", "o", "_") == b"{}"
        assert s.omap_get("1.0", "o") == {"k": b"v"}
        s.apply_transaction(Transaction().write("1.0", "o", 5, b"new"))
        s.umount()
        s2 = WALStore(path)
        s2.mount()
        assert s2.replay_stats["records"] == 2
        assert s2.read("1.0", "o") == b"\x00\xffoldnew"
        s2.umount()
        rep = objectstore_tool.fsck(path)
        assert rep["records_replayed"] == 2 and not rep["issues"]

    def test_compaction_writes_binary_records(self, tmp_path):
        path = str(tmp_path / "s.wal")
        with open(path, "wb") as f:
            f.write(walog.encode_record(_HEX_JSON_RECORD))
        s = WALStore(path)
        s.mount()
        s.compact()
        s.umount()
        payloads, _, _ = walog.scan_path(path)
        assert payloads and not any(p.startswith(b"[") for p in payloads)
        s2 = WALStore(path)
        s2.mount()
        assert s2.read("1.0", "o") == b"\x00\xffold"
        assert s2.omap_get("1.0", "o") == {"k": b"v"}
        s2.umount()


# ---------------------------------------------------------------------------
# unit: seeded crash injector
# ---------------------------------------------------------------------------
class TestCrashInjector:
    def test_deterministic_schedule(self):
        a = CrashInjector(seed=42, osd="osd.1")
        b = CrashInjector(seed=42, osd="osd.1")
        a.set_prob("pre_append", 0.3)
        b.set_prob("pre_append", 0.3)
        va = [a.decide("pre_append") for _ in range(50)]
        vb = [b.decide("pre_append") for _ in range(50)]
        assert va == vb and any(va) and not all(va)
        # different osd or seed => different schedule
        c = CrashInjector(seed=42, osd="osd.2")
        c.set_prob("pre_append", 0.3)
        assert [c.decide("pre_append") for _ in range(50)] != va

    def test_preview_consumes_nothing(self):
        inj = CrashInjector(seed=7, osd="x")
        inj.set_prob("mid_record", 0.5)
        before = dict(inj.counters)
        sched = inj.preview("mid_record", count=20)
        assert inj.counters == before
        observed = [inj.decide("mid_record") for _ in range(20)]
        assert observed == sched

    def test_arm_fires_exactly_once(self):
        inj = CrashInjector()
        inj.arm("post_append_pre_fsync", 2)
        got = [inj.decide("post_append_pre_fsync") for _ in range(5)]
        assert got == [False, False, True, False, False]
        assert inj.fired == [("post_append_pre_fsync", 2)]

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError):
            CrashInjector().arm("nonsense")


# ---------------------------------------------------------------------------
# unit: the full crash-point sweep on a bare WALStore
# ---------------------------------------------------------------------------
def _write_until_crash(store, inj, point, limit=20):
    """Drive writes (and compactions for mid_compaction) until the
    armed point fires; returns indices of acknowledged writes."""
    acked = []
    for n in range(limit):
        t = Transaction().write("2.0", f"o{n}", 0,
                                f"payload-{n}".encode() * 3)
        try:
            store.queue_transaction(t)
            acked.append(n)
            if point == "mid_compaction":
                store.compact()
        except SimulatedPowerLoss:
            return acked
    raise AssertionError(f"{point} never fired")


class TestCrashSweep:
    @pytest.mark.parametrize("point", CRASH_POINTS)
    def test_acked_writes_survive(self, tmp_path, point):
        path = str(tmp_path / "osd.wal")
        inj = CrashInjector(seed=3, osd="osd.0")
        s = WALStore(path, sync_mode="always", crash=inj)
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("2.0"))
        inj.arm(point)
        assert inj.preview(point, count=1) == [True]
        acked = _write_until_crash(s, inj, point)
        assert inj.fired and inj.fired[0][0] == point
        # the store is dead now: every later write must refuse
        with pytest.raises(StoreError):
            s.queue_transaction(Transaction().touch("2.0", "late"))
        # cold remount from what stable storage kept
        s2 = WALStore(path)
        s2.mount()
        assert s2.replay_stats["clean_shutdown"] is False
        for n in acked:
            assert bytes(s2.read("2.0", f"o{n}")) == \
                f"payload-{n}".encode() * 3, (point, n)
        if point == "mid_record":
            # the torn fragment was on disk; replay must have cut it
            assert s2.replay_stats["tail"]["status"] == "torn"
        s2.umount()

    def test_unacked_torn_write_never_surfaces(self, tmp_path):
        path = str(tmp_path / "osd.wal")
        inj = CrashInjector(seed=5, osd="osd.0")
        s = WALStore(path, sync_mode="always", crash=inj)
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("2.0"))
        inj.arm("mid_record")
        with pytest.raises(SimulatedPowerLoss):
            s.queue_transaction(
                Transaction().write("2.0", "ghost", 0, b"never-acked"))
        s2 = WALStore(path)
        s2.mount()
        assert not s2.exists("2.0", "ghost")
        s2.umount()

    def test_durable_unacked_write_surfaces(self, tmp_path):
        # post_fsync_pre_apply: the one legal "extra" state — the
        # record reached stable storage before the cut, so replay
        # must apply it even though no ack ever fired
        path = str(tmp_path / "osd.wal")
        inj = CrashInjector(seed=5, osd="osd.0")
        s = WALStore(path, sync_mode="always", crash=inj)
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("2.0"))
        inj.arm("post_fsync_pre_apply")
        with pytest.raises(SimulatedPowerLoss):
            s.queue_transaction(
                Transaction().write("2.0", "extra", 0, b"durable"))
        s2 = WALStore(path)
        s2.mount()
        assert bytes(s2.read("2.0", "extra")) == b"durable"
        s2.umount()

    def test_mid_compaction_keeps_old_log_authoritative(self, tmp_path):
        path = str(tmp_path / "osd.wal")
        inj = CrashInjector(seed=9, osd="osd.0")
        s = WALStore(path, sync_mode="always", crash=inj)
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("2.0")
                            .write("2.0", "a", 0, b"aaa"))
        inj.arm("mid_compaction")
        with pytest.raises(SimulatedPowerLoss):
            s.compact()
        # the checkpoint temp is stranded; remount must ignore it
        assert os.path.exists(path + ".compact.tmp")
        s2 = WALStore(path)
        s2.mount()
        assert not os.path.exists(path + ".compact.tmp")
        assert bytes(s2.read("2.0", "a")) == b"aaa"
        s2.umount()


# ---------------------------------------------------------------------------
# unit: sync modes, group commit, compaction, failure-as-state
# ---------------------------------------------------------------------------
class TestWALStoreModes:
    def test_sync_mode_validation(self, tmp_path):
        with pytest.raises(ValueError):
            WALStore(str(tmp_path / "w"), sync_mode="sometimes")
        s = WALStore(str(tmp_path / "w"))
        assert s.sync_mode == "batch"
        assert WALStore(str(tmp_path / "w2"), sync=True).sync_mode \
            == "always"
        assert WALStore(str(tmp_path / "w3"), sync=False).sync_mode \
            == "none"

    def test_batch_commit_fires_after_kick(self, tmp_path):
        s = WALStore(str(tmp_path / "w"), sync_mode="batch")
        s.mount(); s.mkfs()
        done = threading.Event()
        s.queue_transaction(
            Transaction().create_collection("1.0"), done.set)
        s.kick()
        assert done.wait(5.0)
        assert s.wal_stats["group_syncs"] >= 1
        s.umount()

    def test_group_commit_amortizes(self, tmp_path):
        s = WALStore(str(tmp_path / "w"), sync_mode="batch")
        s.mount(); s.mkfs()
        s.commit_latency_s = 0.5     # only kicks close the window
        events = [threading.Event() for _ in range(32)]
        s.queue_transaction(Transaction().create_collection("1.0"))
        for i, ev in enumerate(events):
            s.queue_transaction(
                Transaction().touch("1.0", f"o{i}"), ev.set)
        s.kick()
        for ev in events:
            assert ev.wait(5.0)
        assert s.flush_commits()
        # one burst, a couple of fsyncs at most — not one per op
        assert s.wal_stats["group_syncs"] <= 3, dict(s.wal_stats)
        s.umount()

    def test_set_sync_mode_transitions(self, tmp_path):
        s = WALStore(str(tmp_path / "w"), sync_mode="none")
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("1.0"))
        s.set_sync_mode("batch")
        done = threading.Event()
        s.queue_transaction(Transaction().touch("1.0", "a"), done.set)
        s.kick()
        assert done.wait(5.0)
        s.set_sync_mode("always")
        s.queue_transaction(Transaction().touch("1.0", "b"))
        assert s.wal_stats["syncs"] >= 1
        s.umount()

    def test_compaction_shrinks_and_preserves(self, tmp_path):
        path = str(tmp_path / "w")
        s = WALStore(path, sync_mode="none")
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("1.0"))
        for i in range(50):
            s.queue_transaction(
                Transaction().write("1.0", "hot", 0, b"v%d" % i)
                .setattrs("1.0", "hot", {"k": b"x"})
                .omap_setkeys("1.0", "hot", {"m": b"y"}))
        stats = s.compact()
        assert stats["records_after"] < stats["records_before"]
        s.umount()
        s2 = WALStore(path)
        s2.mount()
        assert bytes(s2.read("1.0", "hot")) == b"v49"
        assert s2.getattrs("1.0", "hot") == {"k": b"x"}
        assert s2.omap_get("1.0", "hot") == {"m": b"y"}
        s2.umount()

    def test_auto_compaction_threshold(self, tmp_path):
        s = WALStore(str(tmp_path / "w"), sync_mode="none",
                     compact_min_records=20)
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("1.0"))
        for i in range(40):
            s.queue_transaction(
                Transaction().write("1.0", "o", 0, b"x" * 8))
        assert s.wal_stats["compactions"] >= 1
        s.umount()

    def test_failure_is_sticky_and_notified_once(self, tmp_path):
        inj = CrashInjector()
        s = WALStore(str(tmp_path / "w"), sync_mode="always",
                     crash=inj)
        errors = []
        s.on_error = errors.append
        s.mount(); s.mkfs()
        s.queue_transaction(Transaction().create_collection("1.0"))
        inj.arm("post_append_pre_fsync")
        with pytest.raises(SimulatedPowerLoss):
            s.queue_transaction(Transaction().touch("1.0", "a"))
        for _ in range(3):
            with pytest.raises(StoreError):
                s.queue_transaction(Transaction().touch("1.0", "b"))
        assert len(errors) == 1
        assert isinstance(errors[0], SimulatedPowerLoss)

    def test_dirty_marker_lifecycle(self, tmp_path):
        path = str(tmp_path / "w")
        s = WALStore(path, sync_mode="none")
        s.mount(); s.mkfs()
        assert os.path.exists(path + ".dirty")
        s.umount()
        assert not os.path.exists(path + ".dirty")
        s2 = WALStore(path)
        s2.mount()
        assert s2.replay_stats["clean_shutdown"] is True
        s2.umount()


# ---------------------------------------------------------------------------
# mon store: shared framing + exhaustive torn-tail recovery
# ---------------------------------------------------------------------------
class TestMonStoreTornTail:
    def test_torn_tail_every_byte_offset(self, tmp_path):
        """Mid-record truncation at every byte offset of the last
        record: the mon must come back with exactly the prefix
        state, never a partial or phantom commit."""
        path = str(tmp_path / "mon.wal")
        st = MonitorDBStore(path)
        st.apply_transaction(
            StoreTransaction().put("p", "committed", b"yes"))
        st.close()
        good_size = os.path.getsize(path)
        st = MonitorDBStore(path)
        st.apply_transaction(
            StoreTransaction().put("p", "last", b"L" * 40))
        st.close()
        full_size = os.path.getsize(path)
        with open(path, "rb") as f:
            full = f.read()
        for cut in range(good_size + 1, full_size):
            p2 = str(tmp_path / f"cut")
            with open(p2, "wb") as f:
                f.write(full[:cut])
            st2 = MonitorDBStore(p2)
            assert st2.get("p", "committed") == b"yes", cut
            assert st2.get("p", "last") is None, cut
            assert st2.replay_stats["tail"]["status"] == "torn", cut
            st2.close()

    def test_mon_records_use_shared_framing(self, tmp_path):
        path = str(tmp_path / "mon.wal")
        st = MonitorDBStore(path)
        st.apply_transaction(StoreTransaction().put("p", "k", b"v"))
        st.close()
        payloads, _, tail = walog.scan_path(path)
        assert tail["status"] == "clean"
        assert json.loads(payloads[0].decode())  # a parseable txn

    def test_corrupt_record_truncated_on_open(self, tmp_path):
        path = str(tmp_path / "mon.wal")
        st = MonitorDBStore(path)
        st.apply_transaction(StoreTransaction().put("p", "k", b"v"))
        st.close()
        with open(path, "ab") as f:
            f.write(b"garbage-that-is-not-a-frame")
        st2 = MonitorDBStore(path)
        assert st2.get("p", "k") == b"v"
        st2.close()
        # the repair is durable: the tail is gone from disk
        _, _, tail = walog.scan_path(path)
        assert tail["status"] == "clean"


# ---------------------------------------------------------------------------
# cluster: ack-after-commit — no client ack before WAL durability
# ---------------------------------------------------------------------------
class GatedWALStore(WALStore):
    """Commit callbacks park until the test opens the gate: any client
    ack that arrives while the gate is shut proves an ack-before-commit
    path."""

    def __init__(self, path, **kw):
        kw.setdefault("sync_mode", "none")
        super().__init__(path, **kw)
        self.gate_open = True
        self._held = []

    def queue_transaction(self, txn, on_commit=None):
        if self.gate_open:
            return super().queue_transaction(txn, on_commit)
        super().queue_transaction(txn, None)
        if on_commit is not None:
            self._held.append(on_commit)

    def open_gate(self):
        self.gate_open = True
        held, self._held = self._held, []
        for cb in held:
            self.finisher.queue(cb)


class TestAckAfterCommit:
    def test_client_ack_waits_for_commit(self, tmp_path):
        store = GatedWALStore(str(tmp_path / "osd.0.wal"))
        c = MiniCluster(n_mons=1, n_osds=1, osd_stores=[store])
        c.start()
        try:
            r = c.rados()
            r.create_pool("p", pg_num=2, size=1)
            io = r.open_ioctx("p")
            io.write_full("warm", b"w")        # gate still open
            store.gate_open = False
            acked = threading.Event()

            def client_write():
                io.write_full("gated", b"g")
                acked.set()

            t = threading.Thread(target=client_write, daemon=True)
            t.start()
            # the write must stall: its commit callback is parked
            assert not acked.wait(1.0), \
                "client acked before the WAL committed"
            store.open_gate()
            assert acked.wait(10.0)
            t.join(5.0)
            assert io.read("gated") == b"g"
        finally:
            store.gate_open = True
            c.stop()


# ---------------------------------------------------------------------------
# cluster: cold-restart replay + power-loss drills + deep-scrub verify
# ---------------------------------------------------------------------------
def _byte_verify(io, objects):
    for name, data in objects.items():
        assert bytes(io.read(name)) == data, name


class TestClusterCrashDrills:
    def test_crash_revive_deep_scrub(self):
        """One OSD loses power mid-workload; after cold remount +
        re-peer, deep scrub finds zero errors and every acked write
        byte-verifies."""
        c = MiniCluster(n_mons=1, n_osds=3)
        c.start()
        try:
            r = c.rados()
            r.create_pool("p", pg_num=8, size=2)
            io = r.open_ioctx("p")
            objects = {f"obj-{i}": f"payload-{i}".encode() * 9
                       for i in range(24)}
            for name, data in objects.items():
                io.write_full(name, data)
            c.wait_for_clean(timeout=60)
            c.crash_osd(0)
            c.wait_for_osd_down(0, timeout=60)
            c.revive_osd(0)
            c.wait_for_clean(timeout=90)
            stats = c.osds[0].store.replay_stats
            assert stats["clean_shutdown"] is False
            assert stats["records"] > 0
            _byte_verify(io, objects)
            pgids = set()
            for osd in c.osds.values():
                with osd.lock:
                    pgids.update(p for p, pg in osd.pgs.items()
                                 if pg.is_primary)
            assert pgids
            for pgid in sorted(pgids):
                assert c.scrub_pg(pgid, timeout=30, deep=True) == 0
        finally:
            c.stop()

    def test_whole_cluster_power_loss(self):
        c = MiniCluster(n_mons=1, n_osds=3)
        c.start()
        try:
            r = c.rados()
            r.create_pool("p", pg_num=4, size=2)
            io = r.open_ioctx("p")
            objects = {f"o{i}": os.urandom(256) for i in range(12)}
            for name, data in objects.items():
                io.write_full(name, data)
            c.wait_for_clean(timeout=60)
            stats = c.power_loss(timeout=120)
            assert set(stats) == {0, 1, 2}
            for s in stats.values():
                assert s["clean_shutdown"] is False
            c.wait_for_clean(timeout=120)
            io2 = c.rados().open_ioctx("p")
            _byte_verify(io2, objects)
        finally:
            c.stop()

    @pytest.mark.slow
    @pytest.mark.parametrize("point", CRASH_POINTS)
    def test_seeded_sweep_every_crash_point(self, point):
        """The acceptance drill: arm each crash point on one OSD,
        drive a workload until it fires, cold-restart, re-peer, and
        byte-verify that no acked write was lost."""
        c = MiniCluster(n_mons=1, n_osds=3, fault_seed=13)
        c.start()
        try:
            r = c.rados()
            r.create_pool("p", pg_num=4, size=2)
            io = r.open_ioctx("p")
            c.wait_for_clean(timeout=60)
            victim = c.osds[0]
            inj = victim.store.crash
            assert inj is not None
            inj.arm(point)
            acked = {}
            deadline = time.monotonic() + 60
            i = 0
            while not inj.fired:
                if time.monotonic() > deadline:
                    raise AssertionError(f"{point} never fired")
                name, data = f"o{i}", f"v{i}".encode() * 11
                try:
                    io.write_full(name, data)
                    acked[name] = data
                except Exception:
                    # the victim died mid-op: the write was never
                    # acked, so no durability claim attaches to it
                    break
                if point == "mid_compaction" and i % 5 == 4:
                    try:
                        victim.store.compact()
                    except (SimulatedPowerLoss, StoreError):
                        break
                i += 1
            assert inj.fired and inj.fired[0][0] == point
            # the daemon degraded; give the cluster the kill signal
            c.crash_osd(0)
            c.wait_for_osd_down(0, timeout=60)
            c.revive_osd(0)
            c.wait_for_clean(timeout=90)
            io2 = c.rados().open_ioctx("p")
            _byte_verify(io2, acked)
        finally:
            c.stop()


# ---------------------------------------------------------------------------
# cluster: store failure degrades the daemon, health reports it
# ---------------------------------------------------------------------------
class TestStoreErrorDegradation:
    def test_failed_store_marks_osd_down_with_health_err(self):
        c = MiniCluster(n_mons=1, n_osds=3,
                        osd_config={"osd_heartbeat_interval": 0.3,
                                    "osd_heartbeat_grace": 2.0})
        c.start()
        try:
            r = c.rados()
            r.create_pool("p", pg_num=4, size=2)
            io = r.open_ioctx("p")
            io.write_full("before", b"ok")
            c.wait_for_clean(timeout=60)
            victim = c.osds[0]
            inj = victim.store.crash
            inj.arm("post_append_pre_fsync")
            # write until one lands on the victim's store and dies
            deadline = time.monotonic() + 60
            i = 0
            while not inj.fired:
                assert time.monotonic() < deadline
                try:
                    io.write_full(f"x{i}", b"y")
                except Exception:
                    break
                i += 1
            c.wait_for_osd_down(0, timeout=60)

            # health must carry the new evaluator's verdict
            def reported_codes():
                rc, _, rep = r.mon_command({"prefix": "health detail"})
                assert rc == 0
                return {chk["code"] for chk in rep.get("checks", [])}
            deadline = time.monotonic() + 30
            while "OSD_STORE_ERROR" not in reported_codes():
                assert time.monotonic() < deadline, reported_codes()
                time.sleep(0.2)
            # the cluster keeps serving without the degraded OSD
            c.wait_for_clean(timeout=90)
            io.write_full("after", b"still-writable")
            assert io.read("after") == b"still-writable"
        finally:
            c.stop()


# ---------------------------------------------------------------------------
# batch engine on/off: same bytes, same acks — just different batching
# ---------------------------------------------------------------------------
class TestEngineDurabilityParity:
    # engine=True (the default path) is already crash-covered by
    # TestClusterCrashDrills; tier-1 keeps the non-default engine-off
    # parity case and the redundant one rides in tier-3
    @pytest.mark.parametrize(
        "engine",
        [pytest.param(True, marks=pytest.mark.slow), False])
    def test_writes_ack_and_survive(self, engine):
        c = MiniCluster(n_mons=1, n_osds=3,
                        osd_config={"osd_batch_enable": engine})
        c.start()
        try:
            r = c.rados()
            r.create_pool("p", pg_num=4, size=2)
            io = r.open_ioctx("p")
            objects = {f"e{i}": os.urandom(512) for i in range(10)}
            for name, data in objects.items():
                io.write_full(name, data)
            c.wait_for_clean(timeout=60)
            c.crash_osd(1)
            c.wait_for_osd_down(1, timeout=60)
            c.revive_osd(1)
            c.wait_for_clean(timeout=90)
            _byte_verify(io, objects)
        finally:
            c.stop()
