"""A ranged EC read fetches only the shard byte ranges its extent maps
to: the mapping (``ErasureCodeInterface.data_extents``), and the OSD
path that serves from it (``op_r_extent``) or falls back to the
whole-shard gather — for a whole-object read, a lost shard, a write in
flight, a sealed payload — and answers EIO for a corrupted shard."""

import contextlib
import json
import time

import numpy as np
import pytest

from ceph_tpu.core.admin_socket import admin_command
from ceph_tpu.ec import create_erasure_code
from ceph_tpu.ec.jerasure import ErasureCodeJerasure
from ceph_tpu.os_store import Transaction
from ceph_tpu.osd import messages as M
from ceph_tpu.osdc.librados import Error
from ceph_tpu.scrub.crc32c_jax import crc32c
from ceph_tpu.vstart import MiniCluster

KIB, MIB = 1 << 10, 1 << 20


# ------------------------------------------------------------- the mapping

@pytest.fixture(scope="module")
def k4():
    return create_erasure_code({"plugin": "jerasure", "k": 4, "m": 2})


class TestDataExtents:
    @pytest.mark.parametrize("size,extents,want", [
        (4 * MIB, [(8 * KIB, 4 * KIB)], {0: (8 * KIB, 4 * KIB)}),
        (4 * MIB, [(MIB + 4 * KIB, 4 * KIB)], {1: (4 * KIB, 4 * KIB)}),
        (4 * MIB, [(MIB - 100, 200)], {0: (MIB - 100, 100), 1: (0, 100)}),
        (4 * MIB - 10, [(4 * MIB - 100, 4 * KIB)], {3: (MIB - 100, 90)}),
        (4 * MIB, [(5, 0)], {}),
        (4 * MIB, [(4 * MIB + 10, 10)], {}),
        (0, [(0, 4 * KIB)], {}),
        (4 * MIB, [(0, 10), (100, 10)], {0: (0, 110)}),
    ], ids=["aligned_4k", "aligned_4k_shard1", "straddles_two_shards",
            "clipped_at_size", "zero_length", "past_size", "empty_object",
            "covering_range"])
    def test_maps_extents_to_shard_ranges(self, k4, size, extents, want):
        assert k4.data_extents(size, extents) == want

    def test_whole_object_is_every_shard_at_full_length(self, k4):
        chunk = k4.get_chunk_size(4 * MIB)
        assert k4.data_extents(4 * MIB, [(0, 4 * MIB)]) == \
            {i: (0, chunk) for i in range(4)}

    @pytest.mark.parametrize("profile", [
        {"plugin": "jerasure", "k": 4, "m": 2},
        {"plugin": "jerasure", "k": 4, "m": 2, "technique": "cauchy_good"},
        {"plugin": "isa", "k": 4, "m": 2},
        {"plugin": "shec", "k": 6, "m": 3, "c": 2},
        {"plugin": "lrc", "k": 4, "m": 2, "l": 3},
        {"plugin": "clay", "k": 4, "m": 2},
    ], ids=["jerasure", "cauchy_good", "isa", "shec", "lrc", "clay"])
    def test_shard_i_is_data_chunk_i(self, profile):
        """Every plugin here stores data chunk i raw and whole as shard
        i, so the ranges read back the logical bytes."""
        ec = create_erasure_code(profile)
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, 10_000, dtype=np.uint8).tobytes()
        shards = ec.encode(set(range(ec.k + ec.m)), payload)
        for off, ln in [(0, 1), (123, 4096), (2500, 5000), (9000, 4096)]:
            ranges = ec.data_extents(len(payload), [(off, ln)])
            got = b"".join(shards[s].tobytes()[a:a + n]
                           for s, (a, n) in sorted(ranges.items()))
            assert got == payload[off:off + ln]

    def test_other_chunk_layout_falls_back(self):
        class Interleaved(ErasureCodeJerasure):
            def encode_prepare(self, data):
                flat = super().encode_prepare(data).reshape(-1)
                return flat.reshape(-1, self.k).T.copy()

        ec = Interleaved(create_erasure_code(
            {"plugin": "jerasure", "k": 4, "m": 2}).profile)
        assert ec.data_extents(4 * MIB, [(0, 4 * KIB)]) is None


# ---------------------------------------------------------- the OSD path

COUNTERS = ("op_r_extent", "op_r_degraded", "msgr_recv_bytes")
TIME_AVGS = ("op_subread_lat", "op_decode_lat")


def _counts(c) -> dict:
    out = dict.fromkeys(COUNTERS + TIME_AVGS, 0)
    for i, o in c.osds.items():
        d = admin_command(o.admin_socket.path, "perf dump")[f"osd.{i}"]
        for n in COUNTERS:
            out[n] += d[n]
        for n in TIME_AVGS:
            out[n] += d[n]["avgcount"]
    return out


@contextlib.contextmanager
def _delta(c):
    before, out = _counts(c), {}
    yield out
    after = _counts(c)
    out.update({n: after[n] - before[n] for n in after})


@contextlib.contextmanager
def _sent(c, kind):
    """Messages of ``kind`` the OSDs send while the block runs."""
    sent = []
    osds = list(c.osds.values())
    for osd in osds:
        def spy(o, msg, _send=osd.send_to_osd):
            if isinstance(msg, kind):
                sent.append(msg)
            _send(o, msg)
        osd.send_to_osd = spy
    try:
        yield sent
    finally:
        for osd in osds:
            del osd.send_to_osd


def _placement(r, io, oid):
    m = r.objecter.osdmap
    pgid = m.raw_pg_to_pg(m.object_locator_to_pg(oid, io.pool_id))
    _up, _upp, acting, primary = m.pg_to_up_acting_osds(pgid)
    return pgid, list(acting), primary


def _remote_shard(r, io, oid) -> tuple:
    """(pgid, a data shard held off the primary, its holder)."""
    pgid, acting, primary = _placement(r, io, oid)
    s = next(s for s in (0, 1) if acting[s] != primary)
    return pgid, s, acting[s]


def _ec22(c, r, name, **pool):
    rc, outs, _ = r.monc.command({
        "prefix": "osd erasure-code-profile set", "name": "k2m2",
        "profile": ["plugin=jerasure", "technique=reed_sol_van",
                    "k=2", "m=2", "crush-failure-domain=osd"]})
    assert rc == 0, outs
    r.create_pool(name, pg_num=4, pool_type="erasure",
                  erasure_code_profile="k2m2", **pool)
    return r.open_ioctx(name)


BIG = bytes(np.random.default_rng(3).integers(
    0, 256, 2 * MIB, dtype=np.uint8))     # k=2: two 1 MiB shards


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_mons=1, n_osds=4)
    c.start()
    r = c.rados()
    io = _ec22(c, r, "ec22")
    cio = _ec22(c, r, "ec22c", compression_mode="aggressive",
                compression_algorithm="rle")
    c.wait_for_clean()
    io.write_full("big", BIG)
    yield c, r, io, cio
    r.shutdown()
    c.stop()


class TestRangedRead:
    @pytest.mark.parametrize("off,ln", [
        (0, 4 * KIB), (MIB + 8 * KIB, 4 * KIB), (MIB - 2 * KIB, 4 * KIB),
        (2 * MIB - 100, 4 * KIB), (MIB + 5, 0),
    ], ids=["shard0", "shard1", "straddles", "past_end", "zero_length"])
    def test_serves_the_touched_ranges(self, cluster, off, ln):
        c, r, io, _cio = cluster
        _pgid, acting, primary = _placement(r, io, "big")
        touched = {x // MIB for x in range(off, min(off + ln, 2 * MIB))}
        with _delta(c) as d, _sent(c, M.MOSDECSubOpRead) as sent:
            assert io.read("big", ln, off) == BIG[off:off + ln]
        assert len(sent) == sum(acting[s] != primary for s in touched)
        assert all(m.len <= ln for m in sent)
        assert d["op_r_extent"] == d["op_subread_lat"] == 1
        assert d["op_decode_lat"] == d["op_r_degraded"] == 0

    def test_stat_beside_a_ranged_read(self, cluster):
        _c, _r, io, _cio = cluster
        res, _ = io._sync("big", [{"op": "stat"},
                                  {"op": "read", "off": 7, "len": 9}],
                          raw=True)
        assert res[0]["size"] == len(BIG)
        assert res[1]["data"] == BIG[7:16]

    def test_4k_read_receives_its_range_not_the_shard(self, cluster):
        c, r, io, _cio = cluster
        _pgid, s, _holder = _remote_shard(r, io, "big")
        off = s * MIB + 12 * KIB
        with _delta(c) as d:
            assert io.read("big", 4 * KIB, off) == BIG[off:off + 4 * KIB]
        assert d["msgr_recv_bytes"] < 64 * KIB
        with _delta(c) as whole:
            assert io.read("big") == BIG
        assert whole["msgr_recv_bytes"] > MIB
        assert whole["op_r_extent"] == 0

    def test_read_racing_a_write_falls_back(self, cluster):
        c, r, io, _cio = cluster
        old, new = b"o" * 64 * KIB, b"n" * 64 * KIB
        io.write_full("racy", old)
        pgid, _acting, primary = _placement(r, io, "racy")
        osd = c.osds[primary]
        held = []

        def hold(o, msg, _send=osd.send_to_osd):
            if isinstance(msg, M.MOSDECSubOpWrite):
                held.append((o, msg))
            else:
                _send(o, msg)
        osd.send_to_osd = hold
        try:
            comp = io.aio_write_full("racy", new)
            deadline = time.monotonic() + 10
            while not held and time.monotonic() < deadline:
                time.sleep(0.01)
            assert held and "racy" in osd.pgs[pgid].backend._rmw
            with _delta(c) as d:
                assert io.read("racy", 4 * KIB, 40 * KIB) == old[:4 * KIB]
            assert d["op_r_extent"] == 0
        finally:
            del osd.send_to_osd
            with osd.lock:
                for o, msg in held:
                    osd.send_to_osd(o, msg)
        assert comp.wait_for_complete(10) and comp.rc == 0
        with _delta(c) as d:
            assert io.read("racy", 4 * KIB, 40 * KIB) == new[:4 * KIB]
        assert d["op_r_extent"] == 1

    def test_corrupt_remote_shard_fails_with_eio(self, cluster):
        c, r, io, _cio = cluster
        data = bytes(range(256)) * 256            # 64 KiB: 32 KiB shards
        io.write_full("rot", data)
        pgid, s, holder = _remote_shard(r, io, "rot")
        osd = c.osds[holder]
        with osd.lock:
            osd.store.queue_transaction(Transaction().write(
                f"{pgid}s{s}", "rot", 1000, b"BITROT"))
        with pytest.raises(Error) as e:
            io.read("rot", 4 * KIB, s * 32 * KIB + 8 * KIB)
        assert e.value.rc == -5

    def test_stale_remote_shard_is_never_served(self, cluster):
        """A holder whose shard is at another version (a stale stray)
        answers with bytes that pass its own hinfo check: the primary's
        version check sends the read to the whole-shard gather."""
        c, r, io, _cio = cluster
        data = bytes(range(256)) * 256            # 64 KiB: 32 KiB shards
        io.write_full("stale", data)
        pgid, s, holder = _remote_shard(r, io, "stale")
        osd, cid, old = c.osds[holder], f"{pgid}s{s}", bytes(32 * KIB)
        with osd.lock:
            meta = json.loads(bytes(osd.store.getattr(cid, "stale", "_")))
            meta.update(version=[0, 1], hinfo=crc32c(old))
            osd.store.queue_transaction(Transaction().write(
                cid, "stale", 0, old).setattrs(
                cid, "stale", {"_": json.dumps(meta).encode()}))
        off = s * 32 * KIB + 8 * KIB
        with _delta(c) as d:
            assert io.read("stale", 4 * KIB, off) == data[off:off + 4 * KIB]
        assert d["op_r_extent"] == 0

    def test_compressed_pool_falls_back(self, cluster):
        c, _r, _io, cio = cluster
        runs = b"".join(bytes([i]) * 700 for i in range(60))
        cio.write_full("sealed", runs)
        # the second range lies inside the stored (sealed) bytes, so
        # only the meta's seal keeps it off the ranged path
        for off, ln in [(9000, 4 * KIB), (10, 100)]:
            with _delta(c) as d:
                assert cio.read("sealed", ln, off) == runs[off:off + ln]
            assert d["op_r_extent"] == 0 and d["op_subread_lat"] == 1


def test_lost_shard_falls_back_and_decodes():
    c = MiniCluster(n_mons=1, n_osds=4)
    c.start()
    try:
        r = c.rados()
        io = _ec22(c, r, "ec22")
        c.wait_for_clean()
        data = bytes(range(256)) * 256
        io.write_full("obj", data)
        _pgid, s, victim = _remote_shard(r, io, "obj")
        off = s * 32 * KIB + 4 * KIB
        with _delta(c) as d:
            assert io.read("obj", 4 * KIB, off) == data[off:off + 4 * KIB]
        assert d["op_r_extent"] == 1 and d["op_decode_lat"] == 0
        c.kill_osd(victim)
        r.mon_command({"prefix": "osd down", "ids": [victim]})
        c.wait_for_osd_down(victim, timeout=60)
        with _delta(c) as d:
            assert io.read("obj", 4 * KIB, off) == data[off:off + 4 * KIB]
        assert d["op_r_extent"] == 0
        assert d["op_decode_lat"] == d["op_r_degraded"] == 1
    finally:
        c.stop()
