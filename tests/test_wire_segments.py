"""Byte values ride a message as raw data segments after its JSON front
(the msgr2 front / data segments shape): the codec, the messenger that
frames it, and the librados edge that keeps a hex op-dict form for its
callers while the OSD data path carries bytes.
"""

import json
import os
import struct
import threading
import time

import pytest

from ceph_tpu.core.auth import ClusterAuth
from ceph_tpu.core.encoding import DecodeError, Encoder
from ceph_tpu.mon.messages import MMonCommand, MOSDMapMsg
from ceph_tpu.msg import Dispatcher, Messenger
from ceph_tpu.os_store import Transaction
from ceph_tpu.osd import messages as M

KIB, MIB = 1 << 10, 1 << 20


def _roundtrip(msg):
    return type(msg).decode(msg.encode())


def _front_and_segments(msg):
    """(front JSON text, segment count) of an encoded message."""
    from ceph_tpu.core.encoding import Decoder
    dec = Decoder(msg.encode())
    dec.u16(), dec.u64(), dec.u8()
    with dec.struct_block(type(msg).VERSION) as blk:
        return blk.dec.string(), blk.dec.u32()


class TestCodec:
    @pytest.mark.parametrize("ops", [
        [{"op": "write_full", "data": b"\x00\x01payload"}],
        [{"op": "write", "off": 4, "data": b"a" * 100},
         {"op": "setxattr", "name": "n", "data": b"v"},
         {"op": "omap_set", "kv": {"k1": b"x", "k2": b"\xff" * 3}}],
        [{"op": "x", "nest": {"a": {"b": [b"deep", {"c": b"deeper"}]}}}],
    ], ids=["one", "list_of_ops", "dict_of_dicts"])
    def test_bytes_at_any_depth_roundtrip(self, ops):
        msg = M.MOSDOp(tid=1, client="c", pgid="1.0", oid="o", epoch=3,
                       ops=ops, flags=0)
        got = _roundtrip(msg)
        assert got.ops == ops
        assert got.tid == 1 and got.epoch == 3
        assert got.data_bytes == msg.data_bytes > 0

    @pytest.mark.parametrize("value,expect", [
        (b"", b""),
        (bytearray(b"mutable"), b"mutable"),
        (memoryview(b"viewed bytes")[2:], b"ewed bytes"),
    ], ids=["empty", "bytearray", "memoryview"])
    def test_buffer_kinds_roundtrip_as_bytes(self, value, expect):
        got = _roundtrip(M.MOSDECSubOpReadReply(tid=1, rc=0, data=value))
        assert type(got.data) is bytes and got.data == expect

    @pytest.mark.parametrize("with_bytes", [False, True])
    @pytest.mark.parametrize("user", [
        {"\x00seg": 0}, {"\x00seg": 0, "x": 1}, {"\x00\x00seg": 1},
        {"\x00": "nul key"}, {"text": "a \x00 and \\u0000 in a string"},
    ])
    def test_marker_shaped_user_dict_unchanged(self, user, with_bytes):
        ops = [{"op": "x", "kv": user}]
        if with_bytes:
            ops.append({"op": "write_full", "data": b"bulk"})
        got = _roundtrip(M.MOSDOp(tid=1, ops=ops))
        assert got.ops == ops

    def test_message_without_bytes_has_zero_segments(self):
        cmd = {"prefix": "osd pool create", "pool": "p", "pg_num": 8,
               "nested": {"a": [1, 2, {"b": None}]}}
        msg = MMonCommand(tid=9, cmd=cmd)
        front, nsegs = _front_and_segments(msg)
        assert nsegs == 0 and msg.data_bytes == 0
        assert front == json.dumps({"tid": 9, "cmd": cmd})
        assert len(msg.encode_parts()) == 1
        got = _roundtrip(msg)
        assert got.cmd == cmd and got.tid == 9

    def test_old_decoder_refuses_segments_only(self):
        """compat 2 only where references would be misread by a v1
        decoder; a message without bytes stays readable to one."""
        plain = MOSDMapMsg(epoch=3, osdmap={"epoch": 3},
                           newest=3).encode()
        seg = M.MOSDOp(tid=1, ops=[{"op": "write_full",
                                    "data": b"x"}]).encode()
        try:
            M.MOSDOp.VERSION = MOSDMapMsg.VERSION = 1
            assert MOSDMapMsg.decode(plain).osdmap == {"epoch": 3}
            with pytest.raises(DecodeError):
                M.MOSDOp.decode(seg)
        finally:
            del M.MOSDOp.VERSION, MOSDMapMsg.VERSION

    def test_encoder_parts_keep_segments_uncopied(self):
        big = os.urandom(100 * KIB)
        enc = Encoder()
        with enc.struct_block(1, 1):
            enc.string("front")
            enc.blob_ref(big)
            enc.u8(7)
        parts = enc.parts()
        assert any(p is big for p in parts)
        assert b"".join(parts) == bytes(enc)
        # the struct length covers the segment
        assert struct.unpack_from("<I", bytes(enc), 2)[0] == \
            len(bytes(enc)) - 6

    def test_transaction_wire_form_keeps_bytes(self):
        t = Transaction()
        t.write("1.0s0", "o", 0, b"chunk")
        t.setattrs("1.0s0", "o", {"_": b"{}", "user": b"\x00v"})
        t.omap_setkeys("1.0s0", "o", {"k": b"val"})
        msg = M.MOSDECSubOpWrite(reqid="c:1", txn=t.to_wire())
        got = Transaction.from_wire(_roundtrip(msg).txn)
        assert got.ops == t.ops
        assert msg.data_bytes == len(b"chunk" b"{}" b"\x00v" b"val")

    def test_bulk_messages_encode_near_their_payload(self):
        data = os.urandom(4 * MIB)
        op = M.MOSDOp(tid=1, client="client.admin:0123456789ab",
                      pgid="1.7f", oid="benchmark_object_123", epoch=42,
                      ops=[{"op": "write_full", "data": data}], flags=0,
                      snapc=None, dmc={"delta": 1, "rho": 1},
                      trace=None, qos_client=None, retry_attempt=0)
        assert len(op.encode()) <= 4 * MIB + 4 * KIB
        chunk = os.urandom(512 * KIB)
        t = Transaction()
        t.truncate("1.7fs3", "benchmark_object_123", 0)
        t.write("1.7fs3", "benchmark_object_123", 0, chunk)
        t.setattrs("1.7fs3", "benchmark_object_123", {"_": json.dumps(
            {"version": [42, 17], "size": 4 * MIB,
             "hinfo": 123456789}).encode()})
        sub = M.MOSDECSubOpWrite(
            reqid="client.admin:0123456789ab:1", pgid="1.7f", shard=3,
            epoch=42, txn=t.to_wire(), version=[42, 17],
            log_entries=[{"op": "modify", "oid": "benchmark_object_123",
                          "version": [42, 17], "prior_version": [0, 0],
                          "reqid": "client.admin:0123456789ab:1",
                          "mtime": time.time()}],
            pg_info={"pgid": "1.7f", "last_update": [42, 17],
                     "last_complete": [42, 17], "les": 40,
                     "history": {"same_interval_since": 40}},
            trace=None)
        assert len(sub.encode()) <= 512 * KIB + 4 * KIB


class _Collector(Dispatcher):
    def __init__(self):
        self.got = []
        self.event = threading.Event()

    def ms_dispatch(self, msg):
        self.got.append(msg)
        self.event.set()
        return True


class _Perf:
    def __init__(self):
        self.c = {}

    def inc(self, name, by=1):
        self.c[name] = self.c.get(name, 0) + by

    def tinc(self, name, dt):
        self.inc(name, dt)


@pytest.mark.parametrize("mode", ["crc", "secure"])
def test_segmented_message_over_live_messengers(mode):
    auth = ClusterAuth(b"sixteen byte key")
    server = Messenger("osd.0", **auth.msgr_kwargs("osd.0", mode))
    client = Messenger("osd.1", **auth.msgr_kwargs("osd.1", mode))
    server.perf, client.perf = _Perf(), _Perf()
    col = _Collector()
    server.add_dispatcher(col)
    try:
        con = client.connect_to(server.bind())
        assert con.secure == (mode == "secure")
        small, big = b"attr", os.urandom(MIB + 3)
        con.send_message(M.MOSDECSubOpReadReply(
            tid=5, pgid="1.0", shard=2, epoch=7, rc=0, data=big,
            attrs={"_": small}, from_osd=1))
        assert col.event.wait(10)
        got = col.got[0]
        assert got.data == big and got.attrs == {"_": small}
        assert got.tid == 5 and got.shard == 2
        data = len(big) + len(small)
        assert client.perf.c["msgr_send_data_bytes"] == data
        assert server.perf.c["msgr_recv_data_bytes"] == data
        assert data < server.perf.c["msgr_recv_bytes"] < data + 512
    finally:
        client.shutdown()
        server.shutdown()


# ------------------------------------------------------ the librados edge

@pytest.fixture(scope="module")
def ec_io():
    from ceph_tpu.vstart import MiniCluster
    c = MiniCluster(n_mons=1, n_osds=3)
    c.start()
    r = c.rados()
    try:
        r.monc.command({"prefix": "osd erasure-code-profile set",
                        "name": "segprof",
                        "profile": ["k=2", "m=1",
                                    "technique=reed_sol_van"]})
        r.create_pool("segpool", pg_num=4, pool_type="erasure",
                      erasure_code_profile="segprof")
        io = r.open_ioctx("segpool")
        c.wait_for_clean()
        yield c, io
    finally:
        r.shutdown()
        c.stop()


class TestLibradosEdge:
    def test_public_and_op_dict_forms_read_each_other(self, ec_io):
        _c, io = ec_io
        a, b = os.urandom(5000), os.urandom(3000)
        io.write_full("pub", a)
        res, _ = io._sync("pub", [{"op": "read", "off": 0}])
        assert bytes.fromhex(res[0]["data"]) == a
        io._sync("dict", [{"op": "write_full", "data": b.hex()}])
        assert io.read("dict") == b
        comp = io._aio("dict", [{"op": "read", "off": 0}])
        assert comp.wait_for_complete(10) and comp.rc == 0
        assert bytes.fromhex(comp.results[0]["data"]) == b
        comp = io.aio_read("pub")
        assert comp.wait_for_complete(10) and comp.rc == 0
        assert bytes.fromhex(comp.results[0]["data"]) == a

    def test_attrs_omap_and_class_calls_return_bytes(self, ec_io):
        _c, io = ec_io
        io.write_full("meta", b"payload")
        io.setxattr("meta", "color", b"\x00blue")
        attrs = io.getxattrs("meta")
        assert attrs["color"] == b"\x00blue"
        assert io.getxattr("meta", "color") == b"\x00blue"
        io.omap_set("meta", {"k": b"\xffv"})
        assert io.omap_get("meta") == {"k": b"\xffv"}
        res, _ = io._sync("meta", [{"op": "omap_get"}])
        assert res[0]["kv"] == {"k": b"\xffv".hex()}
        assert io.execute("meta", "version", "inc") == b"1"
        assert io.execute("meta", "version", "read") == b"1"

    def test_full_object_write_rides_as_data_segments(self, ec_io):
        c, io = ec_io

        def recv_data():
            return sum(o.perf.dump()[f"osd.{i}"]["msgr_recv_data_bytes"]
                       for i, o in c.osds.items())
        before = recv_data()
        data = os.urandom(256 * KIB)
        io.write_full("bulk", data)
        # the primary's copy of the payload and the remote shards
        assert recv_data() - before >= len(data)
        assert io.read("bulk") == data

    def test_sub_read_without_a_range_is_served_whole(self, ec_io):
        c, io = ec_io
        io.write_full("whole", os.urandom(64 * KIB))   # 32 KiB shards
        m = io.rados.objecter.osdmap
        pgid = m.raw_pg_to_pg(m.object_locator_to_pg("whole", io.pool_id))
        holder = c.osds[m.pg_to_up_acting_osds(pgid)[2][1]]
        ranged = M.MOSDECSubOpRead(tid=1, pgid=str(pgid), shard=1,
                                   epoch=holder.osdmap.epoch, oid="whole",
                                   attrs=True, off=5, len=7)
        assert (_roundtrip(ranged).off, _roundtrip(ranged).len) == (5, 7)
        # an older sender's MOSDECSubOpRead has no range fields
        ranged.FIELDS = tuple(f for f in M.MOSDECSubOpRead.FIELDS
                              if f not in ("off", "len"))
        old = _roundtrip(ranged)
        assert old.off is None and old.len is None
        replies = []
        holder.send_to_osd = lambda _o, msg: replies.append(msg)
        try:
            with holder.lock:
                holder.pgs[pgid].backend.handle_sub_read(old)
                stored = holder.store.read(f"{pgid}s1", "whole")
        finally:
            del holder.send_to_osd
        (reply,) = replies
        assert reply.rc == 0 and len(stored) == 32 * KIB
        assert reply.data == stored
