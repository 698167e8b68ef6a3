"""ObjectStore layer tests.

Mirrors the reference's ``src/test/objectstore/store_test.cc`` pattern:
one suite parameterized over every backend (MemStore + WALStore), plus
WAL-specific durability cases (replay, torn tail) the reference covers
via BlueStore fsck/mount tests.
"""

import hashlib

import pytest

from ceph_tpu.os_store import MemStore, Transaction, WALStore, walog


@pytest.fixture(params=["mem", "wal"])
def store(request, tmp_path):
    if request.param == "mem":
        s = MemStore()
    else:
        s = WALStore(str(tmp_path / "store.wal"))
        s.mount()
    s.mkfs()
    yield s
    s.umount()


CID = "1.0"


def test_touch_write_read(store):
    t = Transaction().create_collection(CID)
    t.touch(CID, "a").write(CID, "b", 0, b"hello")
    store.apply_transaction(t)
    assert store.exists(CID, "a") and store.exists(CID, "b")
    assert store.read(CID, "b") == b"hello"
    assert store.read(CID, "b", 1, 3) == b"ell"
    assert store.stat(CID, "b")["size"] == 5
    assert store.stat(CID, "a")["size"] == 0


def test_write_extends_with_zero_fill(store):
    store.apply_transaction(
        Transaction().create_collection(CID).write(CID, "o", 4, b"xy"))
    assert store.read(CID, "o") == b"\0\0\0\0xy"
    store.apply_transaction(Transaction().write(CID, "o", 0, b"AB"))
    assert store.read(CID, "o") == b"AB\0\0xy"


def test_zero_truncate_remove(store):
    store.apply_transaction(
        Transaction().create_collection(CID).write(CID, "o", 0, b"abcdef"))
    store.apply_transaction(Transaction().zero(CID, "o", 1, 2))
    assert store.read(CID, "o") == b"a\0\0def"
    store.apply_transaction(Transaction().truncate(CID, "o", 3))
    assert store.read(CID, "o") == b"a\0\0"
    store.apply_transaction(Transaction().truncate(CID, "o", 5))
    assert store.read(CID, "o") == b"a\0\0\0\0"
    store.apply_transaction(Transaction().remove(CID, "o"))
    assert not store.exists(CID, "o")
    with pytest.raises(KeyError):
        store.read(CID, "o")


def test_attrs_and_omap(store):
    t = Transaction().create_collection(CID)
    t.setattrs(CID, "o", {"_": b"oi", "snapset": b"ss"})
    t.omap_setkeys(CID, "o", {"k1": b"v1", "k2": b"v2"})
    store.apply_transaction(t)
    assert store.getattr(CID, "o", "_") == b"oi"
    assert store.getattrs(CID, "o") == {"_": b"oi", "snapset": b"ss"}
    assert store.omap_get(CID, "o") == {"k1": b"v1", "k2": b"v2"}
    store.apply_transaction(
        Transaction().rmattr(CID, "o", "snapset")
        .omap_rmkeys(CID, "o", ["k1"]))
    assert store.getattrs(CID, "o") == {"_": b"oi"}
    assert store.omap_get(CID, "o") == {"k2": b"v2"}


def test_clone(store):
    store.apply_transaction(
        Transaction().create_collection(CID)
        .write(CID, "src", 0, b"data")
        .setattrs(CID, "src", {"a": b"1"}))
    store.apply_transaction(Transaction().clone(CID, "src", "dst"))
    assert store.read(CID, "dst") == b"data"
    assert store.getattr(CID, "dst", "a") == b"1"
    # clone is a snapshot, not a link
    store.apply_transaction(Transaction().write(CID, "src", 0, b"DATA"))
    assert store.read(CID, "dst") == b"data"


def test_collections(store):
    store.apply_transaction(
        Transaction().create_collection("1.0").create_collection("1.1")
        .touch("1.1", "x"))
    assert store.list_collections() == ["1.0", "1.1"]
    assert store.list_objects("1.1") == ["x"]
    assert store.collection_exists("1.0")
    store.apply_transaction(Transaction().remove_collection("1.0"))
    assert store.list_collections() == ["1.1"]


def test_commit_callbacks_in_order(store):
    got = []
    store.apply_transaction(Transaction().create_collection(CID))
    for i in range(10):
        store.queue_transaction(
            Transaction().write(CID, "o", i, bytes([i])),
            (lambda i=i: got.append(i)))
    if hasattr(store, "flush_commits"):
        # ack-after-commit: a WALStore parks callbacks until the
        # group-commit fsync; drain the barrier before asserting
        assert store.flush_commits(5)
    else:
        assert store.finisher.wait_for_empty(5)
    assert got == list(range(10))


def test_transaction_wire_roundtrip(store):
    t = Transaction().create_collection(CID)
    t.write(CID, "o", 3, b"\x00\xff") \
     .setattrs(CID, "o", {"k": b"\x01\x02"}) \
     .omap_setkeys(CID, "o", {"mk": b"\x03"}) \
     .omap_rmkeys(CID, "o", ["gone"]) \
     .zero(CID, "o", 0, 1).truncate(CID, "o", 4) \
     .clone(CID, "o", "o2").remove(CID, "o2").touch(CID, "t")
    t2 = Transaction.from_record(b"".join(t.to_record()))
    assert t2.ops == t.ops
    store.apply_transaction(t2)
    assert store.read(CID, "o") == b"\0\0\0\x00"


# a transaction of each op kind; the maps carry bytes and user keys that
# start with NUL, as the segment codec escapes them
_OP_KINDS = {
    "touch": lambda t: t.touch(CID, "o"),
    "write": lambda t: t.write(CID, "o", 7, b"\x00\xffraw"),
    "write_empty": lambda t: t.write(CID, "o", 0, b""),
    "zero": lambda t: t.zero(CID, "o", 1, 2),
    "truncate": lambda t: t.truncate(CID, "o", 3),
    "remove": lambda t: t.remove(CID, "o"),
    "setattrs": lambda t: t.setattrs(
        CID, "o", {"_": b"{}", "\x00seg": b"\x00", "\x00k": b"v",
                   "plain": b"\xfe"}),
    "rmattr": lambda t: t.rmattr(CID, "o", "\x00k"),
    "omap_setkeys": lambda t: t.omap_setkeys(
        CID, "o", {"\x00\x00seg": b"x", "k": b"\x00" * 3}),
    "omap_rmkeys": lambda t: t.omap_rmkeys(CID, "o", ["\x00seg", "k"]),
    "clone": lambda t: t.clone(CID, "o", "o2"),
    "coll_move": lambda t: t.coll_move(CID, "o", "1.1"),
    "create_collection": lambda t: t.create_collection(CID),
    "remove_collection": lambda t: t.remove_collection(CID),
    "dedup_ingest": lambda t: t.dedup_ingest(CID, "fp", b"chunk\x00"),
    "dedup_release": lambda t: t.dedup_release(CID, "fp"),
}


@pytest.mark.parametrize("kind", sorted(_OP_KINDS))
def test_record_roundtrip_every_op_kind(kind):
    t = _OP_KINDS[kind](Transaction())
    got = Transaction.from_record(b"".join(t.to_record()))
    assert got.ops == t.ops
    for a, b in zip(got.ops[0], t.ops[0]):
        assert type(a) is type(b)


def test_record_keeps_bytes_raw():
    """A 512 KiB shard write's record is its bytes plus a small front:
    no hex (which would double it)."""
    data = bytes(range(256)) * 2048
    t = Transaction().write(CID, "o", 0, data)
    parts = t.to_record()
    assert any(p is data for p in parts)      # the shard, uncopied
    assert len(walog.encode_record(*parts)) <= len(data) + 4096


# JsonMessage's bytes on the wire, pinned at their form before the codec
# moved to core.encoding (sha256 of the encoding, seq 5)
_MSG_DIGESTS = {
    "no_bytes":
        "5742f6e479ae3107466e81e0248b096b471252b98f222d3efa1ef3dc61f5f2f5",
    "bytes":
        "555c113e108423894a52d45dfb476c47d56f47ab11c467d0e13d7e6bfab07f81",
    "nul_keys":
        "c2a6a9a7344ca1120f89e36fb5a8fda1ecf8c1b5fc53a5dc21e03946de0a0e3d",
    "txn":
        "629168338141f5b11d83fcf2b0ebcd9aaddb009d762d63c695539e4eb6561bf8",
}


@pytest.mark.parametrize("name", sorted(_MSG_DIGESTS))
def test_json_message_bytes_unchanged(name):
    from ceph_tpu.mon.messages import MMonCommand
    from ceph_tpu.osd import messages as M
    msg = {
        "no_bytes": lambda: MMonCommand(tid=9, cmd={
            "prefix": "osd pool create", "pool": "p", "pg_num": 8}),
        "bytes": lambda: M.MOSDOp(
            tid=1, client="c", pgid="1.0", oid="o", epoch=3,
            ops=[{"op": "write_full", "data": b"\x00\x01payload"}],
            flags=0),
        "nul_keys": lambda: M.MOSDOp(tid=2, ops=[
            {"op": "x", "kv": {"\x00seg": 0, "\x00k": b"v"}},
            {"op": "write", "data": b"bulk"}]),
        "txn": lambda: M.MOSDECSubOpWrite(
            reqid="c:1", pgid="1.0", shard=0, epoch=4,
            txn=[["write", "1.0s0", "o", 0, b"chunk"],
                 ["setattrs", "1.0s0", "o", {"_": b"{}"}]],
            version=[4, 1]),
    }[name]()
    msg.seq = 5
    assert hashlib.sha256(msg.encode()).hexdigest() == _MSG_DIGESTS[name]


class TestWALDurability:
    def test_remount_replays(self, tmp_path):
        path = str(tmp_path / "s.wal")
        s = WALStore(path)
        s.mkfs()
        s.apply_transaction(
            Transaction().create_collection(CID)
            .write(CID, "o", 0, b"persist")
            .setattrs(CID, "o", {"a": b"x"})
            .omap_setkeys(CID, "o", {"k": b"v"}))
        s.umount()
        s2 = WALStore(path)
        s2.mount()
        assert s2.read(CID, "o") == b"persist"
        assert s2.getattr(CID, "o", "a") == b"x"
        assert s2.omap_get(CID, "o") == {"k": b"v"}
        s2.umount()

    def test_torn_tail_recovers_prefix(self, tmp_path):
        path = str(tmp_path / "s.wal")
        s = WALStore(path)
        s.mkfs()
        s.apply_transaction(
            Transaction().create_collection(CID).write(CID, "o", 0, b"ok"))
        s.umount()
        with open(path, "ab") as f:          # simulate a torn write
            f.write(b'[["write", "1.0", "o", 0, {"he')
        s2 = WALStore(path)
        s2.mount()
        assert s2.read(CID, "o") == b"ok"    # prefix survived
        s2.umount()
