"""Storage-efficiency subsystem — codec registry, the batch engine's
compression + fingerprint lanes, the dedup refcount layer, and the
pool plumbing end to end.

The contract mirrors the batch-engine suite's shape:

1. **Bit-identity** — every sealed blob expands to its exact logical
   bytes; pass-through engages on incompressible data; batched lane
   results equal the synchronous unbatched path; a cluster with the
   lane disabled stores byte-identical objects to one with it on.
2. **Edge cases** — empty objects, sub-chunk objects, incompressible
   payloads, and oversized payloads through the streaming segment
   path all round-trip.
3. **Pool plumbing** — compression_mode / compression_algorithm /
   dedup_enable flow mon → OSDMap → PG write/read paths, settable at
   create and via `osd pool set`, with validation and audit-log
   coverage.
4. **Refcount balance** — duplicate objects share chunks; overwrites
   and deletes release references; the index balances to zero (the
   MiniCluster teardown leak check enforces this for every test that
   touches a dedup pool).
"""

import io as _io
import json
import sys
import time

import numpy as np
import pytest

from ceph_tpu.compress import dedup as dd
from ceph_tpu.compress.chunker import Chunker, fingerprint
from ceph_tpu.compress.codec import CodecError
from ceph_tpu.compress.registry import create_codec, list_codecs
from ceph_tpu.osd.batch_engine import BatchEngine
from ceph_tpu.tools.ceph import main as ceph_main
from ceph_tpu.tools.rados import main as rados_main
from ceph_tpu.vstart import MiniCluster


def _payload(n, seed=0):
    """Byte-varied (incompressible-ish) payload."""
    return bytes((i * 131 + seed * 17 + 7) & 0xFF for i in range(n))


def _runs(n, seed=0):
    """Run-structured (compressible) payload."""
    out = bytearray()
    v = seed * 2654435761 + 1
    while len(out) < n:
        v = (v * 1103515245 + 12345) & 0x7FFFFFFF
        out += bytes([v & 0xFF]) * (16 + (v >> 8) % 96)
    return bytes(out[:n])


# ---------------------------------------------------------------- codecs

class TestCodecs:
    def test_registry_lists_builtins(self):
        names = list_codecs()
        assert "rle" in names
        assert create_codec("rle").name == "rle"
        with pytest.raises(CodecError):
            create_codec("no-such-codec")

    @pytest.mark.parametrize("size", [0, 1, 31, 4096, 70000])
    def test_round_trip_all_codecs(self, size):
        for name in list_codecs():
            codec = create_codec(name)
            for data in (_runs(size), _payload(size, 3)):
                blob = codec.compress(data)
                assert codec.decompress(blob, len(data)) == data, \
                    f"{name} diverged at {size}"

    def test_rle_shrinks_runs(self):
        codec = create_codec("rle")
        data = _runs(16384)
        assert len(codec.compress(data)) < len(data)


# ---------------------------------------------------------------- lane

class TestCompressionLane:
    def test_compressible_seals_and_expands(self):
        eng = BatchEngine("t")          # flush_ms=0 → immediate mode
        codec = create_codec("rle")
        data = _runs(8192)
        blob, hdr = eng.submit_compress(codec, data).result()
        assert hdr is not None and hdr["algo"] == "rle"
        assert len(blob) < len(data)
        assert eng.decompress(blob, hdr) == data

    def test_incompressible_passes_through(self):
        eng = BatchEngine("t")
        codec = create_codec("rle")
        data = _payload(4096, 9)
        blob, hdr = eng.submit_compress(codec, data).result()
        assert hdr is None and bytes(blob) == data

    def test_force_mode_always_stores_compressed(self):
        eng = BatchEngine("t")
        codec = create_codec("rle")
        data = _payload(512, 4)
        blob, hdr = eng.submit_compress(codec, data,
                                        mode="force").result()
        assert hdr is not None
        assert eng.decompress(blob, hdr) == data

    @pytest.mark.parametrize("size", [0, 1, 17])
    def test_tiny_payloads(self, size):
        eng = BatchEngine("t")
        codec = create_codec("rle")
        data = _runs(size)
        blob, hdr = eng.submit_compress(codec, data).result()
        got = bytes(blob) if hdr is None else eng.decompress(blob, hdr)
        assert got == data

    def test_oversized_segment_path(self):
        eng = BatchEngine("t", comp_segment_bytes=2048)
        codec = create_codec("rle")
        data = _runs(10000) + _payload(2048, 5) + _runs(4000, 2)
        blob, hdr = eng.submit_compress(codec, data).result()
        assert hdr is not None and hdr["seg"] == 2048
        assert len(hdr["segs"]) == (len(data) + 2047) // 2048
        assert eng.decompress(blob, hdr) == data
        # incompressible oversized payload passes through whole
        rnd = _payload(9000, 7)
        blob, hdr = eng.submit_compress(codec, rnd).result()
        assert hdr is None and bytes(blob) == rnd

    def test_batched_matches_unbatched(self):
        on = BatchEngine("on", flush_ms=25.0, max_ops=64)
        off = BatchEngine("off", enabled=False)
        codec = create_codec("rle")
        payloads = [_runs(5000, s) for s in range(6)] + \
            [_payload(3000, 8), b"", _runs(64, 1)]
        comps = [on.submit_compress(codec, p) for p in payloads]
        on.drain()
        for comp, p in zip(comps, payloads):
            assert comp.result() == \
                off.submit_compress(codec, p).result()
        on.stop()


class TestFingerprintLane:
    def test_spans_tile_and_match_host(self):
        eng = BatchEngine("t")
        ch = Chunker(avg_size=1024)
        data = _runs(20000, 3)
        spans = eng.submit_fingerprint(ch, data).result()
        assert spans[0][0] == 0
        assert sum(ln for _o, ln, _f in spans) == len(data)
        for off, ln, fp in spans:
            assert fingerprint(data[off:off + ln]) == fp
        # host reference: same cuts, same digests
        assert [(o, ln) for o, ln, _ in spans] == ch.chunks(data)

    def test_duplicate_content_same_fingerprints(self):
        eng = BatchEngine("t")
        ch = Chunker(avg_size=1024)
        data = _runs(12000, 5)
        a = eng.submit_fingerprint(ch, data).result()
        b = eng.submit_fingerprint(ch, data).result()
        assert a == b

    def test_sub_chunk_and_empty(self):
        eng = BatchEngine("t")
        ch = Chunker(avg_size=4096)
        tiny = _payload(37, 2)
        spans = eng.submit_fingerprint(ch, tiny).result()
        assert spans == [(0, 37, fingerprint(tiny))]
        assert eng.submit_fingerprint(ch, b"").result() == []


# ---------------------------------------------------------------- cluster

@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_mons=1, n_osds=3)
    c.start()
    r = c.rados()
    r.create_pool("cpool", pg_num=4, size=3,
                  compression_mode="aggressive",
                  compression_algorithm="rle")
    r.create_pool("dpool", pg_num=4, size=3,
                  compression_mode="aggressive",
                  compression_algorithm="rle", dedup_enable=True)
    r.monc.command({"prefix": "osd erasure-code-profile set",
                    "name": "cprof",
                    "profile": ["k=2", "m=1",
                                "technique=reed_sol_van"]})
    r.create_pool("ecp", pg_num=4, pool_type="erasure",
                  erasure_code_profile="cprof",
                  compression_mode="aggressive",
                  compression_algorithm="rle")
    c.wait_for_clean()
    c._test_rados = r
    yield c
    r.shutdown()
    c.stop()


def _addrs(c):
    return ",".join(f"{a.host}:{a.port}"
                    for a in c.monmap.mons.values())


def _cli(main, c, *argv):
    old = sys.stdout
    sys.stdout = buf = _io.StringIO()
    try:
        rc = main(["-m", _addrs(c), *argv])
    finally:
        sys.stdout = old
    return rc, buf.getvalue()


def _stored(c, oid, skip_dedup=True):
    """{osd: (stored bytes, "_" meta json)} for every replica."""
    out = {}
    for i, osd in c.osds.items():
        with osd.lock:
            for cid in osd.store.list_collections():
                if skip_dedup and cid == dd.DEDUP_COLL:
                    continue
                if osd.store.exists(cid, oid):
                    out[i] = (bytes(osd.store.read(cid, oid)),
                              json.loads(bytes(
                                  osd.store.getattr(cid, oid, "_"))))
    return out


class TestClusterEfficiency:
    def test_compressed_pool_round_trip_and_rmw(self, cluster):
        io = cluster._test_rados.open_ioctx("cpool")
        runs = _runs(8000) + _payload(512, 3)
        io.write_full("obj1", runs)
        assert io.read("obj1") == runs
        rnd = _payload(4096, 9)            # incompressible
        io.write_full("obj2", rnd)
        assert io.read("obj2") == rnd
        io.write_full("obj3", b"")         # empty
        assert io.read("obj3") == b""
        # RMW on a sealed object: append then partial overwrite
        io.append("obj1", b"C" * 1000)
        io.write("obj1", b"XYZ", 10)
        want = bytearray(runs + b"C" * 1000)
        want[10:13] = b"XYZ"
        assert io.read("obj1") == bytes(want)
        # stat reports LOGICAL size; stored bytes shrank
        assert io.stat("obj1")["size"] == len(want)
        reps = _stored(cluster, "obj1")
        assert len(reps) == 3
        for data, meta in reps.values():
            assert meta["size"] == len(want)
            assert 0 < meta["stored"] < len(want)
            assert len(data) == meta["stored"]
        # incompressible object stored verbatim (no comp header)
        for data, meta in _stored(cluster, "obj2").values():
            assert "comp" not in meta and data == rnd

    def test_ec_compressed_pool(self, cluster):
        io = cluster._test_rados.open_ioctx("ecp")
        runs = _runs(6000, 7)
        io.write_full("e1", runs)
        assert io.read("e1") == runs
        io.append("e1", b"Z" * 777)        # EC RMW on sealed object
        assert io.read("e1") == runs + b"Z" * 777
        rnd = _payload(4096, 11)
        io.write_full("e2", rnd)           # passthrough
        assert io.read("e2") == rnd

    def test_dedup_share_and_balance_to_zero(self, cluster):
        c = cluster
        io = c._test_rados.open_ioctx("dpool")
        dup = _runs(15000, 9)
        io.write_full("d1", dup)
        io.write_full("d2", dup)
        assert io.read("d1") == dup and io.read("d2") == dup
        time.sleep(0.3)
        shared = 0
        for i, osd in c.osds.items():
            with osd.lock:
                probs = dd.verify_refcounts(osd.store)
                stats = dd.dedup_stats(osd.store)
            assert not probs, f"osd.{i}: {probs}"
            if stats["chunks"]:
                # two manifests over one chunk set
                assert stats["referenced_bytes"] \
                    > stats["stored_bytes"]
                shared += 1
        assert shared == 3
        # overwrite releases the old manifest's references
        io.write_full("d1", _payload(2000, 5))
        assert io.read("d1") == _payload(2000, 5)
        io.remove("d1")
        io.remove("d2")
        time.sleep(0.3)
        for i, osd in c.osds.items():
            with osd.lock:
                probs = dd.verify_refcounts(osd.store)
                refs = dd.index_refcounts(osd.store)
            assert not probs, f"osd.{i}: {probs}"
            assert not refs, f"osd.{i} refs not balanced: {refs}"
        assert c.dedup_leak_check() == []

    def test_pool_set_get_and_validation(self, cluster):
        r = cluster._test_rados
        r.create_pool("p_opts", pg_num=4, size=2)

        def mon(**cmd):
            return r.mon_command(cmd)

        rc, _, _ = mon(prefix="osd pool set", pool="p_opts",
                       var="compression_mode", val="aggressive")
        assert rc == 0
        rc, _, out = mon(prefix="osd pool get", pool="p_opts",
                         var="compression_mode")
        assert rc == 0 and out["compression_mode"] == "aggressive"
        # algorithm auto-filled when a mode is enabled without one
        rc, _, out = mon(prefix="osd pool get", pool="p_opts",
                         var="compression_algorithm")
        assert rc == 0 and out["compression_algorithm"] == "rle"
        rc, _, out = mon(prefix="osd pool get", pool="p_opts")
        assert rc == 0 and out["dedup_enable"] is False
        # validation
        rc, outs, _ = mon(prefix="osd pool set", pool="p_opts",
                          var="compression_mode", val="bogus")
        assert rc == -22
        rc, outs, _ = mon(prefix="osd pool set", pool="p_opts",
                          var="compression_algorithm", val="nope")
        assert rc == -22
        rc, outs, _ = mon(prefix="osd pool set", pool="p_opts",
                          var="dedup_enable", val="maybe")
        assert rc == -22
        rc, outs, _ = mon(prefix="osd pool set", pool="ecp",
                          var="dedup_enable", val="true")
        assert rc == -95, "dedup on an EC pool must be refused"
        rc, _, _ = mon(prefix="osd pool set", pool="p_opts",
                       var="dedup_enable", val="true")
        assert rc == 0
        rc, outs, _ = mon(prefix="osd pool mksnap", pool="p_opts",
                          snap="s1")
        assert rc == -95, "snapshots on a dedup pool must be refused"
        # the mutating command landed in the audit ring
        rc, _, entries = mon(prefix="log last", num=50,
                             channel="audit")
        assert rc == 0
        texts = [e.get("text", "") for e in entries]
        assert any("osd pool set" in t and "compression_mode" in t
                   for t in texts), texts

    def test_cli_pool_flags_and_rados_smoke(self, cluster, tmp_path):
        c = cluster
        rc, _ = _cli(ceph_main, c, "osd", "pool", "create", "clieff",
                     "--pg-num", "4", "--size", "2",
                     "--compression-mode", "aggressive",
                     "--compression-algorithm", "rle", "--dedup")
        assert rc == 0
        rc, out = _cli(ceph_main, c, "osd", "pool", "get", "clieff",
                       "compression_mode")
        assert rc == 0 and "aggressive" in out
        rc, out = _cli(ceph_main, c, "osd", "pool", "get", "clieff")
        assert rc == 0 and "dedup_enable" in out
        rc, _ = _cli(ceph_main, c, "osd", "pool", "set", "clieff",
                     "compression_mode", "passive")
        assert rc == 0
        rc, out = _cli(ceph_main, c, "osd", "pool", "get", "clieff",
                       "compression_mode")
        assert rc == 0 and "passive" in out
        rc, _ = _cli(ceph_main, c, "osd", "pool", "set", "clieff",
                     "compression_mode", "aggressive")
        assert rc == 0
        # rados CLI smoke through the compressed+dedup pool
        src = tmp_path / "in.bin"
        src.write_bytes(_runs(9000, 4))
        assert _cli(rados_main, c, "-p", "clieff", "put", "effobj",
                    str(src))[0] == 0
        dst = tmp_path / "out.bin"
        assert _cli(rados_main, c, "-p", "clieff", "get", "effobj",
                    str(dst))[0] == 0
        assert dst.read_bytes() == src.read_bytes()
        rc, out = _cli(rados_main, c, "-p", "clieff", "stat",
                       "effobj")
        assert rc == 0 and "size 9000" in out
        assert _cli(rados_main, c, "-p", "clieff", "rm",
                    "effobj")[0] == 0

    def test_df_reports_stored_vs_logical(self, cluster):
        r = cluster._test_rados
        deadline = time.monotonic() + 15.0
        while True:
            rc, _, out = r.mon_command({"prefix": "df"})
            assert rc == 0
            pools = {p["name"]: p for p in out.get("pools", [])}
            cp = pools.get("cpool")
            if cp and cp.get("bytes_logical", 0) \
                    > cp.get("bytes_used", 0):
                break
            if time.monotonic() > deadline:
                raise AssertionError(f"df never showed a ratio: {cp}")
            time.sleep(0.3)
        assert cp["compress_ratio"] > 1.0
        assert out["total_bytes_logical"] >= out["total_bytes_used"]

    def test_recovery_preserves_sealed_and_dedup_objects(self,
                                                         cluster):
        c = cluster
        r = c._test_rados
        victim = sorted(c.osds)[-1]
        c.kill_osd(victim)
        c.wait_for_osd_down(victim)
        io = r.open_ioctx("cpool")
        io2 = r.open_ioctx("dpool")
        sealed = _runs(7000, 13)
        dup = _runs(12000, 17)
        io.write_full("rec1", sealed)      # written while degraded
        io2.write_full("rd1", dup)
        io2.write_full("rd2", dup)
        c.revive_osd(victim)
        c.wait_for_clean(timeout=60.0)
        time.sleep(0.5)
        assert io.read("rec1") == sealed
        assert io2.read("rd1") == dup and io2.read("rd2") == dup
        # the revived OSD holds the sealed replica with its header
        reps = _stored(c, "rec1")
        assert victim in reps
        _data, meta = reps[victim]
        assert meta["size"] == len(sealed)
        for i, osd in c.osds.items():
            with osd.lock:
                probs = dd.verify_refcounts(osd.store)
            assert not probs, f"osd.{i} after recovery: {probs}"
        io2.remove("rd1")
        io2.remove("rd2")
        time.sleep(0.3)


# ------------------------------------------------------- engine on/off

class TestEngineOnOffIdentity:
    def test_compressed_writes_engine_disabled_bit_identical(self):
        """Lane off vs on: the stored blob and meta for the same
        payloads are byte-identical on every replica (cluster-level
        bit-identity acceptance gate, mirroring the EC batch test)."""
        payloads = {"idc1": _runs(6000, 21),
                    "idc2": _payload(2500, 22),
                    "idc3": _runs(40, 23)}
        stored = {}
        for enabled, flush in ((False, 0.0), (True, 25.0)):
            c = MiniCluster(n_mons=1, n_osds=3, osd_config={
                "osd_compress_batch_enable": enabled,
                "osd_compress_batch_flush_ms": flush})
            c.start()
            try:
                r = c.rados()
                r.create_pool("idp", pg_num=1, size=3,
                              compression_mode="aggressive",
                              compression_algorithm="rle")
                io = r.open_ioctx("idp")
                c.wait_for_clean()
                for oid, data in payloads.items():
                    io.write_full(oid, data)
                time.sleep(0.3)
                snap = {}
                for oid, data in payloads.items():
                    assert io.read(oid) == data
                    snap[oid] = _stored(c, oid)
                stored[enabled] = snap
                r.shutdown()
            finally:
                c.stop()
        assert stored[False] == stored[True]


# ------------------------------------------------- lanes on a write mix

def test_bench_efficiency_leg_cpu_smoke():
    """A write mix through one BatchEngine's compress lane and a
    duplicated stream through its fingerprint lane: every sealed blob
    expands to its payload, incompressible payloads pass through,
    batched results equal an engine-off replay, and the mix meets the
    compression (> 1.5x) and dedup (> 2x) ratios."""
    rng = np.random.default_rng(19)
    codec = create_codec("rle")
    size, nobj = 256 << 10, 24
    payloads = []
    for i in range(nobj):
        if i % 8 == 7:      # incompressible tail: must pass through
            payloads.append(
                rng.integers(0, 256, size, np.uint8).tobytes())
        else:               # run-structured (device logs, zero pages)
            run = int(rng.integers(16, 128))
            vals = rng.integers(0, 256, size // run + 1, np.uint8)
            payloads.append(np.repeat(vals, run)[:size].tobytes())

    eng = BatchEngine("eff", flush_ms=2.0, max_ops=64,
                      max_bytes=64 << 20)
    off = BatchEngine("eff-off", enabled=False)
    try:
        eng.submit_compress(codec, payloads[0])     # warm the bucket
        eng.drain()
        for key in list(eng.stats):
            eng.stats[key] = 0
        comps = [eng.submit_compress(codec, p) for p in payloads]
        eng.drain()
        assert all(c.done() and c.error is None for c in comps)
        passthrough = 0
        for c, p in zip(comps, payloads):
            blob, hdr = c.result()
            if hdr is None:
                passthrough += 1
                assert bytes(blob) == p
            else:
                assert eng.decompress(blob, hdr) == p
        assert passthrough >= nobj // 8      # the 3 incompressible
        ratio = (eng.stats["comp_bytes_in"]
                 / max(1, eng.stats["comp_bytes_out"]))
        assert ratio > 1.5
        # engine-off bit-identity: same codec path, no batching
        for j in (0, 7, nobj - 1):
            assert comps[j].result() == \
                off.submit_compress(codec, payloads[j]).result()

        # 4 shuffled copies of each base block: the CDC chunker must
        # converge on identical fingerprints for identical content
        chunker = Chunker(avg_size=4096)
        blocks = [rng.integers(0, 256, 64 << 10, np.uint8).tobytes()
                  for _ in range(4)]
        order = list(range(len(blocks))) * 4
        rng.shuffle(order)
        stream = b"".join(blocks[i] for i in order)
        fpc = eng.submit_fingerprint(chunker, stream)
        eng.drain()
        spans = fpc.result()
        referenced = sum(ln for _off, ln, _fp in spans)
        assert referenced == len(stream)
        uniq = {}
        for _off, ln, fp in spans:
            uniq.setdefault(fp, ln)
        assert referenced / max(1, sum(uniq.values())) > 2.0
        off0, ln0, fp0 = spans[0]
        assert fingerprint(stream[off0:off0 + ln0]) == fp0
    finally:
        eng.stop()
        off.stop()
