"""Bring up the deployment a configuration file describes, as a
threaded ``vstart.MiniCluster`` in this process (the process that holds
the chip), and read its state back: admin-socket counters, and the
shards each OSD stored."""

from __future__ import annotations

import json
import sys
import time

POOL = "bench"


def log(what: str) -> None:
    print(f"bench: {time.strftime('%H:%M:%S')} {what}", file=sys.stderr,
          flush=True)


class Cluster:
    def __init__(self, cfg: dict):
        from ceph_tpu.vstart import MiniCluster
        self.cfg = cfg
        pool = cfg["pool"]
        self.profile = {k: str(v) for k, v in pool["profile"].items()}
        self.k, self.m = int(self.profile["k"]), int(self.profile["m"])
        self.mc = MiniCluster(n_mons=int(cfg["mons"]),
                              n_osds=int(cfg["osds"]),
                              osd_config=dict(cfg.get("osd_config", {})))
        self.rados = self.io = None
        self.pool_id = None

    def start(self, timeout: float = 300.0) -> "Cluster":
        self.mc.start(timeout=timeout)
        log(f"{self.cfg['mons']} mon, {self.cfg['osds']} OSDs up")
        r = self.rados = self.mc.rados()
        rc, outs, _ = r.monc.command({
            "prefix": "osd erasure-code-profile set", "name": POOL,
            "profile": [f"{k}={v}" for k, v in self.profile.items()]})
        if rc != 0:
            raise RuntimeError(f"erasure-code-profile set: {outs}")
        pool = self.cfg["pool"]
        r.create_pool(POOL, pg_num=int(pool["pg_num"]),
                      pool_type=pool["type"],
                      erasure_code_profile=POOL)
        self.io = r.open_ioctx(POOL)
        self.pool_id = self.io.pool_id
        self.mc.wait_for_clean(timeout=timeout)
        log(f"pool of {pool['pg_num']} PGs clean")
        img = self.cfg.get("image")
        if img is not None:
            from ceph_tpu.rbd import RBD
            RBD().create(self.io, img["name"], int(img["size"]),
                         order=int(img["order"]))
            log(f"image {img['name']} created")
        return self

    def stop(self):
        self.mc.stop()

    # -- counters ------------------------------------------------------
    def _asok(self, cmd: str) -> list[dict]:
        from ceph_tpu.core.admin_socket import admin_command
        return [admin_command(o.admin_socket.path, cmd)
                for o in self.mc.osds.values()]

    def counters(self) -> dict:
        """Every OSD's ``perf dump`` and ``dump_batch_engine``."""
        return {"perf": self._asok("perf dump"),
                "engine": self._asok("dump_batch_engine")}

    # -- placement and stored shards -------------------------------------
    def acting(self, oid: str) -> tuple[str, list[int]]:
        m = self.rados.objecter.osdmap
        pgid = m.raw_pg_to_pg(m.object_locator_to_pg(oid, self.pool_id))
        return str(pgid), list(m.pg_to_up_acting_osds(pgid)[2])

    def stored_shard(self, osd: int, pgid: str, shard: int, oid: str
                     ) -> tuple[bytes, dict] | None:
        """(chunk, "_" meta) that one OSD holds, or None."""
        d = self.mc.osds.get(osd)
        if d is None:
            return None
        cid = f"{pgid}s{shard}"
        try:
            chunk = bytes(d.store.read(cid, oid))
            meta = json.loads(bytes(d.store.getattr(cid, oid, "_")))
        except KeyError:
            return None
        return chunk, meta

    def kill_osd_holding(self, shard: int, oid: str) -> int:
        """Stop the OSD that holds `shard` of `oid`'s PG and mark it
        down through the mon."""
        _pgid, acting = self.acting(oid)
        victim = acting[shard]
        self.mc.kill_osd(victim)
        self.rados.mon_command({"prefix": "osd down", "ids": [victim]})
        self.mc.wait_for_osd_down(victim, timeout=60)
        log(f"osd.{victim} (shard {shard} of {oid}) down")
        return victim


def warm_programs(cfg: dict, reconstruct: bool):
    """Compile, or load from the persistent cache, every device program
    the cell's traffic can launch, before any daemon runs (a compile
    that stalls a live in-process cluster for minutes churns its map):
    at every batch shape a flush can take (rows 1, 2, 4, ... up to what
    the OSDs' max_ops / max_bytes let one flush hold), the write lane's
    encode+digest of one object, and with `reconstruct` the
    reconstruct lane's decode of the k data shards with any one of them
    missing (a degraded read, or a read that races a write and finds
    one data shard stale)."""
    import numpy as np

    from ceph_tpu.core.config import ConfigProxy
    from ceph_tpu.core.options import build_options
    from ceph_tpu.ec import create_erasure_code
    from ceph_tpu.osd.batch_engine import BatchEngine
    conf = ConfigProxy(build_options())
    for key, val in cfg.get("osd_config", {}).items():
        conf.set(key, val)
    profile = {k: str(v) for k, v in cfg["pool"]["profile"].items()}
    ec = create_erasure_code(profile)
    k, n = ec.k, ec.k + ec.m
    size = int(cfg["object_size"])
    chunk = ec.get_chunk_size(size)
    blob = bytes(size)
    jobs = [("encode", lambda eng: eng.submit_encode(ec, blob),
             "osd_batch_max_ops", "osd_batch_max_bytes")]
    if reconstruct:
        jobs += [(f"reconstruct without {e}",
                  lambda eng, e=e: eng.submit_reconstruct(
                      ec, {i: np.zeros(chunk, np.uint8)
                           for i in range(n) if i != e},
                      want=set(range(k))),
                  "osd_recovery_batch_max_ops",
                  "osd_recovery_batch_max_bytes")
                 for e in range(k)]
    for what, job, max_ops, max_bytes in jobs:
        most = min(int(conf.get(max_ops)),
                   -(-int(conf.get(max_bytes)) // (k * chunk)))
        rows = 1
        while rows <= max(1, most):
            # an engine of its own that flushes at exactly `rows` ops:
            # compiled programs are shared process-wide by shape
            t = time.monotonic()
            eng = BatchEngine("bench-warm", flush_ms=1e6, max_ops=rows,
                              max_bytes=1 << 40)
            try:
                for c in [job(eng) for _ in range(rows)]:
                    c.result(timeout=1200)
            finally:
                eng.stop()
            log(f"warm {what}, rows {rows}: {time.monotonic() - t:.1f} s")
            rows *= 2
