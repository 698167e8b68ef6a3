"""What decides ``correct``: the timed path's answers against the plain
reference, once the window has closed.

Every number is a count of answers that differ, and every limit is 0
(an exact comparison):

- ``read_mismatch``: reads of the window (and warm-up) whose bytes are
  not those of the last acknowledged write;
- ``op_errors`` / ``ops_lost``: ops the cluster failed, or never
  answered within the grace after the close;
- on a sample of objects drawn from the seed, preferring those the
  window wrote: ``readback_mismatch`` (a whole-object read through
  librados), ``shard_mismatch`` (each of the k+m shards an OSD stored,
  against the reference encode of the expected object: this covers
  the read-modify-write splice), ``crc_mismatch`` (each shard's stored
  CRC-32C against the reference CRC of the reference shard).
"""

from __future__ import annotations

import numpy as np

from .reference import crc32c as ref_crc
from .reference import ec as ref_ec
from .traffic.client import ERROR, LOST, MISMATCH

LIMITS = {"read_mismatch": 0, "op_errors": 0, "ops_lost": 0,
          "readback_mismatch": 0, "shard_mismatch": 0, "crc_mismatch": 0}


def sample(model, records_written: set[str], n: int, seed: int) -> list[str]:
    """n objects from the seed: those the window wrote first."""
    rng = np.random.default_rng([seed % (1 << 64), 7])
    fresh = sorted(records_written)
    rest = [o for o in model.written() if o not in records_written]
    picks = list(rng.permutation(fresh))[:n]
    if len(picks) < n:
        picks += list(rng.permutation(rest))[:n - len(picks)]
    return sorted(str(o) for o in picks)


def verify(cluster, model, records, written: set[str], n_sample: int,
           seed: int, timeout: float = 120.0) -> dict:
    """→ {name: [value, limit]}."""
    checks = {
        "read_mismatch": sum(r.status == MISMATCH for r in records),
        "op_errors": sum(r.status == ERROR for r in records),
        "ops_lost": sum(r.status == LOST for r in records),
    }
    objs = sample(model, written, n_sample, seed)
    coding = ref_ec.reed_sol_van(cluster.k, cluster.m)
    live = set(cluster.mc.osds)
    comps = {oid: cluster.io.aio_read(oid) for oid in objs}
    back = shards = crcs = 0
    want_rows, got_crcs = [], []
    for oid in objs:
        expect = model.expected(oid)
        comp = comps[oid]
        if not comp.wait_for_complete(timeout) or comp.rc != 0 or \
                bytes.fromhex(comp.results[0].get("data", "")) != expect:
            back += 1
        ref = ref_ec.encode(coding, expect)
        pgid, acting = cluster.acting(oid)
        for s, osd in enumerate(acting):
            if osd not in live:
                continue        # a fault the cell injected
            got = cluster.stored_shard(osd, pgid, s, oid)
            if got is None or got[0] != ref[s] or \
                    int(got[1].get("size", -1)) != len(expect):
                shards += 1
            if got is not None:
                want_rows.append(ref[s])
                got_crcs.append(got[1].get("hinfo"))
    for want, got in zip(ref_crc.crc32c_rows(want_rows), got_crcs):
        if got is None or int(got) != int(want):
            crcs += 1
    checks.update(readback_mismatch=back, shard_mismatch=shards,
                  crc_mismatch=crcs)
    return {k: [int(v), LIMITS[k]] for k, v in checks.items()}
