"""Faults planted under the timed path, and the control.

Each entry is a context manager that breaks the system under test in
this process; a run of a cell inside it must come out ``correct:
false``.  ``control`` breaks a guarantee the deployments state (a
write is acknowledged only after all k+m shards commit): parity shards
are never written, yet the write is acknowledged.  The others are the
faults a storage cell can have: a write acknowledged without changing
state, half of each encode megabatch left out, and an answer altered
where it is produced (a parity byte on the device, a byte of the
payload an OSD decodes for a read).  The exchange between chips does
not exist in a one-chip cell.

Run the control on the chip at a cell's own size:

    python benchmark/tests/faults.py --workload <cell> --seeds 1,2,3 --seconds 15
"""

from __future__ import annotations

import contextlib
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from unittest import mock  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@contextlib.contextmanager
def control():
    """Parity shards never committed; the write acknowledged anyway."""
    from ceph_tpu.os_store import Transaction
    from ceph_tpu.osd.pg import ECBackend
    orig = ECBackend._shard_txn

    def shard_txn(self, shard, oid, chunks, delete, *a, **kw):
        if chunks is not None and not delete and shard >= self.engine.k:
            return Transaction()
        return orig(self, shard, oid, chunks, delete, *a, **kw)

    with mock.patch.object(ECBackend, "_shard_txn", shard_txn):
        yield


@contextlib.contextmanager
def state_unchanged():
    """Writes acknowledged without touching any shard."""
    from ceph_tpu.os_store import Transaction
    from ceph_tpu.osd.pg import ECBackend
    orig = ECBackend._shard_txn

    def shard_txn(self, shard, oid, chunks, delete, *a, **kw):
        if chunks is not None and not delete:
            return Transaction()
        return orig(self, shard, oid, chunks, delete, *a, **kw)

    with mock.patch.object(ECBackend, "_shard_txn", shard_txn):
        yield


@contextlib.contextmanager
def half_batch():
    """The second half of every encode megabatch's rows is left out."""
    from ceph_tpu.ops.gf_jax import GFEncodeDigest
    orig = GFEncodeDigest.__call__

    def call(self, batch):
        batch = batch.copy()
        batch[batch.shape[0] // 2:] = 0
        return orig(self, batch)

    with mock.patch.object(GFEncodeDigest, "__call__", call):
        yield


@contextlib.contextmanager
def altered_parity():
    """One parity byte flipped where the device produces it."""
    from ceph_tpu.ops.gf_jax import GFEncodeDigest
    orig = GFEncodeDigest.__call__

    def call(self, batch):
        parity, crcs = orig(self, batch)
        return parity.at[0, 0, 0].set(parity[0, 0, 0] ^ 1), crcs

    with mock.patch.object(GFEncodeDigest, "__call__", call):
        yield


@contextlib.contextmanager
def altered_read():
    """The first byte of every payload an OSD decodes is flipped."""
    from ceph_tpu.osd.pg import PG
    orig = PG.unseal_payload

    def unseal(self, raw, meta):
        out = orig(self, raw, meta)
        return bytes([out[0] ^ 1]) + out[1:] if out else out

    with mock.patch.object(PG, "unseal_payload", unseal):
        yield


FAULTS = {"control": control, "state_unchanged": state_unchanged,
          "half_batch": half_batch, "altered_parity": altered_parity,
          "altered_read": altered_read}


def run_broken(fault: str, workload: str, seed: int, seconds: float, *,
               on_chip: bool = True, overrides: dict | None = None,
               t_start: float | None = None) -> dict:
    from benchmark.run import run_cell
    with FAULTS[fault]():
        return run_cell(workload, seed, seconds, False, on_chip=on_chip,
                        overrides=overrides,
                        t_start=time.monotonic() if t_start is None
                        else t_start)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run a cell under a fault")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="control", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "xla")
    os.environ["CEPH_TPU_CACHE_DIR"] = cache
    sys.path.insert(0, ROOT)
    from benchmark.run import device_gate, load_cell
    device_gate(load_cell(args.workload)["chips"])
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_broken(args.fault, args.workload, seed, args.seconds,
                         t_start=t)
        print(json.dumps({"fault": args.fault, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
        t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
