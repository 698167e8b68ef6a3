"""Erasure-code benchmark — `ceph_erasure_code_benchmark` CLI parity.

Reference harness being re-created: ``src/test/erasure-code/
ceph_erasure_code_benchmark.{h,cc}`` (SURVEY.md §4.4) — same flags, same
semantics (seconds elapsed, caller derives GB/s), plus:

- ``--batch``: stripes per device launch (the TPU engine's native unit; the
  reference encodes one buffer at a time, we batch to fill the MXU);
- ``--verify``: cross-check parity bytes against the NumPy oracle.

Examples::

    python -m ceph_tpu.tools.ec_bench --plugin jax_tpu --workload encode \
        --size 1048576 --iterations 100 --parameter k=8 --parameter m=3 \
        --parameter technique=reed_sol_van
    python -m ceph_tpu.tools.ec_bench --workload decode --erasures 2 ...
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

import numpy as np

from ..ec import create_erasure_code
from ..ec.interface import ECProfile
from ..ops import rs


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="ec_bench", description=__doc__)
    p.add_argument("--plugin", "-P", default="jax_tpu")
    p.add_argument("--workload", "-w", choices=["encode", "decode"],
                   default="encode")
    p.add_argument("--iterations", "-i", type=int, default=1)
    p.add_argument("--size", "-s", type=int, default=1 << 20,
                   help="total payload bytes per iteration")
    p.add_argument("--parameter", "-p", action="append", default=[],
                   help="profile parameter k=v (repeatable)")
    p.add_argument("--erasures", "-e", type=int, default=1,
                   help="erasures per decode")
    p.add_argument("--erasures-generation", "-E",
                   choices=["random", "exhaustive"], default="random")
    p.add_argument("--erased", type=int, action="append", default=None,
                   help="explicit chunk id to erase (repeatable)")
    p.add_argument("--batch", "-b", type=int, default=None,
                   help="stripes per launch (default: whole payload as one "
                        "stripe, matching the reference)")
    p.add_argument("--verify", "-v", action="store_true",
                   help="verify bytes against the NumPy oracle")
    p.add_argument("--json", action="store_true", help="emit one JSON line")
    return p


def run(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    for kv in args.parameter:
        if "=" not in kv:
            print(f"ec_bench: bad --parameter {kv!r} (expected key=value)",
                  file=sys.stderr)
            raise SystemExit(2)
    params = dict(kv.split("=", 1) for kv in args.parameter)
    params.setdefault("plugin", args.plugin)
    profile = ECProfile.parse(params)
    code = create_erasure_code(profile)
    k, m = code.k, code.m

    rng = np.random.default_rng(0)
    chunk = code.get_chunk_size(args.size)
    batch = args.batch or 1
    data = rng.integers(0, 256, size=(batch, k, chunk), dtype=np.uint8)
    engine = getattr(code, "engine", None)

    def encode_once():
        if engine is not None:
            return engine.encode_device(data)
        stripes = []
        for b in range(batch):
            out = code.encode(set(range(k, k + m)), data[b].reshape(-1))
            # index by chunk id: set/dict iteration order is not id order
            stripes.append(np.stack([out[i] for i in range(k, k + m)]))
        return np.stack(stripes)

    # erasure patterns for decode
    if args.erased:
        patterns = [tuple(args.erased)]
    elif args.erasures_generation == "exhaustive":
        patterns = list(itertools.combinations(range(k + m), args.erasures))
    else:
        patterns = []
        for _ in range(args.iterations):
            patterns.append(tuple(
                sorted(rng.choice(k + m, size=args.erasures, replace=False))))

    parity_np = None
    if args.workload == "decode" or args.verify:
        parity_dev = encode_once()
        if engine is not None:
            parity_np = np.asarray(jax_block(parity_dev))
        else:
            parity_np = np.asarray(parity_dev)

    if args.verify:
        coding = getattr(code, "coding_matrix", None)
        if coding is not None:
            from ..ec.bitmatrix import BitMatrixECEngine
            from ..ec.bitmatrix import encode_oracle as bm_oracle
            bitmatrix = isinstance(engine, BitMatrixECEngine)
            for b in range(min(batch, 4)):
                expect = (bm_oracle(coding, data[b], code.w) if bitmatrix
                          else rs.encode_oracle(coding, data[b]))
                assert np.array_equal(parity_np[b], expect), \
                    f"parity mismatch vs oracle at stripe {b}"

    total_bytes = 0
    if args.workload == "encode":
        jax_block(encode_once())  # warm: exclude XLA compile from timing
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            # materialize EVERY iteration: fetching the parity fences
            # the work (the host transfer is included)
            np.asarray(jax_block(encode_once()))
            total_bytes += batch * k * chunk
        elapsed = time.perf_counter() - t0
    else:
        all_chunks = np.concatenate([data, parity_np], axis=1)

        def decode_once(pattern):
            survivors = [i for i in range(k + m) if i not in pattern][:k]
            if engine is not None and code.is_mds:
                # MDS matrix codes: first-k survivor rule (jerasure's).
                # Non-MDS plugins (SHEC/LRC) must use their own solver —
                # an arbitrary k-subset can be singular for them.
                return engine.decode_batch(all_chunks[:, survivors, :],
                                           pattern)
            # non-MDS / locality codes: ask the plugin what to read
            want = set(range(k))
            avail = set(range(k + m)) - set(pattern)
            reads = code.minimum_to_decode(want, avail)
            for b in range(batch):
                code.decode(want, {i: all_chunks[b, i] for i in reads})
            return None

        for pattern in set(patterns):
            decode_once(pattern)  # warm each distinct erasure pattern
        t0 = time.perf_counter()
        for it in range(args.iterations):
            out = decode_once(patterns[it % len(patterns)])
            if out is not None:
                np.asarray(jax_block(out))   # see encode-loop comment
            total_bytes += batch * k * chunk
        elapsed = time.perf_counter() - t0

    result = {
        "plugin": profile.plugin, "technique": profile.technique,
        "k": k, "m": m, "workload": args.workload,
        "size": args.size, "chunk": chunk, "batch": batch,
        "iterations": args.iterations,
        "seconds": elapsed,
        "GBps": total_bytes / elapsed / 1e9 if elapsed > 0 else float("inf"),
        "verified": bool(args.verify),
    }
    if args.json:
        print(json.dumps(result))
    else:
        print(f"{elapsed:.6f}")  # reference prints elapsed seconds
        print(f"# {result['GBps']:.3f} GB/s "
              f"({profile.plugin}/{profile.technique} k={k} m={m} "
              f"chunk={chunk} batch={batch} x{args.iterations})",
              file=sys.stderr)
    return result


def jax_block(x):
    """block_until_ready if x is a jax array (no-op for numpy)."""
    try:
        return x.block_until_ready()
    except AttributeError:
        return x


if __name__ == "__main__":
    run()
