"""Per-OSD coalescing device data plane — kill the per-op dispatch floor.

The dispatch floor is the tax every OSD op pays to cross
Python→device once: EC encode, CRC digest, parity recheck each
launch alone, so an op-mix workload runs at launch rate, not at MXU
rate.  This engine is the Python mirror of the native coalescing ring
(``native/pjrt_executor.cc``): the write stream for a tick — across
PGs and across op types — accumulates into one **megabatch** that a
single fused launch (`ops.gf_jax.GFEncodeDigest`) encodes *and*
digests, so per-shard hinfo CRCs ride the same program.

Shape discipline keeps the jit cache bounded: members are grouped by
EC code identity and bucketed by chunk length, rows and lengths both
pad to powers of two.  Zero padding is free for the GF encode
(linearity: zero columns encode to zero parity) and reversible for
the digest (`scrub.crc32c_jax.crc32c_zero_unpad` strips the pad with
two 32-bit GF(2) matrix applications) — so batched results are
**bit-identical** to the unbatched path, asserted in
tests/test_batch_engine.py and before any bench timing.

Three lanes share the machinery but accumulate separately.  The
**write lane** (PR 8) carries encode+digest for the client write
stream.  The **compression lane** carries the storage-efficiency
pre-pass of the write path: per-pool inline compression (the codec's
device scan runs once over the whole size-bucketed megabatch, see
``compress/codec.py``) and dedup fingerprinting (gear-hash CDC
boundaries as one jitted launch + one batched CRC-32C launch per
flush, ``compress/chunker.py``), with its own knobs
(``osd_compress_batch_*`` → ``comp_*``, defaulting to the write
lane's) and stats (``comp_`` prefix).  Oversized payloads split into
fixed segments that batch *across* objects — the streaming segment
path.  The **reconstruct lane** carries the degraded path —
degraded reads, recovery pushes, backfill pulls, and scrub parity
rechecks — grouped per (code identity, erasure pattern, size
bucket) so one fused launch reconstructs a whole sweep's worth of
objects: a single ``GFLinear`` over the plan's stacked
``[k + p, k]`` recovery matrix on CPU/1-chip, the resident
bit-plane path (``ops.gf_pallas2.ResidentPlanes``,
expand-once/multiply-many with per-matrix operands held across the
sweep) when planes are selected, or the ``parallel.reconstruct``
shard_map program over a (dp, shard) mesh.  Erased *parity* rows
ride the same launch via the plan's composed ``coding ∘ dm``
matrix (GF associativity makes the composition byte-exact).  The
lane has its own knobs (``recon_*``, defaulting to the write
lane's) and its own stats (``recon_`` prefix); each lane flush
reports to ``on_lane_flush`` so the OSD can debit the mClock
recovery reservation for the bandwidth the lane just consumed.

Flush policy (reference: the OSD op queue's batching heuristics):

- ``max_bytes`` / ``max_ops`` — size triggers, checked at submit;
- ``flush_ms`` — the accumulation deadline.  ``0`` (the default)
  means *immediate*: every submit flushes synchronously and
  completions fire before ``submit_*`` returns — CPU-only CI runs
  exactly the old one-op-at-a-time semantics, just through one code
  path.  ``> 0`` arms a timer (``schedule``) and enables the
  double-buffered flight pipeline: a flush dispatches its launches
  asynchronously and hands the flights to a completion worker that
  fences them in FIFO order while the next tick keeps staging — the
  device never idles between launches, and FIFO completion preserves
  per-PG version ordering.

Lock order (lockdep-clean by construction): submitters may hold the
daemon lock when calling ``submit_*`` (engine locks are leaves);
completion callbacks re-acquire the daemon lock but run either on
the submitter's own thread (immediate mode — RLock re-entry) or on
the completion worker with **no** engine lock held, so there is no
path that holds an engine lock while waiting on the daemon lock.
"""

from __future__ import annotations

import collections
import queue
import threading
import time

import numpy as np

from ..core.tracer import stage


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


class Completion:
    """One submitted op's pending result.

    ``value`` for an encode op is ``(shard_chunks, hinfos)`` —
    ``{shard: bytes}`` for all k+m shards and ``{shard: crc32c}`` to
    match; for a digest op it is the ``int`` crc.  ``info`` carries
    flush attribution (rows, members, reason) for the member's span.
    """

    __slots__ = ("_ev", "value", "error", "info", "_cb")

    def __init__(self, callback=None):
        self._ev = threading.Event()
        self.value = None
        self.error: BaseException | None = None
        self.info: dict = {}
        self._cb = callback

    def done(self) -> bool:
        return self._ev.is_set()

    def wait(self, timeout: float | None = None) -> bool:
        return self._ev.wait(timeout)

    def result(self, timeout: float | None = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("batch op still pending")
        if self.error is not None:
            raise self.error
        return self.value

    def _fire(self, value=None, error: BaseException | None = None):
        if self._ev.is_set():
            return              # first outcome wins
        self.value = value
        self.error = error
        self._ev.set()
        if self._cb is not None:
            self._cb(self)


class _Op:
    __slots__ = ("kind", "key", "chunks", "payload", "length",
                 "nbytes", "comp", "span", "want", "passthrough",
                 "codec", "mode", "chunker")

    def __init__(self, kind, key, comp, span, length, nbytes,
                 chunks=None, payload=None, want=None,
                 passthrough=None, codec=None, mode=None,
                 chunker=None):
        self.kind = kind            # "encode"|"digest"|"recon"|
        #                             "recheck"|"compress"|"fingerprint"
        self.key = key              # executable-identity group key
        self.comp = comp
        self.span = span
        self.length = length        # true (unpadded) per-row length
        self.nbytes = nbytes
        self.chunks = chunks        # encode/recheck: [k, length];
        #                             recon: survivor stack [k, length]
        self.payload = payload      # digest/compress/fingerprint: bytes
        self.want = want            # recon: frozenset of wanted ids
        self.passthrough = passthrough  # recon: {id: chunk} present+wanted
        self.codec = codec          # compress: Codec instance
        self.mode = mode            # compress: pool compression_mode
        self.chunker = chunker      # fingerprint: Chunker instance


class _Flight:
    """One dispatched launch awaiting its fence."""

    __slots__ = ("kind", "ops", "out", "length", "bucket", "ln",
                 "span", "reason", "plan")

    def __init__(self, kind, ops, out, length, bucket, ln, span,
                 reason, plan=None):
        self.kind = kind
        self.ops = ops
        self.out = out              # device value(s), un-fenced
        self.length = length        # bucket row length
        self.bucket = bucket        # padded row count
        self.ln = ln                # profiler launch (overlap) or None
        self.span = span
        self.reason = reason
        self.plan = plan            # recon: DecodePlan (row_of mapping)


class BatchEngine:
    """Tick-accumulating megabatch launcher for one OSD's device ops."""

    def __init__(self, name: str = "", *, enabled: bool = True,
                 max_bytes: int = 8 << 20, max_ops: int = 64,
                 flush_ms: float = 0.0, schedule=None,
                 profiler=None, tracer=None,
                 recon_enabled: bool | None = None,
                 recon_max_bytes: int | None = None,
                 recon_max_ops: int | None = None,
                 recon_flush_ms: float | None = None,
                 comp_enabled: bool | None = None,
                 comp_max_bytes: int | None = None,
                 comp_max_ops: int | None = None,
                 comp_flush_ms: float | None = None,
                 comp_segment_bytes: int = 1 << 20,
                 bucket_floor: int = 32,
                 use_mesh: bool = False, on_lane_flush=None,
                 store_kick=None):
        self.name = name
        self.enabled = bool(enabled)
        self.max_bytes = int(max_bytes)
        self.max_ops = int(max_ops)
        self.flush_ms = float(flush_ms)
        # reconstruct-lane knobs default to the write lane's values
        self.recon_enabled = (self.enabled if recon_enabled is None
                              else bool(recon_enabled))
        self.recon_max_bytes = (self.max_bytes if recon_max_bytes
                                is None else int(recon_max_bytes))
        self.recon_max_ops = (self.max_ops if recon_max_ops is None
                              else int(recon_max_ops))
        self.recon_flush_ms = (self.flush_ms if recon_flush_ms is None
                               else float(recon_flush_ms))
        # compression-lane knobs default to the write lane's values
        self.comp_enabled = (self.enabled if comp_enabled is None
                             else bool(comp_enabled))
        self.comp_max_bytes = (self.max_bytes if comp_max_bytes
                               is None else int(comp_max_bytes))
        self.comp_max_ops = (self.max_ops if comp_max_ops is None
                             else int(comp_max_ops))
        self.comp_flush_ms = (self.flush_ms if comp_flush_ms is None
                              else float(comp_flush_ms))
        self.comp_segment_bytes = int(comp_segment_bytes)
        self.bucket_floor = int(bucket_floor)
        self.use_mesh = bool(use_mesh)
        self.use_planes: bool | None = None  # None = auto (TPU only)
        self.on_lane_flush = on_lane_flush   # (lane, ops, bytes) hook
        # zero-arg durability nudge (WALStore.kick): one group-commit
        # fsync per megabatch flush instead of one per op
        self.store_kick = store_kick
        self._schedule = schedule   # schedule(delay_s, fn) -> token
        self.profiler = profiler
        self.tracer = tracer
        self._lock = threading.Lock()        # pending accumulator
        self._flush_lock = threading.Lock()  # serializes dispatch
        self._pending: list[_Op] = []
        self._pending_bytes = 0
        self._pending_since: float | None = None
        self._deadline_armed = False
        self._pending_recon: list[_Op] = []
        self._pending_recon_bytes = 0
        self._recon_since: float | None = None
        self._recon_armed = False
        self._pending_comp: list[_Op] = []
        self._pending_comp_bytes = 0
        self._comp_since: float | None = None
        self._comp_armed = False
        self._fused: dict = {}               # code key → GFEncodeDigest
        self._rexec: dict = {}               # recon/recheck key → GFLinear
        self._plan_cache: dict = {}          # DecodePlan per erasure set
        self._plane_mats: dict = {}          # bit-plane matrix operands
        self._sharded: dict = {}             # code key → ShardedEC
        self._mesh = None
        self._mesh_devs: tuple[str, ...] | None = None
        self._flights: queue.Queue = queue.Queue()
        self._worker: threading.Thread | None = None
        self._stopped = False
        self.stats = collections.Counter()
        # flight kind → {device label} its outputs landed on
        self.out_devices: dict[str, set[str]] = {}

    # -- submission --------------------------------------------------------

    @staticmethod
    def _matrix_engine(ec):
        """The batchable core of an EC plugin, or None (LRC/SHEC/
        bitmatrix layers fall back to the unbatched path)."""
        from ..ec.jax_backend import MatrixECEngine
        eng = getattr(ec, "engine", None)
        return eng if isinstance(eng, MatrixECEngine) else None

    def submit_encode(self, ec, data, *, span=None,
                      callback=None) -> Completion:
        """Queue a full-stripe encode+digest; the completion's value is
        ``({shard: bytes}, {shard: crc32c})`` over all k+m shards —
        byte- and digest-identical to ``ec.encode`` + host
        ``crc32c`` per shard."""
        comp = Completion(callback)
        self.stats["ops_submitted"] += 1
        value = None
        try:
            eng = self._matrix_engine(ec)
            if eng is None or not self.enabled or self._stopped:
                value = self._encode_unbatched(ec, data)
            else:
                chunks = np.ascontiguousarray(
                    ec.encode_prepare(data), dtype=np.uint8)
                key = ("encode", eng.k, eng.m, eng.coding.tobytes())
                op = _Op("encode", key, comp, span,
                         length=int(chunks.shape[1]),
                         nbytes=int(chunks.nbytes), chunks=chunks)
                self._enqueue(op)
                return comp
        except Exception as e:      # noqa: BLE001 — poisoned payloads
            self.stats["ops_failed"] += 1   # fail their own op only
            comp._fire(error=e)
            return comp
        # fire outside the try: a callback raising must surface to the
        # submitter, not masquerade as an encode failure
        comp._fire(value=value)
        return comp

    def submit_digest(self, payload, *, span=None,
                      callback=None) -> Completion:
        """Queue a CRC-32C digest; completion value is the int crc."""
        comp = Completion(callback)
        self.stats["ops_submitted"] += 1
        try:
            buf = bytes(payload)
            if self.enabled and not self._stopped and buf:
                op = _Op("digest", ("digest",), comp, span,
                         length=len(buf), nbytes=len(buf),
                         payload=buf)
                self._enqueue(op)
                return comp
            from ..scrub.crc32c_jax import crc32c
            value = crc32c(buf)
        except Exception as e:      # noqa: BLE001
            self.stats["ops_failed"] += 1
            comp._fire(error=e)
            return comp
        comp._fire(value=value)
        return comp

    @staticmethod
    def _encode_unbatched(ec, data):
        """The exact pre-engine semantics: whole-stripe encode, then
        host CRC per shard — the bit-identity reference."""
        from ..scrub.crc32c_jax import crc32c
        n = ec.k + ec.m
        out = ec.encode(set(range(n)), data)
        shard_chunks = {i: bytes(np.asarray(out[i]).tobytes())
                        for i in range(n)}
        hinfos = {i: crc32c(shard_chunks[i]) for i in range(n)}
        return shard_chunks, hinfos

    # -- reconstruct lane --------------------------------------------------

    def submit_reconstruct(self, ec, chunks, *, want=None, span=None,
                           callback=None) -> Completion:
        """Queue a degraded decode; the completion's value is
        ``{chunk_id: uint8 array}`` for every wanted id — byte-identical
        to ``ec.decode(want, chunks)``.

        ``want`` defaults to the k data ids (the client-read case).
        When every wanted id is already present the op completes
        synchronously with no device work (the systematic fast path,
        mirroring ``ErasureCode.decode``'s early-out); otherwise ops
        group per (code identity, erasure pattern, size bucket) and
        one fused launch recovers the whole group."""
        comp = Completion(callback)
        self.stats["recon_ops_submitted"] += 1
        value = None
        try:
            from ..ec.interface import ECError
            present = {int(i): np.asarray(c, dtype=np.uint8)
                       for i, c in chunks.items()}
            if not present:
                raise ECError("no chunks to decode from")
            want_ids = frozenset(
                int(i) for i in (want if want is not None
                                 else range(ec.k)))
            if want_ids <= present.keys():
                # systematic fast path: nothing to reconstruct
                self.stats["recon_fast_path"] += 1
                value = {i: present[i] for i in want_ids}
            else:
                eng = self._matrix_engine(ec)
                if (eng is None or not self.enabled
                        or not self.recon_enabled or self._stopped):
                    value = self._reconstruct_unbatched(
                        ec, want_ids, chunks)
                else:
                    sizes = {c.size for c in present.values()}
                    if len(sizes) != 1:
                        raise ECError("chunk sizes differ")
                    if len(present) < eng.k:
                        raise ECError(
                            f"{len(present)} chunks < k={eng.k}")
                    erasures = tuple(i for i in range(eng.k + eng.m)
                                     if i not in present)
                    avail = sorted(present)
                    surv = np.ascontiguousarray(np.stack(
                        [present[i] for i in avail[:eng.k]]))
                    op = _Op("recon",
                             ("recon", eng.k, eng.m,
                              eng.coding.tobytes(), erasures),
                             comp, span, length=int(surv.shape[1]),
                             nbytes=int(surv.nbytes), chunks=surv,
                             want=want_ids,
                             passthrough={i: present[i]
                                          for i in want_ids
                                          if i in present})
                    self._enqueue(op, lane="recon")
                    return comp
        except Exception as e:      # noqa: BLE001 — poisoned payloads
            self.stats["recon_ops_failed"] += 1
            comp._fire(error=e)
            return comp
        comp._fire(value=value)
        return comp

    def submit_recheck(self, ec, data, *, span=None,
                       callback=None) -> Completion:
        """Queue a scrub parity re-encode; completion value is the
        ``[m, length]`` parity array, byte-identical to
        ``np.asarray(ec._encode_chunks(data))`` — so deep-scrub parity
        rechecks coalesce with recovery reconstructs instead of
        launching standalone."""
        comp = Completion(callback)
        self.stats["recon_ops_submitted"] += 1
        try:
            eng = self._matrix_engine(ec)
            arr = np.ascontiguousarray(data, dtype=np.uint8)
            if (eng is None or not self.enabled
                    or not self.recon_enabled or self._stopped):
                value = np.asarray(ec._encode_chunks(arr))
            else:
                op = _Op("recheck",
                         ("recheck", eng.k, eng.m,
                          eng.coding.tobytes()),
                         comp, span, length=int(arr.shape[1]),
                         nbytes=int(arr.nbytes), chunks=arr)
                self._enqueue(op, lane="recon")
                return comp
        except Exception as e:      # noqa: BLE001
            self.stats["recon_ops_failed"] += 1
            comp._fire(error=e)
            return comp
        comp._fire(value=value)
        return comp

    @staticmethod
    def _reconstruct_unbatched(ec, want, chunks):
        """The exact pre-lane semantics — the bit-identity reference."""
        return ec.decode(set(want), chunks)

    # -- compression lane --------------------------------------------------

    def submit_compress(self, codec, payload, *, mode: str = "aggressive",
                        span=None, callback=None) -> Completion:
        """Queue an inline-compression pass; the completion's value is
        ``(stored_bytes, header | None)``.  ``header is None`` means
        pass-through — the payload did not shrink under an
        ``aggressive`` mode and is stored verbatim (``force`` always
        stores compressed).  The header (``{"algo", "len"}``, plus
        ``{"seg", "segs"}`` on the streaming segment path) is what the
        caller persists in the object meta so reads can expand.

        Payloads above ``comp_segment_bytes`` split into fixed
        segments that batch across objects — the oversized path keeps
        one row per segment instead of blowing up the bucket ladder.
        Batched and unbatched paths are bit-identical: the device scan
        feeds the same host finalize the single-op path uses."""
        comp = Completion(callback)
        self.stats["comp_ops_submitted"] += 1
        try:
            buf = bytes(payload)
            if len(buf) > self.comp_segment_bytes > 0:
                return self._submit_compress_segmented(
                    codec, buf, mode, span, comp)
            if (not self.enabled or not self.comp_enabled
                    or self._stopped or not buf):
                value = self._compress_unbatched(codec, buf, mode)
            else:
                op = _Op("compress", ("compress", codec.name), comp,
                         span, length=len(buf), nbytes=len(buf),
                         payload=buf, codec=codec, mode=mode)
                self._enqueue(op, lane="comp")
                return comp
        except Exception as e:      # noqa: BLE001 — poisoned payloads
            self.stats["comp_ops_failed"] += 1
            comp._fire(error=e)
            return comp
        comp._fire(value=value)
        return comp

    def _compress_unbatched(self, codec, buf: bytes, mode: str):
        """Single-op host semantics — the bit-identity reference for
        the lane (same codec, same fallback rule)."""
        blob = codec.compress(buf)
        self.stats["comp_bytes_in"] += len(buf)
        if mode != "force" and len(blob) >= len(buf):
            self.stats["comp_passthrough"] += 1
            self.stats["comp_bytes_out"] += len(buf)
            return buf, None
        self.stats["comp_bytes_out"] += len(blob)
        return blob, {"algo": codec.name, "len": len(buf)}

    def _submit_compress_segmented(self, codec, buf: bytes, mode: str,
                                   span, comp: Completion) -> Completion:
        """Streaming segment path: fixed-size segments submitted as
        ordinary lane members (they coalesce with other objects'
        segments), joined back into one blob whose header carries the
        per-segment compressed lengths."""
        seg = self.comp_segment_bytes
        segs = [buf[i:i + seg] for i in range(0, len(buf), seg)]
        results: list = [None] * len(segs)
        state = {"left": len(segs), "err": None}
        lock = threading.Lock()

        def _child(i):
            def cb(child):
                with lock:
                    if child.error is not None:
                        state["err"] = state["err"] or child.error
                    else:
                        results[i] = child.value
                    state["left"] -= 1
                    if state["left"]:
                        return
                if state["err"] is not None:
                    comp._fire(error=state["err"])
                    return
                clens = [[len(b), 1 if h is None else 0]
                         for b, h in results]
                total = sum(c for c, _raw in clens)
                if mode != "force" and total >= len(buf):
                    self.stats["comp_passthrough"] += 1
                    comp._fire(value=(buf, None))
                    return
                blob = b"".join(b for b, _h in results)
                comp._fire(value=(blob, {
                    "algo": codec.name, "len": len(buf),
                    "seg": seg, "segs": clens}))
            return cb

        for i, s in enumerate(segs):
            self.submit_compress(codec, s, mode=mode, span=span,
                                 callback=_child(i))
        return comp

    def decompress(self, blob, header: dict) -> bytes:
        """Expand a sealed blob back to its logical bytes (the
        read/recovery half).  Host work by design: RLE expansion is a
        single ``np.repeat`` gather with nothing for the MXU to win,
        so it stays synchronous where the read path needs it — the
        lane's device budget goes to the write-side scans.  Counted
        under ``comp_decompress_bytes`` for the telemetry spine."""
        from ..compress.codec import CodecError
        from ..compress.registry import create_codec
        blob = bytes(blob)
        codec = create_codec(header["algo"])
        total = int(header["len"])
        segs = header.get("segs")
        if segs is None:
            out = codec.decompress(blob, total)
        else:
            seg = int(header["seg"])
            parts = []
            off = 0
            for i, (clen, raw) in enumerate(segs):
                llen = min(seg, total - i * seg)
                piece = blob[off:off + clen]
                off += clen
                parts.append(bytes(piece) if raw
                             else codec.decompress(piece, llen))
            out = b"".join(parts)
        if len(out) != total:
            raise CodecError(
                f"decompress produced {len(out)} of {total} bytes")
        self.stats["comp_decompress_bytes"] += len(out)
        return out

    def submit_fingerprint(self, chunker, payload, *, span=None,
                           callback=None) -> Completion:
        """Queue a dedup fingerprint pass; the completion's value is
        ``[(off, length, fp), ...]`` — content-defined chunk spans
        with their fingerprints.  The gear-hash boundary scan runs as
        one fused launch over the size-bucketed megabatch and every
        chunk of the flush digests through one batched CRC-32C
        launch; the host path (lane off / empty payload) computes the
        identical spans and fingerprints."""
        comp = Completion(callback)
        self.stats["comp_ops_submitted"] += 1
        try:
            buf = bytes(payload)
            if (not self.enabled or not self.comp_enabled
                    or self._stopped or not buf):
                value = self._fingerprint_unbatched(chunker, buf)
            else:
                op = _Op("fingerprint",
                         ("fingerprint",) + chunker.key(), comp, span,
                         length=len(buf), nbytes=len(buf),
                         payload=buf, chunker=chunker)
                self._enqueue(op, lane="comp")
                return comp
        except Exception as e:      # noqa: BLE001
            self.stats["comp_ops_failed"] += 1
            comp._fire(error=e)
            return comp
        comp._fire(value=value)
        return comp

    def _fingerprint_unbatched(self, chunker, buf: bytes):
        from ..compress.chunker import fingerprint
        self.stats["comp_fingerprint_bytes"] += len(buf)
        return [(off, ln, fingerprint(buf[off:off + ln]))
                for off, ln in chunker.chunks(buf)]

    def _lane_knobs(self, lane: str) -> tuple[int, int, float]:
        """(max_ops, max_bytes, flush_ms) for one lane."""
        if lane == "recon":
            return (self.recon_max_ops, self.recon_max_bytes,
                    self.recon_flush_ms)
        if lane == "comp":
            return (self.comp_max_ops, self.comp_max_bytes,
                    self.comp_flush_ms)
        return self.max_ops, self.max_bytes, self.flush_ms

    def _enqueue(self, op: _Op, lane: str = "write"):
        arm = False
        fire = None
        max_ops, max_bytes, flush_ms = self._lane_knobs(lane)
        with self._lock:
            if lane == "recon":
                self._pending_recon.append(op)
                self._pending_recon_bytes += op.nbytes
                if self._recon_since is None:
                    self._recon_since = time.monotonic()
                n, nbytes = (len(self._pending_recon),
                             self._pending_recon_bytes)
                armed = self._recon_armed
            elif lane == "comp":
                self._pending_comp.append(op)
                self._pending_comp_bytes += op.nbytes
                if self._comp_since is None:
                    self._comp_since = time.monotonic()
                n, nbytes = (len(self._pending_comp),
                             self._pending_comp_bytes)
                armed = self._comp_armed
            else:
                self._pending.append(op)
                self._pending_bytes += op.nbytes
                if self._pending_since is None:
                    self._pending_since = time.monotonic()
                n, nbytes = len(self._pending), self._pending_bytes
                armed = self._deadline_armed
            if n >= max_ops:
                fire = "max_ops"
            elif nbytes >= max_bytes:
                fire = "max_bytes"
            elif flush_ms <= 0:
                fire = "immediate"
            elif not armed and self._schedule is not None:
                if lane == "recon":
                    self._recon_armed = True
                elif lane == "comp":
                    self._comp_armed = True
                else:
                    self._deadline_armed = True
                arm = True
        if fire is not None:
            self.flush(reason=fire, lane=lane)
        elif arm:
            self._schedule(
                flush_ms / 1000.0,
                lambda: self.flush(reason="deadline", lane=lane))

    def maybe_flush(self) -> bool:
        """Tick backstop: flush any lane whose oldest pending op has
        waited past its deadline window (covers a lost/absent timer)."""
        now = time.monotonic()
        due = []
        with self._lock:
            for lane, pending, since in (
                    ("write", self._pending, self._pending_since),
                    ("recon", self._pending_recon, self._recon_since),
                    ("comp", self._pending_comp, self._comp_since)):
                ms = self._lane_knobs(lane)[2]
                if pending and since is not None \
                        and (now - since) * 1000.0 >= ms:
                    due.append(lane)
        for lane in due:
            self.flush(reason="deadline", lane=lane)
        return bool(due)

    # -- flush / dispatch --------------------------------------------------

    def flush(self, reason: str = "manual", lane: str | None = None
              ) -> int:
        """Dispatch everything pending as megabatch launches.  In
        immediate mode the flights complete inline (after all engine
        locks drop); in batched mode they go to the FIFO completion
        worker so the next tick stages while these fence.  ``lane``
        limits the flush to one lane; default flushes all."""
        lanes = ("write", "recon", "comp") if lane is None else (lane,)
        return sum(self._flush_lane(ln, reason) for ln in lanes)

    def flush_sync(self, lane: str, reason: str = "manual") -> int:
        """Dispatch and complete a lane's pending inline on the
        calling thread, bypassing the completion worker.  For
        submitters that must consume results synchronously while
        possibly holding the daemon lock (deep-scrub parity recheck):
        inline completion re-enters that lock on the caller's own
        thread (RLock), so the caller never waits behind worker-queue
        flights whose callbacks need the lock it holds."""
        return self._flush_lane(lane, reason, force_inline=True)

    def _flush_lane(self, lane: str, reason: str,
                    force_inline: bool = False) -> int:
        inline: list[_Flight] = []
        n = 0
        ms = self._lane_knobs(lane)[2]
        with self._flush_lock:
            with self._lock:
                if lane == "recon":
                    pending = self._pending_recon
                    self._pending_recon = []
                    staged = self._pending_recon_bytes
                    self._pending_recon_bytes = 0
                    self._recon_since = None
                    self._recon_armed = False
                elif lane == "comp":
                    pending = self._pending_comp
                    self._pending_comp = []
                    staged = self._pending_comp_bytes
                    self._pending_comp_bytes = 0
                    self._comp_since = None
                    self._comp_armed = False
                else:
                    pending, self._pending = self._pending, []
                    staged = self._pending_bytes
                    self._pending_bytes = 0
                    self._pending_since = None
                    self._deadline_armed = False
                use_worker = (ms > 0 and not self._stopped
                              and not force_inline)
            if not pending:
                return 0
            prefix = {"recon": "recon_", "comp": "comp_"}.get(lane, "")
            self.stats[f"{prefix}flush_{reason}"] += 1
            with stage("engine_launch"):
                flights = self._dispatch(pending, reason, lane)
            n = len(flights)
            for fl in flights:
                if use_worker:
                    self._ensure_worker()
                    self._flights.put(fl)
                else:
                    inline.append(fl)
        for fl in inline:
            with stage("engine_complete"):
                self._complete(fl)
        if self.on_lane_flush is not None:
            try:
                self.on_lane_flush(lane, len(pending), staged)
            except Exception:       # noqa: BLE001 — accounting hook
                self.stats["callback_errors"] += 1
        if self.store_kick is not None:
            # durability boundary: the completions just dispatched
            # queued their transactions — nudge the WAL group-commit
            # thread so the whole megabatch shares ONE fsync and its
            # acks (gated on commit) release together
            try:
                self.store_kick()
                self.stats[f"{prefix}store_kicks"] += 1
            except Exception:       # noqa: BLE001
                self.stats["callback_errors"] += 1
        return n

    def drain(self):
        """Flush and wait until every in-flight completion has fired
        (shutdown / test barrier)."""
        self.flush(reason="drain")
        self._flights.join()

    def stop(self):
        """Drain, then retire the completion worker.  Later submits
        degrade to the synchronous unbatched path."""
        self._stopped = True
        self.drain()
        w = self._worker
        if w is not None:
            self._flights.put(None)
            w.join(timeout=5.0)
            self._worker = None

    def _ensure_worker(self):
        w = self._worker
        if w is not None and w.is_alive():
            return
        w = threading.Thread(target=self._worker_loop,
                             name=f"batch-{self.name}", daemon=True)
        self._worker = w
        w.start()

    def _worker_loop(self):
        while True:
            fl = self._flights.get()
            try:
                if fl is None:
                    return
                with stage("engine_complete"):
                    self._complete(fl)
            finally:
                self._flights.task_done()

    def _groups(self, pending):
        groups: dict = {}
        floor = max(1, int(self.bucket_floor))
        for op in pending:
            bucket_len = _next_pow2(max(op.length, floor))
            groups.setdefault((op.key, bucket_len), []).append(op)
        return groups

    def _dispatch(self, pending, reason, lane="write") -> list[_Flight]:
        flights = []
        launches_key = {"recon": "recon_launches",
                        "comp": "comp_launches"}.get(lane, "launches")
        for (key, bucket_len), ops in self._groups(pending).items():
            rows = _next_pow2(len(ops))
            span = None
            if self.tracer is not None:
                span = self.tracer.start_span(
                    "megabatch_flush", tags={
                        "layer": "device", "kernel": "megabatch",
                        "op": key[0], "lane": lane,
                        "members": len(ops),
                        "rows": rows, "row_len": bucket_len,
                        "reason": reason})
                if span is not None:
                    for op in ops:
                        if op.span is not None:
                            span.add_link(op.span)
            try:
                if key[0] == "encode":
                    fl = self._launch_encode(key, ops, rows,
                                             bucket_len, span, reason)
                elif key[0] == "digest":
                    fl = self._launch_digest(ops, rows, bucket_len,
                                             span, reason)
                elif key[0] == "recon":
                    fl = self._launch_reconstruct(
                        key, ops, rows, bucket_len, span, reason)
                elif key[0] == "compress":
                    fl = self._launch_compress(ops, rows, bucket_len,
                                               span, reason)
                elif key[0] == "fingerprint":
                    fl = self._launch_fingerprint(ops, rows,
                                                  bucket_len, span,
                                                  reason)
                else:
                    fl = self._launch_recheck(key, ops, rows,
                                              bucket_len, span, reason)
            except Exception as e:  # noqa: BLE001 — one group's
                # launch failure must not kill sibling groups
                self._fail_group(ops, e, span)
                continue
            flights.append(fl)
            self.stats[launches_key] += 1
        return flights

    def _prof_start(self, ops, rows, staged_bytes, reason, op_kind,
                    cache_hit, lane="write", devices=None):
        if self.profiler is None:
            return None
        return self.profiler.start(
            "megabatch", bytes_in=staged_bytes,
            bytes_used=sum(o.nbytes for o in ops),
            rows=rows, rows_used=len(ops), overlap=True,
            devices=devices,
            members=len(ops), reason=reason, op=op_kind,
            cache_hit=cache_hit, lane=lane)

    def _engine_mesh(self):
        """The process-wide cluster mesh when ``use_mesh`` is on and
        more than one device is visible, else None (single-chip paths
        unchanged).  One mesh serves every lane, so all sharded
        executable caches key off the same device grid."""
        if not self.use_mesh:
            return None
        if self._mesh is None:
            import jax
            if len(jax.devices()) <= 1:
                return None
            from ..parallel.mesh import cluster_mesh
            self._mesh = cluster_mesh()
        return self._mesh

    def _mesh_labels(self):
        mesh = self._engine_mesh()
        if mesh is None:
            return None
        if self._mesh_devs is None:
            from ..parallel.mesh import mesh_device_labels
            self._mesh_devs = mesh_device_labels(mesh)
        return self._mesh_devs

    def _launch_encode(self, key, ops, rows, bucket_len, span,
                       reason) -> _Flight:
        from ..ops.gf_jax import GFEncodeDigest
        _kind, k, m, mat = key
        fused = self._fused.get(key)
        if fused is None:
            fused = self._fused[key] = GFEncodeDigest(
                np.frombuffer(mat, dtype=np.uint8).reshape(m, k),
                mesh=self._engine_mesh())
        if fused.mesh is not None:
            # pad the row bucket up so the batch axis divides the mesh
            # (pow2 rows and pow2 device counts nest; odd device
            # counts fall back silently inside GFEncodeDigest)
            rows = max(rows, _next_pow2(fused.mesh.size))
        batch = np.zeros((rows, k, bucket_len), dtype=np.uint8)
        for i, op in enumerate(ops):
            batch[i, :, :op.length] = op.chunks
        shape = (rows, k, bucket_len)
        ln = self._prof_start(ops, rows, batch.nbytes, reason,
                              "encode", fused.export_hits.get(shape,
                                                              False),
                              devices=(self._mesh_labels()
                                       if fused.mesh is not None
                                       else None))
        try:
            out = fused(batch)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("encode", ops, out, bucket_len, rows, ln, span,
                       reason)

    def _launch_digest(self, ops, rows, bucket_len, span,
                       reason) -> _Flight:
        import jax.numpy as jnp
        from ..scrub.crc32c_jax import _batch_kernel
        batch = np.zeros((rows, bucket_len), dtype=np.uint8)
        for i, op in enumerate(ops):
            batch[i, :op.length] = np.frombuffer(op.payload, np.uint8)
        ln = self._prof_start(ops, rows, batch.nbytes, reason,
                              "digest", True)
        try:
            out = _batch_kernel(bucket_len)(
                jnp.asarray(batch), jnp.zeros(rows, jnp.uint32))
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("digest", ops, out, bucket_len, rows, ln, span,
                       reason)

    def _launch_reconstruct(self, key, ops, rows, bucket_len, span,
                            reason) -> _Flight:
        from ..parallel.reconstruct import decode_plan
        _kind, k, m, mat, erasures = key
        coding = np.frombuffer(mat, dtype=np.uint8).reshape(m, k)
        plan = decode_plan(coding, k, m, erasures,
                           cache=self._plan_cache)
        batch = np.zeros((rows, k, bucket_len), dtype=np.uint8)
        for i, op in enumerate(ops):
            batch[i, :, :op.length] = op.chunks
        ln = self._prof_start(ops, rows, batch.nbytes, reason,
                              "recon", key in self._rexec,
                              lane="recon",
                              devices=self._mesh_labels())
        try:
            out = self._run_reconstruct(key, plan, batch)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("recon", ops, out, bucket_len, rows, ln, span,
                       reason, plan=plan)

    def _run_reconstruct(self, key, plan, batch):
        """Pick the reconstruct strategy for one fused group:

        - resident planes (``use_planes``, auto on TPU): expand the
          survivor batch to bit planes once, multiply by the plan's
          stacked matrix — per-matrix operands persist in
          ``_plane_mats`` across the whole sweep.  With the mesh on,
          the planes expand *sharded* over the batch axis and each
          multiply is a shard_map of the local kernel.
        - mesh (``use_mesh`` and >1 device): the shard_map program of
          ``parallel.reconstruct.ShardedEC`` — survivor rows scattered
          to their chunk-id positions, batch padded to a dp multiple.
          Parity-hole erasure patterns ride this launch too: the
          decode fn is built from the plan's stacked ``[k + p, k]``
          matrix, so the all-gather reduce emits the composed
          ``coding ∘ dm`` rows alongside the data rows.
        - default: one cached ``GFLinear`` over the plan's fused
          ``[k + p, k]`` matrix — a single launch per group.
        """
        import jax
        mesh = self._engine_mesh()
        planes = (self.use_planes if self.use_planes is not None
                  else jax.default_backend() == "tpu")
        if planes:
            from ..ops.gf_pallas2 import ResidentPlanes
            rp = ResidentPlanes(
                batch, interpret=jax.default_backend() != "tpu",
                mats=self._plane_mats, mesh=mesh)
            return rp.multiply(plan.matrix)
        if mesh is not None:
            return self._run_mesh(key, plan, batch)
        prog = self._rexec.get(key)
        if prog is None:
            from ..ops.gf_jax import GFLinear
            prog = self._rexec[key] = GFLinear(plan.matrix)
        return prog(batch)

    def _sharded_ec(self, k, m, mat):
        """Cached per-code ShardedEC over the cluster mesh — shared by
        the recovery reconstruct and the scrub recheck paths (one
        compiled program family per code, not per caller)."""
        code_key = (k, m, mat)
        sh = self._sharded.get(code_key)
        if sh is None:
            from ..parallel.reconstruct import ShardedEC
            coding = np.frombuffer(mat, dtype=np.uint8).reshape(m, k)
            # byte payloads in, byte payloads out: word_native stays
            # off so host staging needs no dtype views
            sh = self._sharded[code_key] = ShardedEC(
                coding, k, m, self._engine_mesh(), word_native=False)
        return sh

    def _run_mesh(self, key, plan, batch):
        _kind, k, m, mat = key[:4]
        sh = self._sharded_ec(k, m, mat)
        rows, _k, length = batch.shape
        dp = sh.mesh.shape["dp"]
        b_pad = -(-rows // dp) * dp
        full = np.zeros((b_pad, sh.n_pad, length), dtype=np.uint8)
        for r, sid in enumerate(plan.survivors):
            full[:rows, sid] = batch[:, r]
        # emit="plan": the mesh launch returns the k data rows AND the
        # composed parity rows in plan.out_ids order, so parity-hole
        # patterns complete through the same plan.row_of indexing the
        # fused single-chip matrix uses.
        out = sh.reconstruct(full, plan.erasures, emit="plan")
        return out[:rows]

    def _launch_recheck(self, key, ops, rows, bucket_len, span,
                        reason) -> _Flight:
        if self._engine_mesh() is not None:
            return self._launch_recheck_mesh(key, ops, rows,
                                             bucket_len, span, reason)
        _kind, k, m, mat = key
        cache_hit = key in self._rexec
        prog = self._rexec.get(key)
        if prog is None:
            from ..ops.gf_jax import GFLinear
            prog = self._rexec[key] = GFLinear(
                np.frombuffer(mat, dtype=np.uint8).reshape(m, k))
        batch = np.zeros((rows, k, bucket_len), dtype=np.uint8)
        for i, op in enumerate(ops):
            batch[i, :, :op.length] = op.chunks
        ln = self._prof_start(ops, rows, batch.nbytes, reason,
                              "recheck", cache_hit, lane="recon")
        try:
            out = prog(batch)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("recheck", ops, out, bucket_len, rows, ln, span,
                       reason)

    def _launch_recheck_mesh(self, key, ops, rows, bucket_len, span,
                             reason) -> _Flight:
        """Scrub parity recheck on the mesh: a recheck IS an encode,
        so it rides the same chunk-sharded ShardedEC program the
        recovery lane caches (per-device GF partials XOR-combined over
        ICI) — bit-identical to the single-chip GFLinear, both being
        oracle-exact."""
        _kind, k, m, mat = key
        cache_hit = (k, m, mat) in self._sharded
        sh = self._sharded_ec(k, m, mat)
        dp = sh.mesh.shape["dp"]
        rows = -(-rows // dp) * dp
        batch = np.zeros((rows, k, bucket_len), dtype=np.uint8)
        for i, op in enumerate(ops):
            batch[i, :, :op.length] = op.chunks
        ln = self._prof_start(ops, rows, batch.nbytes, reason,
                              "recheck", cache_hit, lane="recon",
                              devices=self._mesh_labels())
        try:
            out = sh.encode(sh.pad_data(batch))
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("recheck", ops, out, bucket_len, rows, ln, span,
                       reason)

    def _launch_compress(self, ops, rows, bucket_len, span,
                         reason) -> _Flight:
        """Stage one codec's ops into a pow2 megabatch and run the
        codec's device boundary scan (``scan_batch``); host-only
        codecs (zlib) fly with ``out=None`` and finalize entirely in
        ``_complete_comp`` — they still gain the shared flush cadence
        and stats spine."""
        codec = ops[0].codec
        scan = getattr(codec, "scan_batch", None)
        out = None
        if scan is not None:
            batch = np.zeros((rows, bucket_len), dtype=np.uint8)
            for i, op in enumerate(ops):
                batch[i, :op.length] = np.frombuffer(op.payload,
                                                     np.uint8)
            staged = batch.nbytes
        else:
            staged = sum(op.length for op in ops)
        ln = self._prof_start(ops, rows, staged, reason, "compress",
                              True, lane="comp")
        try:
            if scan is not None:
                out = scan(batch)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("compress", ops, out, bucket_len, rows, ln,
                       span, reason)

    def _launch_fingerprint(self, ops, rows, bucket_len, span,
                            reason) -> _Flight:
        """Two fused launches per flush: the gear-hash boundary scan
        over the pow2 megabatch, then — after the host pass walks the
        sparse candidate lists into bounded chunk spans — one CRC-32C
        batch launch digesting *every* chunk of the flush at once.
        The flight carries the finished per-op values; the fence in
        ``_complete_comp`` only fires completions."""
        import zlib as _zlib
        import jax.numpy as jnp
        from ..scrub.crc32c_jax import (_batch_kernel,
                                        crc32c_zero_unpad)
        chunker = ops[0].chunker
        mesh = self._engine_mesh()
        if mesh is not None:
            # pad rows so the gear scan's row axis divides the mesh —
            # zero rows hash to a constant the cut walk never reads
            rows = max(rows, _next_pow2(mesh.size))
        batch = np.zeros((rows, bucket_len), dtype=np.uint8)
        for i, op in enumerate(ops):
            batch[i, :op.length] = np.frombuffer(op.payload, np.uint8)
        ln = self._prof_start(ops, rows, batch.nbytes, reason,
                              "fingerprint", True, lane="comp",
                              devices=self._mesh_labels())
        try:
            hashes = np.asarray(chunker.hash_batch(batch, mesh=mesh))
            spans_per_op = []
            all_chunks = []
            for i, op in enumerate(ops):
                spans = []
                last = 0
                for c in chunker.cuts_from_hashes(hashes[i],
                                                  op.length):
                    spans.append((last, c - last))
                    all_chunks.append(op.payload[last:c])
                    last = c
                spans_per_op.append(spans)
            if all_chunks:
                cbucket = _next_pow2(
                    max(max(len(c) for c in all_chunks), 32))
                cbatch = np.zeros((len(all_chunks), cbucket),
                                  dtype=np.uint8)
                for i, c in enumerate(all_chunks):
                    cbatch[i, :len(c)] = np.frombuffer(c, np.uint8)
                crcs = np.asarray(_batch_kernel(cbucket)(
                    jnp.asarray(cbatch),
                    jnp.zeros(len(all_chunks), jnp.uint32)))
            values = []
            j = 0
            for spans in spans_per_op:
                vals = []
                for off, clen in spans:
                    c = all_chunks[j]
                    crc = crc32c_zero_unpad(int(crcs[j]),
                                            cbucket - len(c))
                    vals.append((off, clen,
                                 f"{crc:08x}"
                                 f"{_zlib.crc32(c) & 0xFFFFFFFF:08x}"
                                 f"{len(c):08x}"))
                    j += 1
                values.append(vals)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.dispatched()
        return _Flight("fingerprint", ops, values, bucket_len, rows,
                       ln, span, reason)

    # -- completion --------------------------------------------------------

    def _complete(self, fl: _Flight):
        from ..scrub.crc32c_jax import crc32c_zero_unpad
        if fl.kind in ("compress", "fingerprint"):
            self._complete_comp(fl)
            return
        parity = crcs = rec = None
        try:
            self._note_devices(fl)
            if fl.kind == "encode":
                parity = np.asarray(fl.out[0])
                crcs = np.asarray(fl.out[1])
                bytes_out = int(parity.nbytes) + int(crcs.nbytes)
            elif fl.kind == "digest":
                crcs = np.asarray(fl.out)
                bytes_out = int(crcs.nbytes)
            else:               # recon | recheck
                rec = np.asarray(fl.out)
                bytes_out = int(rec.nbytes)
        except Exception as e:      # noqa: BLE001 — launch died at the
            if fl.ln is not None:   # fence: fail every member
                fl.ln.abort()
            self._fail_group(fl.ops, e, fl.span)
            return
        if fl.ln is not None:
            fl.ln.finish(bytes_out=bytes_out)
        if fl.span is not None:
            fl.span.finish()
        info = {"rows": fl.bucket, "members": len(fl.ops),
                "row_len": fl.length, "reason": fl.reason}
        if rec is not None:
            info["lane"] = "recon"
            plan = fl.plan
            for i, op in enumerate(fl.ops):
                try:
                    if fl.kind == "recheck":
                        value = np.ascontiguousarray(
                            rec[i, :, :op.length])
                    else:
                        value = {
                            cid: (op.passthrough[cid]
                                  if cid in op.passthrough else
                                  np.ascontiguousarray(
                                      rec[i, plan.row_of[cid],
                                          :op.length]))
                            for cid in op.want}
                    op.comp.info = info
                    op.comp._fire(value=value)
                    self.stats["recon_ops_completed"] += 1
                except Exception:   # noqa: BLE001 — a member's
                    # callback blowing up must not starve its siblings
                    self.stats["callback_errors"] += 1
            return
        for i, op in enumerate(fl.ops):
            pad = fl.length - op.length
            try:
                if fl.kind == "encode":
                    k = op.chunks.shape[0]
                    m = parity.shape[1]
                    shard_chunks = {j: op.chunks[j].tobytes()
                                    for j in range(k)}
                    for j in range(m):
                        shard_chunks[k + j] = \
                            parity[i, j, :op.length].tobytes()
                    hinfos = {s: crc32c_zero_unpad(int(crcs[i, s]),
                                                   pad)
                              for s in range(k + m)}
                    value = (shard_chunks, hinfos)
                else:
                    value = crc32c_zero_unpad(int(crcs[i]), pad)
                op.comp.info = info
                op.comp._fire(value=value)
                self.stats["ops_completed"] += 1
            except Exception:       # noqa: BLE001 — a member's
                # callback blowing up must not starve its siblings
                self.stats["callback_errors"] += 1

    def _note_devices(self, fl: _Flight):
        """Where the flight's outputs live (``platform:id``; ``host``
        for a numpy result) — what a chip run checks to prove no lane
        quietly ran off the device."""
        outs = fl.out if isinstance(fl.out, tuple) else (fl.out,)
        seen = self.out_devices.setdefault(fl.kind, set())
        for o in outs:
            devs = getattr(o, "devices", None)
            seen.update([f"{d.platform}:{d.id}" for d in devs()]
                        if devs is not None else ["host"])

    def _complete_comp(self, fl: _Flight):
        """Fence + per-member finalize for the compression lane.  A
        member whose codec finalize blows up fails alone — group
        isolation inside the flight, same contract as the write
        lane's per-group isolation outside it."""
        try:
            if fl.kind == "compress":
                mask = (np.asarray(fl.out) if fl.out is not None
                        else None)
            else:
                values = fl.out     # precomputed in the launch half
        except Exception as e:      # noqa: BLE001 — died at the fence
            if fl.ln is not None:
                fl.ln.abort()
            self._fail_group(fl.ops, e, fl.span)
            return
        info = {"rows": fl.bucket, "members": len(fl.ops),
                "row_len": fl.length, "reason": fl.reason,
                "lane": "comp"}
        bytes_out = 0
        for i, op in enumerate(fl.ops):
            try:
                if fl.kind == "compress":
                    codec = op.codec
                    if mask is not None:
                        row = np.frombuffer(op.payload, np.uint8)
                        blob = codec.compress_from_scan(
                            row, op.length, mask[i])
                    else:
                        blob = codec.compress(op.payload)
                    self.stats["comp_bytes_in"] += op.length
                    if op.mode != "force" and len(blob) >= op.length:
                        self.stats["comp_passthrough"] += 1
                        self.stats["comp_bytes_out"] += op.length
                        bytes_out += op.length
                        value = (op.payload, None)
                    else:
                        self.stats["comp_bytes_out"] += len(blob)
                        bytes_out += len(blob)
                        value = (blob, {"algo": codec.name,
                                        "len": op.length})
                else:
                    self.stats["comp_fingerprint_bytes"] += op.length
                    value = values[i]
            except Exception as e:  # noqa: BLE001 — poisoned member
                self.stats["comp_ops_failed"] += 1
                try:
                    op.comp._fire(error=e)
                except Exception:   # noqa: BLE001
                    self.stats["callback_errors"] += 1
                continue
            op.comp.info = info
            try:
                op.comp._fire(value=value)
                self.stats["comp_ops_completed"] += 1
            except Exception:       # noqa: BLE001 — a member's
                # callback blowing up must not starve its siblings
                self.stats["callback_errors"] += 1
        if fl.ln is not None:
            fl.ln.finish(bytes_out=bytes_out)
        if fl.span is not None:
            fl.span.finish()

    def _fail_group(self, ops, err, span):
        if span is not None:
            span.set_tag("error", repr(err))
            span.finish()
        for op in ops:
            self.stats["recon_ops_failed"
                       if op.kind in ("recon", "recheck")
                       else "comp_ops_failed"
                       if op.kind in ("compress", "fingerprint")
                       else "ops_failed"] += 1
            try:
                op.comp._fire(error=err)
            except Exception:       # noqa: BLE001
                self.stats["callback_errors"] += 1

    # -- introspection -----------------------------------------------------

    def dump(self) -> dict:
        with self._lock:
            pending = len(self._pending)
            pending_bytes = self._pending_bytes
            rpending = len(self._pending_recon)
            rpending_bytes = self._pending_recon_bytes
            cpending = len(self._pending_comp)
            cpending_bytes = self._pending_comp_bytes
        d = dict(self.stats)
        d.update(enabled=self.enabled, flush_ms=self.flush_ms,
                 max_bytes=self.max_bytes, max_ops=self.max_ops,
                 bucket_floor=self.bucket_floor,
                 pending_ops=pending, pending_bytes=pending_bytes,
                 recon_enabled=self.recon_enabled,
                 recon_flush_ms=self.recon_flush_ms,
                 recon_max_bytes=self.recon_max_bytes,
                 recon_max_ops=self.recon_max_ops,
                 recon_pending_ops=rpending,
                 recon_pending_bytes=rpending_bytes,
                 recon_use_mesh=self.use_mesh,
                 recon_plans=len(self._plan_cache),
                 comp_enabled=self.comp_enabled,
                 comp_flush_ms=self.comp_flush_ms,
                 comp_max_bytes=self.comp_max_bytes,
                 comp_max_ops=self.comp_max_ops,
                 comp_segment_bytes=self.comp_segment_bytes,
                 comp_pending_ops=cpending,
                 comp_pending_bytes=cpending_bytes,
                 out_devices={k: sorted(v)
                              for k, v in self.out_devices.items()},
                 inflight=self._flights.unfinished_tasks)
        return d
