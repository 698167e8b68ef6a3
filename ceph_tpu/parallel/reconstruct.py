"""SPMD erasure-code pipeline: chunk-sharded encode + degraded-read
reconstruct under ``shard_map``.

Reference behavior being re-created TPU-natively (SURVEY.md §4.2-4.3):

- EC write: ``ECBackend::submit_transaction`` fans sub-writes of k+m chunks
  to k+m OSDs.  Here a stripe's chunk axis is sharded over the mesh's
  ``shard`` axis; computing parity requires combining contributions from
  data chunks on different devices — an XOR-reduction that rides ICI
  (implemented as an all-gather of local GF partial products + local XOR,
  exactly the collective the scaling-book recipe would pick for a small
  contraction axis).
- EC degraded read: ``objects_read_and_reconstruct`` gathers any k
  surviving shards from peer OSDs.  Here: ``jax.lax.all_gather`` of the
  surviving shard rows over ICI, then each device decodes its local stripe
  batch with the cached inverse submatrix.

Chunk ids are padded up to a multiple of the shard-axis size so every
device owns the same number of chunk rows (static shapes for XLA).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import rs
from ..ops.gf import gf_matmul
from ..ops.gf_jax import _bit_layout_matrix, gf_matmul_bits
from ..ops.gf_pallas2 import (_BIT_MASK, _gf_apply_words, block_diag4,
                              _word_operands)


class DecodePlan:
    """Survivor selection + recovery matrices for one erasure pattern.

    One plan answers every question a batched reconstruct needs about
    a (coding matrix, erasure set) pair:

    - ``survivors``: the first k surviving chunk ids in id order —
      the exact selection ``ErasureCode::_minimum_to_decode`` makes,
      so plan-driven decodes are byte-identical to the per-stripe
      path;
    - ``dm`` [k, k]: the decode matrix over those survivors;
    - ``parity_matrix`` [p, k] or None: for the p *erased parity*
      rows, the GF(2^8) composition ``coding[j] ∘ dm`` — parity
      straight from survivors, no decode-then-encode round trip
      (associativity makes the composition byte-exact);
    - ``matrix`` [k + p, k]: dm and parity_matrix stacked, so one
      fused matmul yields every recoverable row;
    - ``row_of``: chunk id → row in that fused output.
    """

    __slots__ = ("k", "m", "erasures", "survivors", "dm",
                 "parity_matrix", "matrix", "out_ids", "row_of")

    def __init__(self, coding: np.ndarray, k: int, m: int,
                 erasures: tuple[int, ...]):
        coding = np.asarray(coding, dtype=np.uint8)
        self.k, self.m = k, m
        self.erasures = tuple(sorted(erasures))
        self.survivors = tuple(
            i for i in range(k + m) if i not in self.erasures)[:k]
        self.dm = rs.decode_matrix(coding, k, list(self.erasures))
        miss_par = [j for j in range(m) if k + j in self.erasures]
        if miss_par:
            self.parity_matrix = gf_matmul(coding[miss_par], self.dm)
            self.matrix = np.vstack([self.dm, self.parity_matrix])
        else:
            self.parity_matrix = None
            self.matrix = self.dm
        self.out_ids = tuple(range(k)) + tuple(k + j for j in miss_par)
        self.row_of = {cid: r for r, cid in enumerate(self.out_ids)}


_PLAN_CACHE: dict = {}


def decode_plan(coding: np.ndarray, k: int, m: int,
                erasures, cache: dict | None = None) -> DecodePlan:
    """Cached :class:`DecodePlan` lookup.  Real clusters see a handful
    of erasure patterns at a time (reference: ECBackend caches decode
    tables per want/avail set), so plans persist for a whole recovery
    sweep — pass ``cache`` to scope the cache to an owner (the batch
    engine's reconstruct lane), default is process-wide."""
    key = (coding.tobytes(), k, m, tuple(sorted(erasures)))
    store = _PLAN_CACHE if cache is None else cache
    plan = store.get(key)
    if plan is None:
        plan = store[key] = DecodePlan(coding, k, m, key[3])
    return plan


class ShardedEC:
    """Erasure code over a (dp, shard) mesh.

    Layout: stripes [B, nchunks_padded, C] with spec P('dp', 'shard', None):
    stripe batches over dp, chunk ids over shard.

    ``word_native`` (auto: on for the TPU backend) switches the chunk
    payload dtype from uint8 [.., C] to int32 words [.., C/4] and the
    local GF multiply from the XLA bitmatrix path to the fused Pallas
    word kernel — the 10x-over-native encode path
    (`gf_pallas2.gf_matmul_words`); uint8 payloads on TPU pay a 4x
    sublane-padding tax per HBM read.  Host conversion is a free
    ``bytes.view("<i4")``.  The collectives are dtype-agnostic.
    """

    def __init__(self, coding: np.ndarray, k: int, m: int, mesh: Mesh,
                 word_native: bool | None = None):
        self.coding = np.asarray(coding, dtype=np.uint8)
        self.k, self.m = k, m
        self.mesh = mesh
        self.word_native = (jax.default_backend() == "tpu"
                            if word_native is None else word_native)
        self.payload_dtype = (np.int32 if self.word_native
                              else np.uint8)
        self.shard_n = mesh.shape["shard"]
        self.k_pad = -(-k // self.shard_n) * self.shard_n
        self.n_pad = -(-(k + m) // self.shard_n) * self.shard_n
        # coding matrix padded on the data axis [m, k_pad]
        cpad = np.zeros((m, self.k_pad), dtype=np.uint8)
        cpad[:, :k] = self.coding
        self._coding_pad = cpad
        self._decode_cache: dict[tuple[int, ...], object] = {}
        from .mesh import mesh_device_labels
        self._dev_labels = mesh_device_labels(mesh)

        self._encode = jax.jit(self._build_encode())

    # -- encode: data chunks sharded, XOR-combine partials over ICI --------
    def _build_encode(self):
        mesh = self.mesh
        shard_n = self.shard_n
        klocal = self.k_pad // shard_n
        m = self.m
        # bit-layout matrix of the padded coding [8m, 8*k_pad]: the
        # local multiply runs on the MXU bitmatrix path (the same math
        # GFLinear's production backend uses), not the table-gather.
        # Columns interleave as (bit s, chunk i) = s*k_pad + i, so a
        # device's chunk-column slice is strided — reshape to
        # [8m, 8, k_pad] and slice the chunk axis.
        bm_full = _bit_layout_matrix(self._coding_pad)
        bm3 = jnp.asarray(
            bm_full.reshape(8 * m, 8, self.k_pad))
        if self.word_native:
            # block-diag word matrix [32m, 32*k_pad]; columns factor as
            # ((b*8+s), chunk i) so the per-device chunk-column slice
            # is a dynamic_slice on the reshaped last axis
            bd4 = jnp.asarray(block_diag4(bm_full).reshape(
                32 * m, 32, self.k_pad))
            mrow_l = jnp.asarray(np.array(
                [_BIT_MASK[r // klocal] for r in range(32 * klocal)],
                dtype=np.int32).reshape(32 * klocal, 1))

        # Mosaic lowering of the fused word kernel requires a real TPU;
        # off-TPU (CPU equivalence tests, dev boxes) run it in Pallas
        # interpret mode instead of failing at lowering.
        interpret = jax.default_backend() != "tpu"

        def local_fn(data):  # data: [Bl, klocal, C] (or Cw words)
            idx = jax.lax.axis_index("shard")
            if self.word_native:
                cols = jax.lax.dynamic_slice_in_dim(
                    bd4, idx * klocal, klocal, axis=2).reshape(
                        32 * m, 32 * klocal)
                partial = _gf_apply_words(cols, mrow_l, data,
                                          k=klocal, m=m,
                                          interpret=interpret)
            else:
                cols3 = jax.lax.dynamic_slice_in_dim(
                    bm3, idx * klocal, klocal, axis=2)
                cols = cols3.reshape(8 * m, 8 * klocal)
                partial = gf_matmul_bits(cols, data, m)  # [Bl, m, C]
            # XOR-combine partials across the shard axis via all-gather
            # (ICI); every device ends with the full parity of its stripes.
            gathered = jax.lax.all_gather(partial, "shard", axis=0)
            parity = jax.lax.reduce(gathered,
                                    np.zeros((), gathered.dtype)[()],
                                    jax.lax.bitwise_xor, dimensions=(0,))
            return parity  # [Bl, m, C] replicated over shard

        def sharded_encode(data):  # [B, k_pad, C], P('dp','shard',None)
            # out is replicated over 'shard' by construction (all_gather +
            # full XOR-reduce); the static VMA check can't see that.
            # Traced under x64=False: every dtype here is explicit, and
            # an embedding process with x64 on (the CRUSH mapper needs
            # it) otherwise widens internals.
            with jax.enable_x64(False):
                return jax.shard_map(
                    local_fn, mesh=mesh,
                    in_specs=P("dp", "shard", None),
                    out_specs=P("dp", None, None), check_vma=False)(data)

        return sharded_encode

    def pad_data(self, data: np.ndarray) -> np.ndarray:
        """[B, k, C] -> [B, k_pad, C] zero-padded (payload dtype kept:
        uint8 bytes or int32 words)."""
        B, k, C = data.shape
        assert k == self.k
        out = np.zeros((B, self.k_pad, C), dtype=data.dtype)
        out[:, :k] = data
        return out

    def to_payload(self, data: np.ndarray) -> np.ndarray:
        """Host bytes -> this instance's payload dtype (free view)."""
        if self.word_native:
            return np.ascontiguousarray(data).view("<i4")
        return data

    def payload_to_bytes(self, arr: np.ndarray) -> np.ndarray:
        if self.word_native:
            return np.ascontiguousarray(arr).view("<u1")
        return np.asarray(arr)

    def shard_array(self, arr: np.ndarray, spec: P) -> jax.Array:
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def encode(self, data_padded) -> jax.Array:
        """[B, k_pad, C] (sharded or host) -> parity [B, m, C]."""
        from ..core.device_profiler import DeviceProfiler
        nbytes = getattr(data_padded, "nbytes", 0)
        B = int(data_padded.shape[0])
        ln = DeviceProfiler.active().start(
            "sharded_encode", bytes_in=nbytes,
            rows=B * self.k_pad, rows_used=B * self.k,
            devices=self._dev_labels)
        try:
            out = self._encode(data_padded)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.finish(out=out, bytes_out=getattr(out, "nbytes", 0))
        return out

    # -- degraded read: all-gather survivors, decode locally ---------------
    def _decode_fn(self, erasures: tuple[int, ...]):
        # per-instance cache (an lru_cache on the method would pin self and
        # share one global budget across instances)
        cached = self._decode_cache.get(erasures)
        if cached is not None:
            return cached
        fn = self._build_decode_fn(erasures)
        self._decode_cache[erasures] = fn
        return fn

    def _build_decode_fn(self, erasures: tuple[int, ...]):
        mesh = self.mesh
        k, m = self.k, self.m
        # The plan's stacked [k + p, k] matrix covers parity-hole
        # patterns too: rows 0..k-1 are the decode matrix (data
        # chunks), rows k.. are the composed ``coding[j] ∘ dm`` for
        # each erased parity row — GF associativity makes parity
        # straight from survivors byte-exact, so the all-gather reduce
        # path emits every recoverable row in one launch instead of
        # bailing to single-chip whenever a parity row is erased.
        plan = decode_plan(self.coding, k, m, erasures)
        pbits_np = _bit_layout_matrix(plan.matrix)
        pbits = jnp.asarray(pbits_np)
        nrows = plan.matrix.shape[0]
        surv_idx = jnp.asarray(np.array(plan.survivors, dtype=np.int32))
        if self.word_native:
            wcache: dict = {}
            wbd, wmrow = _word_operands(pbits_np, k, wcache)
        interpret = jax.default_backend() != "tpu"  # see _build_encode

        def local_fn(chunks):  # [Bl, nlocal, C] — this device's chunk rows
            # gather every device's chunk rows over ICI (the sub-read fan-in)
            full = jax.lax.all_gather(chunks, "shard", axis=0)
            # full: [shard_n, Bl, nlocal, C]; chunk id = shard*nlocal + local
            full = jnp.moveaxis(full, 2, 1).reshape(
                -1, chunks.shape[0], chunks.shape[2])  # [n_pad, Bl, C]
            surv = full[surv_idx]                      # [k, Bl, C]
            surv = jnp.moveaxis(surv, 1, 0)            # [Bl, k, C]
            if self.word_native:
                # fused Pallas word kernel (the production decode path)
                data = _gf_apply_words(wbd, wmrow, surv,
                                       k=k, m=nrows,
                                       interpret=interpret)
            else:
                # MXU bitmatrix decode (byte-exact vs the oracle)
                data = gf_matmul_bits(pbits, surv, nrows)
            return data

        def sharded_reconstruct(chunks):  # [B, n_pad, C], sharded as data
            # replicated over 'shard' by construction (decode after
            # gather); x64=False at trace time — see _build_encode
            with jax.enable_x64(False):
                return jax.shard_map(
                    local_fn, mesh=mesh,
                    in_specs=P("dp", "shard", None),
                    out_specs=P("dp", None, None), check_vma=False)(chunks)

        return jax.jit(sharded_reconstruct)

    def reconstruct(self, chunks_padded, erasures: tuple[int, ...],
                    emit: str = "data") -> jax.Array:
        """[B, n_pad, C] chunk-sharded -> recovered rows.

        ``erasures`` lists erased chunk ids; their rows in the input are
        ignored (may be garbage/zeros).  ``emit`` selects the output
        rows: ``"data"`` (default) returns the k data chunks
        [B, k, C]; ``"plan"`` returns every recoverable row in the
        decode plan's ``out_ids`` order [B, k + p, C] — data chunks
        followed by the erased parity chunks, so parity-hole erasure
        patterns ride the mesh launch too (``DecodePlan.row_of`` maps
        chunk id → row).
        """
        from ..core.device_profiler import DeviceProfiler
        if emit not in ("data", "plan"):
            raise ValueError(f"emit must be 'data' or 'plan': {emit!r}")
        key = tuple(sorted(erasures))
        B = int(chunks_padded.shape[0])
        ln = DeviceProfiler.active().start(
            "sharded_reconstruct",
            bytes_in=getattr(chunks_padded, "nbytes", 0),
            rows=B * self.n_pad, rows_used=B * (self.k + self.m),
            cache_hit=key in self._decode_cache,
            devices=self._dev_labels)
        try:
            out = self._decode_fn(key)(chunks_padded)
            if emit == "data":
                out = out[:, :self.k]
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.finish(out=out, bytes_out=getattr(out, "nbytes", 0))
        return out

    def reconstruct_batch(self, groups: dict) -> dict:
        """Batched multi-pattern entry: ``{erasures: chunks_padded
        [B, n_pad, C]}`` → ``{erasures: data [B, k, C]}``.

        One shard_map launch per distinct erasure pattern; decode
        programs come from the per-instance ``_decode_cache``, so a
        recovery sweep that mixes patterns (different failed shards
        across PGs) compiles each pattern once and then replays
        executables.  Results stay on device (callers fence)."""
        return {tuple(sorted(er)): self.reconstruct(cp, er)
                for er, cp in groups.items()}

    def assemble_chunks(self, data_padded, parity) -> jnp.ndarray:
        """Lay out the [B, n_pad, C] chunk array `_decode_fn` expects:
        data rows 0..k-1, parity rows k..k+m-1, zero padding to n_pad.
        The single definition of that implicit layout contract — the
        bench and the multichip dryrun build their inputs through it
        too."""
        B = data_padded.shape[0]
        C = data_padded.shape[2]
        parity = jnp.asarray(parity)
        return jnp.concatenate(
            [data_padded[:, :self.k], parity,
             jnp.zeros((B, self.n_pad - self.k - self.m, C),
                       parity.dtype)], axis=1)

    # -- the full pipeline step (flagship "train step") --------------------
    def pipeline_step(self, data_padded, erasures: tuple[int, ...]):
        """Encode, then reconstruct with ``erasures`` erased, returning
        (parity, recovered_data).  The compiled graph contains both the
        XOR-combine and the all-gather collectives — this is the program
        `__graft_entry__.dryrun_multichip` compiles over an N-device mesh.
        """
        parity = self._encode(data_padded)
        recovered = self._decode_fn(tuple(sorted(erasures)))(
            self.assemble_chunks(data_padded, parity))[:, :self.k]
        return parity, recovered
