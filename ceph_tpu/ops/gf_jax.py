"""GF(2^8) linear algebra in JAX — the TPU execution path for erasure codes.

Two formulations, both byte-exact against the NumPy oracle in
``ceph_tpu.ops.gf``:

1. **bitmatrix matmul** (`gf_matmul_bits`): the GF(2^8) coefficient matrix
   C [m, k] expands to a GF(2) matrix; data bytes expand to bit-planes; the
   product is an int8 matmul with int32 accumulation followed by a mod-2
   parity and bit re-packing.  This keeps the hot loop on the MXU, which is
   exactly why this framework exists (reference hot loop:
   ``gf-complete``'s ``galois_w08_region_multiply`` SIMD inner loop behind
   ``src/erasure-code/jerasure``; SURVEY.md §4.2).
2. **table gather** (`gf_matmul_gather`): 256x256 product-table lookup +
   XOR reduce.  Simpler, used for cross-checking and small shapes.

Layout convention for the bitmatrix path (chosen to avoid intra-lane
shuffles on TPU):

- data bit-planes are stacked along the contraction axis in (bit, chunk)
  order: plane row ``s*k + i`` holds bit ``s`` of data chunk ``i``;
- output bit rows are produced in (bit, parity) order: row ``r*m + j`` is
  bit ``r`` of parity chunk ``j``;
- re-packing bytes is then 8 strided row-slices combined with shifts —
  pure elementwise ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .gf import GF_MUL_TABLE, gf_bitmatrix


def _bit_layout_matrix(coding: np.ndarray) -> np.ndarray:
    """[m, k] uint8 -> [8m, 8k] 0/1 int8 bitmatrix in (bit, chunk) layout.

    Row r*m+j, column s*k+i = BM(coding[j, i])[r, s].
    """
    coding = np.asarray(coding, dtype=np.uint8)
    m, k = coding.shape
    bm = gf_bitmatrix(coding)            # [m, k, 8, 8] (j, i, r, s)
    bm = bm.transpose(2, 0, 3, 1)        # [8(r), m(j), 8(s), k(i)]
    return bm.reshape(8 * m, 8 * k).astype(np.int8)


def _expand_bits(data: jnp.ndarray) -> jnp.ndarray:
    """[..., k, n] uint8 -> [..., 8k, n] int8 bit-planes in (bit, chunk) order."""
    k = data.shape[-2]
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(8, 1, 1)
    bits = (data[..., None, :, :] >> shifts) & jnp.uint8(1)   # [..., 8, k, n]
    return bits.reshape(*data.shape[:-2], 8 * k, data.shape[-1]).astype(jnp.int8)


def _pack_bits(bits: jnp.ndarray, m: int) -> jnp.ndarray:
    """[..., 8m, n] int32 0/1 in (bit, parity) order -> [..., m, n] uint8."""
    b = bits.reshape(*bits.shape[:-2], 8, m, bits.shape[-1])
    shifts = jnp.arange(8, dtype=jnp.int32).reshape(8, 1, 1)
    return jnp.sum(b << shifts, axis=-3).astype(jnp.uint8)


def gf_matmul_bits(bitmat: jnp.ndarray, data: jnp.ndarray, m: int) -> jnp.ndarray:
    """GF(2^8) matmul via GF(2) int8 matmul on the MXU.

    bitmat: [8m, 8k] int8 from `_bit_layout_matrix`.
    data:   [..., k, n] uint8.
    Returns [..., m, n] uint8.
    """
    dbits = _expand_bits(data)
    acc = jax.lax.dot_general(
        bitmat, dbits,
        dimension_numbers=(((1,), (dbits.ndim - 2,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    # dot_general output: [8m, ..., n] — move the row axis back
    if dbits.ndim > 2:
        acc = jnp.moveaxis(acc, 0, -2)
    return _pack_bits(acc & 1, m)


def gf_matmul_gather(coding: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """GF(2^8) matmul via product-table gather + XOR reduce.

    coding: [m, k] uint8; data: [..., k, n] uint8 -> [..., m, n] uint8.
    """
    table = jnp.asarray(GF_MUL_TABLE.reshape(-1))
    idx = (coding.astype(jnp.int32)[:, :, None] * 256
           + data.astype(jnp.int32)[..., None, :, :])
    prods = table[idx]                       # [..., m, k, n]
    return jax.lax.reduce(
        prods, np.uint8(0), jax.lax.bitwise_xor, dimensions=(prods.ndim - 2,))


class GFLinear:
    """A compiled GF(2^8) linear map (encode or decode step) over batches.

    Wraps a fixed coefficient matrix [m, k]; calling it on data
    [batch..., k, n] uint8 returns [batch..., m, n] uint8.

    Backends:
    - ``"pallas"`` — the fused VMEM kernel
      (`ceph_tpu.ops.gf_pallas2`), the TPU production path: bytes
      processed 4-per-lane as i32 words, bit-planes expanded in VMEM,
      a 256-deep (at k=8) GF(2) matmul on the MXU, parity packed back
      to words — one HBM read of the data, one write of the parity;
    - ``"pallas-interpret"`` — the same kernel in interpret mode, for
      CPU byte-exactness tests;
    - ``"xla"`` — the dot_general bitmatrix composition above (works on
      any backend; what CPU tests run);
    - ``"auto"`` (default) — pallas on TPU, xla elsewhere.
    Any other name raises ``ValueError``.
    """

    BACKENDS = ("auto", "xla", "pallas", "pallas-interpret")

    def __init__(self, coding: np.ndarray, use_bits: bool = True,
                 backend: str = "auto"):
        self.coding = np.asarray(coding, dtype=np.uint8)
        self.m, self.k = self.coding.shape
        self.use_bits = use_bits
        if backend not in self.BACKENDS:
            raise ValueError(f"unknown GFLinear backend {backend!r}; "
                             f"one of {self.BACKENDS}")
        if backend == "auto":
            backend = ("pallas" if jax.default_backend() == "tpu"
                       and use_bits else "xla")
        self.backend = backend
        if use_bits:
            self._mat = jnp.asarray(_bit_layout_matrix(self.coding))
        else:
            self._mat = jnp.asarray(self.coding)
        # the pallas path jits internally (and interpret mode under an
        # outer jit miscompiles on the CPU backend); jit only the
        # XLA-composed paths here
        if self.backend.startswith("pallas") and use_bits is False:
            raise ValueError("pallas backends are bitmatrix-only")

        def gf_linear(data):        # the program's name in a trace
            return self._apply(data)
        self._fn = (self._apply if self.backend.startswith("pallas")
                    else jax.jit(gf_linear))
        # persistent warm start (XLA path only): per input shape, the
        # lowered program round-trips through the export cache exactly
        # like the CRUSH mapper's — a fresh process deserializes
        # instead of re-tracing the encode/decode programs
        self._shape_fns: dict[tuple, object] = {}
        self.export_hits: dict[tuple, bool] = {}

    def _fn_for_shape(self, shape: tuple):
        fn = self._shape_fns.get(shape)
        if fn is not None:
            return fn
        fn, hit = self._warm_start(shape)
        self._shape_fns[shape] = fn
        self.export_hits[shape] = hit
        return fn

    def _warm_start(self, shape: tuple):
        from ..native.aot import CompileCache, cached_export, named_call
        if CompileCache.default() is None:
            return self._fn, False
        import hashlib
        # v2: the exported program is named gf_linear (v1: _apply)
        key = {"kind": "gf_linear/v2", "jax": jax.__version__,
               "x64": bool(jax.config.jax_enable_x64),
               "backend": jax.default_backend(),
               "use_bits": self.use_bits, "m": self.m, "k": self.k,
               "mat": hashlib.sha256(self.coding.tobytes()).hexdigest(),
               "shape": list(shape)}
        try:
            exported, hit = cached_export(
                "ec", key, lambda: self._fn,
                (jax.ShapeDtypeStruct(shape, jnp.uint8),))
            return jax.jit(named_call("gf_linear", exported)), hit
        except Exception:
            return self._fn, False

    def _apply(self, data: jnp.ndarray) -> jnp.ndarray:
        if self.backend in ("pallas", "pallas-interpret"):
            from .gf_pallas2 import gf_matmul_pallas2
            if not hasattr(self, "_bdmats"):
                self._bdmats = {}
            return gf_matmul_pallas2(
                self._mat, data, self.m,
                interpret=self.backend == "pallas-interpret",
                bdmats=self._bdmats)
        if self.use_bits:
            return gf_matmul_bits(self._mat, data, self.m)
        return gf_matmul_gather(self._mat, data)

    def __call__(self, data) -> jax.Array:
        from ..core.device_profiler import DeviceProfiler
        arr = jnp.asarray(data, dtype=jnp.uint8)
        rows = int(arr.shape[0]) if arr.ndim else 0
        ln = DeviceProfiler.active().start(
            "gf_encode", bytes_in=arr.nbytes, rows=rows,
            cache_hit=self.export_hits.get(arr.shape, False),
            backend=self.backend)
        try:
            if self.backend == "xla":
                out = self._fn_for_shape(arr.shape)(arr)
            else:
                out = self._fn(arr)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.finish(out=out, bytes_out=out.nbytes,
                      cache_hit=self.export_hits.get(arr.shape, False))
        return out


class GFLinearWords:
    """Word-native GF(2^8) linear map: [..., k, nw] int32 -> [..., m, nw].

    The 10x-over-native production encode path (see
    `gf_pallas2.gf_matmul_words` for the measured rationale): chunk
    payloads stay int32 for their whole device lifetime, so no call
    pays the u8<->i32 relayout or the uint8 sublane-padding tax.
    Host-side conversion is a free ``bytes.view("<i4")``.

    Mirrors the reference's region-multiply entry
    (``galois_w08_region_multiply`` behind src/erasure-code/jerasure —
    SURVEY.md §4.2) at word granularity; byte-exactness vs the scalar
    oracle is asserted in tests/test_gf_pallas2.py and before any
    bench timing.
    """

    def __init__(self, coding: np.ndarray, interpret: bool | None = None):
        self.coding = np.asarray(coding, dtype=np.uint8)
        self.m, self.k = self.coding.shape
        # Mosaic only lowers on TPU; elsewhere run the kernel in
        # interpret mode (the CPU test/fallback path)
        self.interpret = (jax.default_backend() != "tpu"
                          if interpret is None else interpret)
        self._mat = jnp.asarray(_bit_layout_matrix(self.coding))
        self._bdmats: dict = {}

    def __call__(self, words) -> jax.Array:
        from .gf_pallas2 import gf_matmul_words
        from ..core.device_profiler import DeviceProfiler
        warr = jnp.asarray(words)
        ln = DeviceProfiler.active().start(
            "gf_encode", bytes_in=warr.nbytes,
            rows=int(warr.shape[0]) if warr.ndim else 0,
            backend="words")
        try:
            out = gf_matmul_words(self._mat, warr, self.m,
                                  interpret=self.interpret,
                                  bdmats=self._bdmats)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.finish(out=out, bytes_out=out.nbytes)
        return out

    @staticmethod
    def to_words(data: np.ndarray) -> np.ndarray:
        """Host bytes [..., n] uint8 (n % 4 == 0) -> [..., n/4] int32."""
        return np.ascontiguousarray(data).view("<i4")

    @staticmethod
    def to_bytes(words: np.ndarray) -> np.ndarray:
        """Host words [..., nw] int32 -> [..., 4*nw] uint8."""
        return np.ascontiguousarray(words).view("<u1")


class GFEncodeDigest:
    """Fused EC encode + CRC-32C digest — one launch per megabatch.

    The batch engine's device program: ``[B, k, L]`` uint8 stripes in,
    ``([B, m, L]`` uint8 parity, ``[B, k+m]`` uint32 shard digests)
    out.  Parity is the GF(2) bitmatrix matmul above; the digest
    reuses ``scrub.crc32c_jax``'s contribution-matrix construction so
    every data *and* parity shard leaves the device already CRC'd —
    the write path's per-shard hinfo costs no second pass.

    One jitted program per ``(B, L)`` — callers bucket both to powers
    of two so the live set stays O(log B · log L).  On TPU the staged
    batch is donated (``donate_argnums``): the input buffer's HBM is
    reusable the moment the launch consumes it, which is what lets
    the engine double-buffer host↔device staging without 2x peak
    memory.  CPU (CI) skips donation — XLA:CPU can't alias them and
    would warn on every launch.

    ``mesh`` shards the megabatch over the batch axis across every
    mesh device (the bitmatrix and the CRC contribution matrix are
    replicated closure constants, so the program is embarrassingly
    data-parallel) — one OSD host drives all chips per launch.  Shapes
    whose batch doesn't divide ``mesh.size`` fall back to the
    single-device program, and the sharded variant skips the export
    cache (serialized programs don't carry shardings); it still
    amortizes through the in-process per-shape table.
    """

    # compiled programs per (code, shape, donate, mesh), process-wide:
    # every OSD of an in-process cluster builds its own engine for the
    # same code, and a per-instance table compiled each shape once per
    # OSD — seconds per compile on the chip, on the write path
    _programs: dict[tuple, tuple] = {}
    _consts: dict[tuple, tuple] = {}       # (L, mesh) → (kt, const_row)

    def __init__(self, coding: np.ndarray, donate: bool | None = None,
                 mesh=None):
        self.coding = np.asarray(coding, dtype=np.uint8)
        self.m, self.k = self.coding.shape
        self._mat = jnp.asarray(_bit_layout_matrix(self.coding))
        self.donate = (jax.default_backend() == "tpu"
                       if donate is None else bool(donate))
        self.mesh = mesh
        self._shape_fns: dict[tuple, object] = {}
        self.export_hits: dict[tuple, bool] = {}
        self.mesh_hits: dict[tuple, bool] = {}

    def _make(self, batch: int, length: int):
        """The program for ``[batch, k, length]``:
        ``gf_encode_digest(data, kt, const_row)``.  The CRC contribution
        matrix ``kt`` (``[8L, 32]`` int8 — 128 MiB at a 512 KiB shard)
        is an argument, not a closure constant: as a constant it was
        baked into every program, so each (B, L) compile and each
        export-cache entry carried it (~10 s and 128 MiB per shape on
        the CPU backend)."""
        k, m = self.k, self.m
        mat = self._mat

        def gf_encode_digest(data, kt, const_row):        # [B, k, L] u8
            parity = gf_matmul_bits(mat, data, m)         # [B, m, L]
            shards = jnp.concatenate([data, parity], axis=1)
            flat = shards.reshape(batch * (k + m), length)
            shifts = jnp.arange(8, dtype=jnp.uint8)
            bits = ((flat[:, :, None] >> shifts) & jnp.uint8(1))
            bits = bits.reshape(batch * (k + m),
                                8 * length).astype(jnp.int8)
            acc = jax.lax.dot_general(
                bits, kt, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            out_bits = ((acc + const_row) & 1).astype(jnp.uint32)
            crcs = jnp.sum(out_bits << jnp.arange(32, dtype=jnp.uint32),
                           axis=-1, dtype=jnp.uint32)
            return parity, crcs.reshape(batch, k + m)

        return gf_encode_digest

    def _digest_consts(self, length: int) -> tuple:
        """(kt, const_row) for one shard length, on the device(s) the
        program runs on — staged once per process, shared by every
        engine (replicated over the mesh when there is one)."""
        key = (length, self.mesh)
        consts = self._consts.get(key)
        if consts is None:
            from ..scrub.crc32c_jax import _contrib
            k_dense, a_dense = _contrib(length)
            ones = np.ones(32, dtype=np.uint8)
            host = (k_dense.T.astype(np.int8),             # [8L, 32]
                    (((a_dense @ ones) % 2) ^ ones).astype(np.int32))
            if self.mesh is None:
                consts = tuple(jnp.asarray(a) for a in host)
            else:
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(self.mesh, PartitionSpec())
                consts = tuple(jax.device_put(a, rep) for a in host)
            self._consts[key] = consts
        return consts

    def _fn_for_shape(self, shape: tuple):
        fn = self._shape_fns.get(shape)
        if fn is not None:
            return fn
        key = (self.coding.tobytes(), shape, self.donate, self.mesh)
        entry = self._programs.get(key)
        if entry is None:
            entry = self._programs[key] = self._build(shape)
        fn, self.export_hits[shape], self.mesh_hits[shape] = entry
        self._shape_fns[shape] = fn
        return fn

    def _build(self, shape: tuple) -> tuple:
        """(program, export-cache hit, mesh-sharded) for one shape."""
        batch, _k, length = shape
        program = self._make(batch, length)
        donate = (0,) if self.donate else ()
        if self.mesh is not None and batch % self.mesh.size == 0:
            from jax.sharding import NamedSharding, PartitionSpec
            spec = PartitionSpec(tuple(self.mesh.axis_names), None, None)
            rep = NamedSharding(self.mesh, PartitionSpec())
            return jax.jit(program, donate_argnums=donate, in_shardings=(
                NamedSharding(self.mesh, spec), rep, rep)), False, True
        fn, hit = jax.jit(program, donate_argnums=donate), False
        from ..native.aot import CompileCache, cached_export, named_call
        if CompileCache.default() is not None:
            import hashlib
            # v3: the exported program is named gf_encode_digest (v2:
            # run); v2: the CRC matrices became arguments (v1 entries
            # baked them in and take one argument)
            key = {"kind": "gf_encode_digest/v3", "jax": jax.__version__,
                   "x64": bool(jax.config.jax_enable_x64),
                   "backend": jax.default_backend(),
                   "m": self.m, "k": self.k,
                   "mat": hashlib.sha256(
                       self.coding.tobytes()).hexdigest(),
                   "shape": list(shape)}
            try:
                exported, hit = cached_export(
                    "ec", key, lambda: jax.jit(program),
                    (jax.ShapeDtypeStruct(shape, jnp.uint8),
                     jax.ShapeDtypeStruct((8 * length, 32), jnp.int8),
                     jax.ShapeDtypeStruct((32,), jnp.int32)))
                fn = jax.jit(named_call("gf_encode_digest", exported),
                             donate_argnums=donate)
            except Exception:
                pass            # non-exportable on this jax: plain jit
        return fn, hit, False

    def __call__(self, data) -> tuple[jax.Array, jax.Array]:
        """[B, k, L] uint8 → (parity [B, m, L], crcs [B, k+m]).

        Returns *device* values un-fenced — the caller (the engine's
        flight queue) decides when to materialise, which is the whole
        double-buffering point.  Not profiler-instrumented: the engine
        brackets each flight itself with rows/bytes occupancy."""
        arr = jnp.asarray(data, dtype=jnp.uint8)
        if arr.ndim != 3 or arr.shape[1] != self.k:
            raise ValueError(
                f"GFEncodeDigest wants [B, {self.k}, L], got {arr.shape}")
        return self._fn_for_shape(arr.shape)(
            arr, *self._digest_consts(arr.shape[2]))
