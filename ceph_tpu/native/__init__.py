"""ctypes binding for the native runtime (``native/``).

The reference keeps its EC hot path in native code (gf-complete /
jerasure, dlopen'd behind ErasureCodePluginRegistry — SURVEY.md §3.6).
This package binds the framework's C++ analog: the GF(2^8) region
engine, the reed_sol_van plugin bridge, and the stripe-coalescing ring
(`native/ec_plugin.h`).  Built with ``make -C native``; everything here
degrades gracefully (`available()` → False) when the library isn't
built, and tests skip accordingly.
"""

from __future__ import annotations

import ctypes
import os
from pathlib import Path

import numpy as np

_LIB_PATH = Path(__file__).resolve().parents[2] / "native" / \
    "libceph_tpu_native.so"
_lib = None


def _load():
    global _lib
    if _lib is None and _LIB_PATH.exists():
        try:
            lib = ctypes.CDLL(str(_LIB_PATH))
        except OSError:
            # a build killed mid-link can leave a truncated .so;
            # treat it as absent (ensure_built may rebuild it)
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.gf256_init.restype = None
        lib.gf256_mul_table.restype = u8p
        lib.gf256_inv_table.restype = u8p
        lib.gf256_mul.restype = ctypes.c_uint8
        lib.gf256_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
        init = getattr(lib, "__erasure_code_init")
        init.restype = ctypes.c_int
        init.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.ec_create.restype = ctypes.c_void_p
        lib.ec_create.argtypes = [ctypes.c_char_p]
        lib.ec_free.argtypes = [ctypes.c_void_p]
        lib.ec_k.argtypes = [ctypes.c_void_p]
        lib.ec_k.restype = ctypes.c_int
        lib.ec_m.argtypes = [ctypes.c_void_p]
        lib.ec_m.restype = ctypes.c_int
        lib.ec_coding_matrix.argtypes = [ctypes.c_void_p]
        lib.ec_coding_matrix.restype = u8p
        lib.ec_encode.restype = ctypes.c_int
        lib.ec_encode.argtypes = [ctypes.c_void_p, u8p, u8p,
                                  ctypes.c_size_t]
        lib.ec_decode.restype = ctypes.c_int
        lib.ec_decode.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_int), u8p, u8p,
                                  ctypes.c_size_t]
        lib.gf256_rs_encode_batch.restype = None
        lib.gf256_rs_encode_batch.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, u8p, u8p,
            ctypes.c_size_t, ctypes.c_size_t]
        lib.gf256_set_tier.restype = ctypes.c_int
        lib.gf256_set_tier.argtypes = [ctypes.c_int]
        lib.ec_ring_create.restype = ctypes.c_void_p
        lib.ec_ring_create.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                       ctypes.c_size_t]
        lib.ec_ring_free.argtypes = [ctypes.c_void_p]
        lib.ec_ring_set_executor.restype = None
        lib.ec_ring_set_executor.argtypes = [ctypes.c_void_p,
                                             ctypes.c_void_p,
                                             ctypes.c_void_p]
        lib.ec_ring_submit.restype = ctypes.c_long
        lib.ec_ring_submit.argtypes = [ctypes.c_void_p, u8p]
        lib.ec_ring_flush.restype = ctypes.c_long
        lib.ec_ring_flush.argtypes = [ctypes.c_void_p]
        lib.ec_ring_get_parity.restype = ctypes.c_int
        lib.ec_ring_get_parity.argtypes = [ctypes.c_void_p, ctypes.c_long,
                                           u8p]
        lib.ec_ring_pending.restype = ctypes.c_size_t
        lib.ec_ring_pending.argtypes = [ctypes.c_void_p]
        lib.ec_ring_fallback_count.restype = ctypes.c_long
        lib.ec_ring_fallback_count.argtypes = [ctypes.c_void_p]
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.crush_set_ln_tables.restype = None
        lib.crush_set_ln_tables.argtypes = [u64p, u64p]
        lib.crush_flat_create.restype = ctypes.c_void_p
        lib.crush_flat_create.argtypes = [
            ctypes.c_int, ctypes.c_int, i32p, i64p, i32p, i32p]
        lib.crush_flat_destroy.argtypes = [ctypes.c_void_p]
        lib.crush_flat_map.restype = None
        lib.crush_flat_map.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, u32p, ctypes.c_int,
            u32p, ctypes.c_int, i32p]
        lib.pjrt_exec_create.restype = ctypes.c_void_p
        lib.pjrt_exec_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            i64p, ctypes.c_size_t, i64p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t]
        lib.pjrt_exec_free.argtypes = [ctypes.c_void_p]
        lib.pjrt_exec_platform.restype = ctypes.c_char_p
        lib.pjrt_exec_platform.argtypes = [ctypes.c_void_p]
        lib.pjrt_exec_run.restype = ctypes.c_int
        lib.pjrt_exec_run.argtypes = [ctypes.c_void_p, u8p, u8p]
        lib.pjrt_exec_last_error.restype = ctypes.c_char_p
        lib.pjrt_exec_last_error.argtypes = [ctypes.c_void_p]
        lib.pjrt_exec_as_ring_executor.restype = ctypes.c_int
        _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def ensure_built(timeout_s: float = 180.0) -> bool:
    """Build the native library for THIS host from the tracked
    sources, then load it.

    The .so is a build artifact (not committed), and its objects are
    compiled with ``-march=native``: the Makefile keys them on the
    host's resolved target flags, so a tree copied from another
    machine rebuilds instead of loading a foreign-ISA binary, and on
    the same host ``make`` is a no-op.  Run before the first load — a
    loaded library cannot be swapped.  Bounded; a failed build leaves
    `available()` False.
    """
    if _lib is None:
        import subprocess
        try:
            subprocess.run(
                ["make", "-C", str(_LIB_PATH.parent)],
                capture_output=True, timeout=timeout_s, check=False)
        except Exception:           # noqa: BLE001 — degrade, don't die
            pass
    return available()


EXECUTOR_CFUNC = ctypes.CFUNCTYPE(
    ctypes.c_int, ctypes.POINTER(ctypes.c_uint8),
    ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t, ctypes.c_size_t,
    ctypes.c_int, ctypes.c_int, ctypes.c_void_p)


def _as_u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def gf256_mul_table() -> np.ndarray:
    lib = _load()
    ptr = lib.gf256_mul_table()
    return np.ctypeslib.as_array(ptr, shape=(256, 256)).copy()


def gf256_set_tier(tier: int) -> int:
    """Force the region-kernel dispatch tier (0=auto, 1=scalar,
    2=avx2, 3=gfni) for tests; → tier in force, -1 if unavailable."""
    return _load().gf256_set_tier(tier)


class NativeEC:
    """The native plugin instance + coalescing ring, Python view."""

    def __init__(self, k: int, m: int, technique: str = "reed_sol_van"):
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native library not built (make -C native)")
        # NB: getattr — inside a class body the literal name would be
        # Python-mangled to _NativeEC__erasure_code_init
        getattr(self._lib, "__erasure_code_init")(b"jax_tpu", b".")
        prof = f"k={k} m={m} technique={technique}".encode()
        self._inst = self._lib.ec_create(prof)
        if not self._inst:
            raise ValueError(f"ec_create rejected profile {prof!r}")
        self.k, self.m = k, m
        self._ring = None
        self._executor_ref = None   # keep the CFUNC alive

    def close(self):
        if self._ring:
            self._lib.ec_ring_free(self._ring)
            self._ring = None
        if self._inst:
            self._lib.ec_free(self._inst)
            self._inst = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def coding_matrix(self) -> np.ndarray:
        ptr = self._lib.ec_coding_matrix(self._inst)
        return np.ctypeslib.as_array(ptr, shape=(self.m, self.k)).copy()

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k, chunk] uint8 → parity [m, chunk]."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        chunk = data.shape[1]
        parity = np.empty((self.m, chunk), dtype=np.uint8)
        rc = self._lib.ec_encode(self._inst, _as_u8p(data),
                                 _as_u8p(parity), chunk)
        if rc:
            raise RuntimeError("ec_encode failed")
        return parity

    def encode_batch(self, data: np.ndarray,
                     matrix: np.ndarray | None = None) -> np.ndarray:
        """data [B, k, chunk] uint8 → out [B, rows, chunk], one
        library call for the whole batch — the fair denominator for
        small stripes, where per-call ctypes overhead would otherwise
        dominate the measurement (the reference benchmark's loop is
        all inside one C process).  With `matrix` (any [rows, k]
        GF(2^8) matrix) the same region kernel applies that map
        instead of the coding matrix — decode is exactly this with
        the inverted survivor submatrix."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        b, k, chunk = data.shape
        if k != self.k:
            raise ValueError(f"data rows {k} != k={self.k}")
        mat = (np.ascontiguousarray(self.coding_matrix())
               if matrix is None
               else np.ascontiguousarray(matrix, dtype=np.uint8))
        if mat.ndim != 2 or mat.shape[1] != self.k:
            raise ValueError(
                f"matrix shape {mat.shape} incompatible with k={self.k}")
        rows = mat.shape[0]
        if not 1 <= rows <= 256:
            # the C encode path stages at most 256 row pointers
            raise ValueError(f"matrix rows {rows} out of range 1..256")
        out = np.empty((b, rows, chunk), dtype=np.uint8)
        self._lib.gf256_rs_encode_batch(
            _as_u8p(mat), self.k, rows, _as_u8p(data),
            _as_u8p(out), chunk, b)
        return out

    def decode(self, chunks: dict[int, np.ndarray]) -> np.ndarray:
        """any k survivors → data [k, chunk]."""
        if len(chunks) < self.k:
            raise ValueError(
                f"{len(chunks)} surviving chunks < k={self.k}")
        survivors = sorted(chunks)[: self.k]
        arrs = np.ascontiguousarray(
            np.stack([np.asarray(chunks[i], dtype=np.uint8)
                      for i in survivors]))
        chunk = arrs.shape[1]
        out = np.empty((self.k, chunk), dtype=np.uint8)
        surv = (ctypes.c_int * self.k)(*survivors)
        rc = self._lib.ec_decode(self._inst, surv, _as_u8p(arrs),
                                 _as_u8p(out), chunk)
        if rc:
            raise RuntimeError("ec_decode failed")
        return out

    # -- coalescing ring ---------------------------------------------------
    def ring_open(self, capacity: int, chunk_size: int):
        if self._ring:
            self._lib.ec_ring_free(self._ring)
        self._ring = self._lib.ec_ring_create(self._inst, capacity,
                                              chunk_size)
        self._chunk = chunk_size
        if not self._ring:
            raise RuntimeError("ec_ring_create failed")

    def ring_set_python_executor(self, fn):
        """fn(data [B,k,chunk] np.uint8) -> parity [B,m,chunk]; wraps it
        as the C executor — this is how the JAX/TPU engine plugs into the
        native bridge (PJRT-in-C++ carries the same signature)."""
        k, m, chunk = self.k, self.m, self._chunk

        def trampoline(data_p, parity_p, chunk_sz, batch, kk, mm, ctx):
            try:
                data = np.ctypeslib.as_array(
                    data_p, shape=(batch, kk, chunk_sz))
                parity = fn(data.copy())
                dst = np.ctypeslib.as_array(
                    parity_p, shape=(batch, mm, chunk_sz))
                dst[...] = parity
                return 0
            except Exception:
                return -1

        self._executor_ref = EXECUTOR_CFUNC(trampoline)
        self._lib.ec_ring_set_executor(
            self._ring, ctypes.cast(self._executor_ref, ctypes.c_void_p),
            None)

    def ring_set_pjrt_executor(self, executor: "PjrtExecutor"):
        """Route ring flushes through the C++ PJRT executor — the full
        no-Python dispatch path (the CFUNC trampoline above is the
        test-only variant).  The executor's program geometry must match
        (ring capacity, k, chunk)."""
        self._executor_ref = executor   # keep alive
        self._lib.ec_ring_set_executor(
            self._ring,
            ctypes.cast(self._lib.pjrt_exec_as_ring_executor,
                        ctypes.c_void_p),
            executor._h)

    def ring_submit(self, data: np.ndarray) -> int:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        slot = self._lib.ec_ring_submit(self._ring, _as_u8p(data))
        if slot < 0:
            raise BufferError("ring full — flush first")
        return slot

    def ring_flush(self) -> int:
        n = self._lib.ec_ring_flush(self._ring)
        if n < 0:
            raise RuntimeError("ring executor failed")
        return n

    def ring_parity(self, slot: int) -> np.ndarray:
        out = np.empty((self.m, self._chunk), dtype=np.uint8)
        rc = self._lib.ec_ring_get_parity(self._ring, slot, _as_u8p(out))
        if rc:
            raise KeyError(f"slot {slot} not available")
        return out

    def ring_pending(self) -> int:
        return self._lib.ec_ring_pending(self._ring)

    def ring_fallbacks(self) -> int:
        """Flushes that fell back from the registered executor to the
        CPU engine — the dead-device health signal."""
        return self._lib.ec_ring_fallback_count(self._ring)


class NativeCrush:
    """Scalar crush_do_rule analog over BatchMapper's flat tables —
    the honest single-core denominator for the CRUSH PGs/sec bench
    (reference ``src/crush/mapper.c`` via ``osdmaptool``)."""

    _tables_set = False

    def __init__(self, mapper):
        """`mapper` is a ceph_tpu.crush.jax_mapper.BatchMapper — the
        flat arrays and parsed rule params are reused verbatim."""
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native library not built")
        if getattr(mapper, "_subs", None):
            # multi-block mappers have no single flat rule to mirror
            raise RuntimeError(
                "NativeCrush mirrors single-block rules only")
        algs = set(getattr(mapper, "_algs", ["straw2"]))
        if algs - {"straw2"}:
            # the native scalar implements straw2 draws only; now
            # that BatchMapper also batches legacy straw/list/tree
            # buckets, refusing here beats silently mis-mapping them
            raise RuntimeError(
                f"NativeCrush is straw2-only; map uses {sorted(algs)}")
        if not NativeCrush._tables_set:
            from ..crush.ln import LL_TBL, RH_LH_TBL
            rh = np.ascontiguousarray(RH_LH_TBL, dtype=np.uint64)
            ll = np.ascontiguousarray(LL_TBL, dtype=np.uint64)
            self._lib.crush_set_ln_tables(
                rh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                ll.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
            NativeCrush._tables_set = True
        items = np.ascontiguousarray(mapper._items, dtype=np.int32)
        # position-0 weights (the scalar denominator doesn't model
        # choose_args positional weight-sets; bench maps have none)
        weights = np.ascontiguousarray(mapper._weights[0],
                                       dtype=np.int64)
        sizes = np.ascontiguousarray(mapper._sizes, dtype=np.int32)
        btype = np.ascontiguousarray(mapper._btype, dtype=np.int32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        self._h = self._lib.crush_flat_create(
            mapper._nb, mapper._S,
            items.ctypes.data_as(i32p),
            weights.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            sizes.ctypes.data_as(i32p), btype.ctypes.data_as(i32p))
        self._m = mapper

    def close(self):
        if getattr(self, "_h", None):
            self._lib.crush_flat_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def map(self, xs: np.ndarray, reweight: np.ndarray | None = None
            ) -> np.ndarray:
        m = self._m
        xs = np.ascontiguousarray(xs, dtype=np.uint32)
        if reweight is None:
            reweight = np.full(max(m.cmap.max_devices, 1), 0x10000,
                               dtype=np.uint32)
        reweight = np.ascontiguousarray(reweight, dtype=np.uint32)
        out = np.empty((len(xs), m.numrep), dtype=np.int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        self._lib.crush_flat_map(
            self._h, m.take, m.target_type, m.numrep,
            int(m.firstn), int(m.recurse and m.target_type != 0),
            m.tries, m.recurse_tries,
            m.cmap.tunables.chooseleaf_vary_r, m.d1, m.d2,
            xs.ctypes.data_as(u32p), len(xs),
            reweight.ctypes.data_as(u32p), len(reweight),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if out.shape[1] < m.result_max:
            pad = np.full((len(xs), m.result_max - out.shape[1]),
                          np.int32(-0x7FFFFFFF), dtype=np.int32)
            out = np.concatenate([out, pad], axis=1)
        return out


class PjrtExecutor:
    """C++-side PJRT program executor (``native/pjrt_executor.cc``).

    Loads a PJRT plugin (TPU: the installed ``libtpu.so``; tests:
    ``native/libpjrt_fake.so``) and an
    AOT-exported program directory produced by
    :func:`ceph_tpu.native.aot.export_encode_program`.  `run` moves
    bytes host→device→host through the C API with no Python on the
    dispatch path beyond this ctypes call; plugged into a NativeEC
    ring via ``ring_set_pjrt_executor`` even that call disappears.
    """

    def __init__(self, plugin_so: str, program_dir: str,
                 client_options: dict | None = None):
        import json as _json
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native library not built (make -C native)")
        meta = _json.loads(
            (Path(program_dir) / "meta.json").read_text())
        self.meta = meta
        self.in_dims = tuple(meta["in_dims"])
        self.out_dims = tuple(meta["out_dims"])
        in_d = (ctypes.c_int64 * len(self.in_dims))(*self.in_dims)
        out_d = (ctypes.c_int64 * len(self.out_dims))(*self.out_dims)
        err = ctypes.create_string_buffer(1024)
        opts = Path(program_dir) / "options.pb"
        copts = None
        if client_options:
            copts = ";".join(
                f"{k}=i{int(v)}" if isinstance(v, (int, bool))
                else f"{k}=s{v}"
                for k, v in client_options.items()).encode()
        self._h = self._lib.pjrt_exec_create(
            str(plugin_so).encode(),
            str(Path(program_dir) / "program.mlir").encode(),
            str(opts).encode() if opts.exists() else None,
            in_d, len(self.in_dims), out_d, len(self.out_dims),
            copts, err, len(err))
        if not self._h:
            raise RuntimeError(
                f"pjrt_exec_create: {err.value.decode(errors='replace')}")

    @property
    def platform(self) -> str:
        return self._lib.pjrt_exec_platform(self._h).decode()

    def run(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if data.shape != self.in_dims:
            raise ValueError(f"input shape {data.shape} != program "
                             f"shape {self.in_dims}")
        out = np.empty(self.out_dims, dtype=np.uint8)
        rc = self._lib.pjrt_exec_run(self._h, _as_u8p(data),
                                     _as_u8p(out))
        if rc != 0:
            raise RuntimeError(
                "pjrt_exec_run: " +
                self._lib.pjrt_exec_last_error(self._h).decode())
        return out

    def close(self):
        if getattr(self, "_h", None):
            self._lib.pjrt_exec_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
