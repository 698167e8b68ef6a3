"""AOT program export + the persistent compile cache.

Two consumers share this layer:

1. **The native PJRT executor** (no-Python-in-process contract,
   SURVEY.md §8 stage 8): Python runs **offline** — here — to export
   the batched EC encode/decode programs as serialized StableHLO plus
   serialized compile options; the C++ runtime
   (``native/pjrt_executor.cc``) then loads and executes it against any
   PJRT plugin with no interpreter in the daemon process.  This mirrors
   how the reference ships pre-built ``libec_*.so`` kernels that the
   OSD merely dlopens (``src/erasure-code/ErasureCodePlugin.cc``).

2. **Warm starts** (`CompileCache`): any ``jax.export``-able program —
   the CRUSH batch mapper, the EC codecs — serialized to disk keyed on
   its *shape* signature (topology shapes, rule, tunables, batch dims,
   jax version), so a fresh process deserializes the lowered module
   instead of re-tracing it.  A key hit means tracing is skipped
   entirely; pair with ``utils.enable_compile_cache`` (XLA's own
   persistent cache) to also skip the backend compile on TPU.

Cache layout (root = ``utils.platform.cache_root()``: the caller's
``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``)::

    <root>/export/<namespace>/<sha256[:24] of canonical key JSON>.jaxpb
    <root>/export/<namespace>/<...same hash...>.json   # the key, readable
    <root>/xla/...                                     # XLA's own cache

Artifacts written by the program exporters to ``out_dir``:
- ``program.mlir``  — StableHLO (portable bytecode, or text for the
  gf256-backed fake plugin, which parses @main's signature);
- ``options.pb``    — serialized xla.CompileOptionsProto;
- ``meta.json``     — {k, m, batch, chunk, in_dims, out_dims, format}.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np

from ..utils.platform import cache_root


class CompileCache:
    """Disk cache of serialized ``jax.export`` programs.

    Corruption-proof by construction: a load that fails for ANY reason
    (truncated write, jax-version drift the key missed, bit rot)
    deletes the entry and reports a miss — the cache can only ever
    cause a fresh compile, never an error.  Writes are atomic
    (tmp + rename) so concurrent processes at worst both compile.

    Bounded: every store prunes age-expired entries and, LRU-style
    (loads touch mtime), trims past ``max_entries`` — so the cache
    dir stops growing unboundedly.  Knobs (0 disables a limit):
    ``CEPH_TPU_EXPORT_CACHE_MAX_ENTRIES`` (default 512) and
    ``CEPH_TPU_EXPORT_CACHE_MAX_AGE_DAYS`` (default 30).
    """

    def __init__(self, root: str | Path,
                 max_entries: int | None = None,
                 max_age_s: float | None = None):
        self.root = Path(root)
        self.max_entries = (self._env_num(
            "CEPH_TPU_EXPORT_CACHE_MAX_ENTRIES", 512)
            if max_entries is None else max_entries)
        self.max_age_s = (self._env_num(
            "CEPH_TPU_EXPORT_CACHE_MAX_AGE_DAYS", 30.0) * 86400.0
            if max_age_s is None else max_age_s)

    @staticmethod
    def _env_num(name: str, default: float) -> float:
        try:
            return float(os.environ.get(name, default))
        except (TypeError, ValueError):
            return default

    @classmethod
    def default(cls) -> "CompileCache | None":
        """The process-wide cache under ``cache_root()/export``, or
        None when disabled via ``CEPH_TPU_EXPORT_CACHE=0``."""
        if os.environ.get("CEPH_TPU_EXPORT_CACHE", "1").lower() in (
                "0", "false", "off"):
            return None
        return cls(Path(cache_root()) / "export")

    @staticmethod
    def key_hash(key: dict) -> str:
        blob = json.dumps(key, sort_keys=True, default=str).encode()
        return hashlib.sha256(blob).hexdigest()[:24]

    def path(self, namespace: str, key: dict) -> Path:
        return self.root / namespace / (self.key_hash(key) + ".jaxpb")

    def load_exported(self, namespace: str, key: dict):
        """→ the deserialized ``jax.export.Exported``, or None."""
        p = self.path(namespace, key)
        try:
            blob = p.read_bytes()
        except OSError:
            return None
        try:
            os.utime(p)         # recency for LRU trimming
        except OSError:
            pass
        try:
            from jax import export as jexport
            return jexport.deserialize(bytearray(blob))
        except Exception:
            try:
                p.unlink()
            except OSError:
                pass
            return None

    def store_exported(self, namespace: str, key: dict,
                       exported) -> Path:
        p = self.path(namespace, key)
        p.parent.mkdir(parents=True, exist_ok=True)
        tmp = p.with_name(p.name + f".tmp{os.getpid()}")
        tmp.write_bytes(bytes(exported.serialize()))
        os.replace(tmp, p)
        p.with_suffix(".json").write_text(
            json.dumps(key, sort_keys=True, default=str, indent=1))
        try:
            self.prune()
        except Exception:
            pass                # pruning is best-effort housekeeping
        return p

    def prune(self, now: float | None = None) -> int:
        """Expire entries older than `max_age_s`, then trim the
        oldest-by-mtime past `max_entries` (across all namespaces).
        → number of entries removed."""
        import time
        now = time.time() if now is None else now
        try:
            entries = sorted(self.root.rglob("*.jaxpb"),
                             key=lambda p: p.stat().st_mtime)
        except OSError:
            return 0
        doomed = []
        if self.max_age_s and self.max_age_s > 0:
            cutoff = now - self.max_age_s
            doomed += [p for p in entries if p.stat().st_mtime < cutoff]
        keep = [p for p in entries if p not in doomed]
        if self.max_entries and self.max_entries > 0:
            excess = len(keep) - int(self.max_entries)
            if excess > 0:
                doomed += keep[:excess]
        for p in doomed:
            for victim in (p, p.with_suffix(".json")):
                try:
                    victim.unlink()
                except OSError:
                    pass
        return len(doomed)


def named_call(name: str, exported):
    """``exported.call`` under ``name``: jit names its program (the HLO
    module ``jit_<name>``, which a profiler trace shows) after the
    function it wraps, and a bare ``exported.call`` is ``jit_call``."""
    def call(*args):
        return exported.call(*args)
    call.__name__ = call.__qualname__ = name
    return call


def cached_export(namespace: str, key: dict, make_fn, specs):
    """Export-through-cache: deserialize `namespace`/`key` if present,
    else trace+export ``make_fn()`` (a zero-arg callable returning the
    jitted function) at `specs` and persist it.  → (Exported, hit)."""
    from jax import export as jexport
    cache = CompileCache.default()
    if cache is not None:
        exp = cache.load_exported(namespace, key)
        if exp is not None:
            return exp, True
    exp = jexport.export(make_fn())(*specs)
    if cache is not None:
        try:
            cache.store_exported(namespace, key, exp)
        except Exception:
            pass  # read-only cache dir etc. — caching is best-effort
    return exp, False


def _write_program(out_dir: str, make_fn, spec, fmt: str,
                   namespace: str, key: dict, meta: dict) -> dict:
    import jax

    if fmt == "text":
        lowered = jax.jit(make_fn()).lower(spec)
        code = str(lowered.compiler_ir("stablehlo")).encode()
    elif fmt == "bytecode":
        exported, _ = cached_export(namespace, key,
                                    lambda: jax.jit(make_fn()), (spec,))
        code = exported.mlir_module_serialized
    else:
        raise ValueError(f"unknown export format {fmt!r}")

    from jax._src.lib import xla_client as xc
    options = xc.CompileOptions().SerializeAsString()

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "program.mlir").write_bytes(code)
    (out / "options.pb").write_bytes(options)
    (out / "meta.json").write_text(json.dumps(meta, indent=1))
    return meta


def _ec_key(kind: str, **kw) -> dict:
    import jax
    return {"kind": kind, "jax": jax.__version__,
            "x64": bool(jax.config.jax_enable_x64), **kw}


def export_encode_program(out_dir: str, *, k: int = 8, m: int = 3,
                          batch: int = 64, chunk: int = 4096,
                          fmt: str = "bytecode") -> dict:
    """Export encode: [batch, k, chunk] u8 → [batch, m, chunk] u8."""
    import jax
    import jax.numpy as jnp

    from ..ops import rs
    from ..ops.gf_jax import _bit_layout_matrix, gf_matmul_bits

    coding = rs.reed_sol_van_matrix(k, m)

    def make():
        bitmat = jnp.asarray(_bit_layout_matrix(coding))

        def encode(data):
            return gf_matmul_bits(bitmat, data, m)

        return encode

    spec = jax.ShapeDtypeStruct((batch, k, chunk), jnp.uint8)
    meta = {"k": k, "m": m, "batch": batch, "chunk": chunk,
            "in_dims": [batch, k, chunk], "out_dims": [batch, m, chunk],
            "format": fmt}
    return _write_program(out_dir, make, spec, fmt, "ec",
                          _ec_key("encode", k=k, m=m, batch=batch,
                                  chunk=chunk), meta)


def export_decode_program(out_dir: str, *, k: int = 8, m: int = 3,
                          erasures: tuple[int, ...] = (0,),
                          batch: int = 64, chunk: int = 4096,
                          fmt: str = "bytecode") -> dict:
    """Export decode for a fixed erasure pattern: the first k
    surviving chunks [batch, k, chunk] u8 → the erased+leading data
    rows [batch, r, chunk] u8 (r = decode-matrix rows, row order as
    ``ops.rs.decode_matrix``)."""
    import jax
    import jax.numpy as jnp

    from ..ops import rs
    from ..ops.gf_jax import _bit_layout_matrix, gf_matmul_bits

    erasures = tuple(sorted(erasures))
    coding = rs.reed_sol_van_matrix(k, m)
    dm = rs.decode_matrix(coding, k, list(erasures))
    r = dm.shape[0]

    def make():
        bitmat = jnp.asarray(_bit_layout_matrix(dm))

        def decode(surv):
            return gf_matmul_bits(bitmat, surv, r)

        return decode

    spec = jax.ShapeDtypeStruct((batch, k, chunk), jnp.uint8)
    meta = {"k": k, "m": m, "batch": batch, "chunk": chunk,
            "erasures": list(erasures),
            "in_dims": [batch, k, chunk], "out_dims": [batch, r, chunk],
            "format": fmt}
    return _write_program(out_dir, make, spec, fmt, "ec",
                          _ec_key("decode", k=k, m=m, batch=batch,
                                  chunk=chunk, erasures=list(erasures)),
                          meta)


def oracle_encode(k: int, m: int, data: np.ndarray) -> np.ndarray:
    """NumPy reference bytes for a [batch, k, chunk] input."""
    from ..ops import rs
    coding = rs.reed_sol_van_matrix(k, m)
    return np.stack([rs.encode_oracle(coding, d) for d in data])
