"""Device-plane routing lint — every lane profiles and warm-starts.

Walks the product tree for modules that build device executables
(``jax.jit`` / ``shard_map`` / ``pallas_call``) and asserts the two
conventions the multichip device plane relies on:

- every device entry point routes launches through
  ``core.device_profiler`` (or is on the explicit indirect list,
  meaning a profiled wrapper one layer up owns its launches);
- every module that serializes programs with ``jax.export`` does so
  through the persistent ``CompileCache`` / ``cached_export`` layer —
  a naked export never warm-starts across processes.

The indirect list is checked for staleness: an entry whose module no
longer builds executables (or grew its own profiling) fails the test,
so the list can't rot into a blanket waiver.
"""

import pathlib
import re

import ceph_tpu

ROOT = pathlib.Path(ceph_tpu.__file__).parent

_ENTRY = re.compile(r"jax\.jit\(|shard_map\(|pallas_call")
_PROFILED = re.compile(r"device_profiler|DeviceProfiler")
_CACHED = re.compile(r"CompileCache|cached_export")
_EXPORTS = re.compile(r"from jax import export|jexport\.export\(")

# Device entry points whose profiling lives one layer up, with the
# layer that owns it.  Additions need the same justification.
INDIRECT = {
    "compress/chunker.py":   # osd.batch_engine profiles the comp lane
        "hash_batch launches ride the engine's lane profiler",
    "mon/pgmap.py":          # control plane, not a data lane
        "vectorized health/summary passes, no per-object launches",
    "native/aot.py":         # IS the cache layer
        "CompileCache itself wraps jit for export",
    "ops/gf_pallas2.py":     # launched via scrub/recovery engines
        "kernel factory; scrub.engine owns the launch",
}


def _sources():
    out = {}
    for p in sorted(ROOT.rglob("*.py")):
        out[p.relative_to(ROOT).as_posix()] = p.read_text()
    return out


def test_device_entry_points_route_through_profiler():
    srcs = _sources()
    entries = {rel for rel, src in srcs.items() if _ENTRY.search(src)}
    assert len(entries) >= 6, f"lint lost its targets: {sorted(entries)}"
    naked = sorted(rel for rel in entries
                   if rel not in INDIRECT
                   and not _PROFILED.search(srcs[rel]))
    assert not naked, \
        f"device entry points without profiler routing: {naked}"
    # the core lanes must profile DIRECTLY (not via the waiver list)
    for rel in ("crush/jax_mapper.py", "ops/gf_jax.py",
                "parallel/reconstruct.py", "scrub/crc32c_jax.py"):
        assert rel in entries and _PROFILED.search(srcs[rel]), rel


def test_indirect_list_is_not_stale():
    srcs = _sources()
    for rel in INDIRECT:
        assert rel in srcs, f"waived module vanished: {rel}"
        assert _ENTRY.search(srcs[rel]), \
            f"{rel} no longer builds executables — drop it from INDIRECT"
        assert not _PROFILED.search(srcs[rel]), \
            f"{rel} grew its own profiling — drop it from INDIRECT"


def test_exports_go_through_compile_cache():
    srcs = _sources()
    exporters = {rel for rel, src in srcs.items() if _EXPORTS.search(src)}
    assert "native/aot.py" in exporters     # the cache layer itself
    naked = sorted(rel for rel in exporters
                   if rel != "native/aot.py"
                   and not _CACHED.search(srcs[rel]))
    assert not naked, f"jax.export outside the compile cache: {naked}"
    # the persistent lanes really do reference the cache layer
    for rel in ("crush/jax_mapper.py", "ops/gf_jax.py"):
        assert _CACHED.search(srcs[rel]), rel
