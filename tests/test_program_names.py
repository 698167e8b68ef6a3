"""Each device program is named after what it computes.

``jax.jit`` names a program's HLO module ``jit_<function name>``, and a
profiler trace's "XLA Modules" line shows that name, so a per-program
busy time can be read from a trace.  Every case lowers one program at a
tiny shape (the plain ``jit`` path and, where the program goes through
the export cache, the ``jax.export`` path too) and checks its module
name.  With the names put back as they were before the programs were
named (``jit_run``, ``jit__apply``, ``jit_fn``, ``jit_call`` ...), the
lowered text hashes to what those unnamed programs lowered to: naming
changed nothing else.
"""

import hashlib

import pytest

from ceph_tpu.ops import rs

K, M = 2, 1
CODING = rs.reed_sol_van_matrix(K, M)


def _specs(*shapes):
    import jax
    return tuple(jax.ShapeDtypeStruct(s, d) for s, d in shapes)


def _gf_encode_digest():
    import jax.numpy as jnp

    from ceph_tpu.ops.gf_jax import GFEncodeDigest
    fn = GFEncodeDigest(CODING, donate=False)._build((1, K, 128))[0]
    return fn, _specs(((1, K, 128), jnp.uint8), ((1024, 32), jnp.int8),
                      ((32,), jnp.int32))


def _gf_linear():
    import jax.numpy as jnp

    from ceph_tpu.ops.gf_jax import GFLinear
    fn = GFLinear(CODING, backend="xla")._warm_start((1, K, 128))[0]
    return fn, _specs(((1, K, 128), jnp.uint8))


def _crc32c_batch():
    import jax.numpy as jnp

    from ceph_tpu.scrub.crc32c_jax import _batch_program
    return _batch_program(64), _specs(
        ((2, 64), jnp.uint8), ((2,), jnp.uint32), ((512, 32), jnp.int8),
        ((32, 32), jnp.int8), ((32,), jnp.int32))


def _sharded():
    from ceph_tpu.parallel.mesh import make_mesh
    from ceph_tpu.parallel.reconstruct import ShardedEC
    return ShardedEC(CODING, K, M, make_mesh(2, shard=2),
                     word_native=False)


def _sharded_encode():
    import jax.numpy as jnp
    ec = _sharded()
    return ec._encode, _specs(((2, ec.k_pad, 128), jnp.uint8))


def _sharded_reconstruct():
    import jax.numpy as jnp
    ec = _sharded()
    return ec._decode_fn((0,)), _specs(((2, ec.n_pad, 128), jnp.uint8))


def _crush_map():
    from ceph_tpu.crush import BatchMapper, build_hierarchy
    bm = BatchMapper(build_hierarchy(1, 2, 2), 0, result_max=2, chunk=8)
    return bm._compile()[0], bm._arg_specs()


# (program, build function, export cache on, {new name: old name},
# digest of the old program's lowered text)
CASES = [
    ("gf_encode_digest", _gf_encode_digest, False,
     {"@jit_gf_encode_digest": "@jit_run"},
     "fdbb72e6b730cb9a4d27bffd7d46a39c"
     "2051da52ab57cfb177b5d222416b9a51"),
    ("gf_encode_digest", _gf_encode_digest, True,
     {"@jit_gf_encode_digest": "@jit_call", "gf_encode_digest": "run"},
     "bdad8c8e420ca1c078b28e7b6f155df2"
     "1f09fc7ceb4073ae1d9f5f4bc0506981"),
    ("gf_linear", _gf_linear, False,
     {"@jit_gf_linear": "@jit__apply"},
     "bad5f91164a09fdea494d805126736d0"
     "e62894e85023162a8ff23ec8819a8732"),
    ("gf_linear", _gf_linear, True,
     {"@jit_gf_linear": "@jit_call", "gf_linear": "_apply"},
     "6a6049292025878e393ebb50a8a1c7b0"
     "3f6e11a8989b9010400cf5c20c53c6aa"),
    ("crc32c_batch", _crc32c_batch, False,
     {"@jit_crc32c_batch": "@jit_run"},
     "d9db5ee842e6a7facef03f0808394b83"
     "b42606e0716435f5a6188de877f4374a"),
    ("sharded_encode", _sharded_encode, False,
     {"@jit_sharded_encode": "@jit_fn"},
     "e501ca395c5b3fad00d56839edb29ae4"
     "66efb00043ed43ac8c4e71e0b6bd28ba"),
    ("sharded_reconstruct", _sharded_reconstruct, False,
     {"@jit_sharded_reconstruct": "@jit_fn"},
     "8a50fdbcc2d5724170c52bb344c8e271"
     "0c6b58a8f754698379a5627b711e1266"),
    ("crush_map", _crush_map, False,
     {"@jit_crush_map": "@jit_run"},
     "dc6fa2a69730cbf4dda947d555b6d161"
     "c2542b262631f058f3833f3e864e774b"),
    ("crush_map", _crush_map, True,
     {"@jit_crush_map": "@jit_call", "crush_map": "run"},
     "1c2333dc564cb788178946afc91585f4"
     "c477c53e3dd61f6b33ce0b367d760219"),
]


def lowered_text(build, export: bool, monkeypatch, tmp_path) -> str:
    monkeypatch.setenv("CEPH_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("CEPH_TPU_EXPORT_CACHE", "1" if export else "0")
    fn, specs = build()
    return fn.lower(*specs).as_text()


@pytest.mark.parametrize(
    "name,build,export,renames,digest", CASES,
    ids=[f"{c[0]}-{'export' if c[2] else 'jit'}" for c in CASES])
def test_program_is_named_and_otherwise_unchanged(
        name, build, export, renames, digest, monkeypatch, tmp_path):
    text = lowered_text(build, export, monkeypatch, tmp_path)
    assert text.startswith(f"module @jit_{name} ")
    for new, old in renames.items():
        text = text.replace(new, old)
    assert hashlib.sha256(text.encode()).hexdigest() == digest
