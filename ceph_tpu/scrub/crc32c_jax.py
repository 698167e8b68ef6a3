"""CRC32C (Castagnoli) as GF(2) linear algebra — host scalar + JAX batch.

The reference's ``ceph_crc32c`` (``src/common/crc32c*``) is CRC-32C:
polynomial ``0x1EDC6F41``, reflected (LSB-first) register, init and
final xor ``0xFFFFFFFF`` — the iSCSI/RFC 3720 CRC, *not* zlib's
ISO-HDLC CRC-32.  Three entry points, all byte-exact against the RFC
3720 golden vectors:

- :func:`crc32c` — host scalar, slice-by-8 table-driven; the drop-in
  for ``zlib.crc32``-shaped call sites (``crc32c(data, seed)``).
- :func:`crc32c_combine` — ``crc(A||B)`` from ``crc(A)``, ``crc(B)``
  and ``len(B)`` via GF(2) matrix exponentiation (the zlib
  ``crc32_combine`` construction, Castagnoli matrices): chunked CRCs
  merge exactly like the reference's CRC over a buffer chain.
- :func:`crc32c_batch` — the device kernel: one fused matmul digests
  a whole ``[n_objects, chunk]`` uint8 batch.

Why a matmul: the CRC register update is linear over GF(2).  With
``r`` the raw (conditioned) register and ``b`` a data byte,

    r' = A·r ⊕ B·bits(b)

where ``A`` is the 32x32 shift-a-zero-byte matrix and ``B`` maps the 8
data bits through the CRC table (the table is additive:
``T[x^y] = T[x]^T[y]``).  Unrolled over a chunk of L bytes,

    crc_out = A^L·crc_in ⊕ (A^L·F ⊕ F) ⊕ K·bits(data),   F = 0xFFFFFFFF

with ``K = [A^(L-1)·B | A^(L-2)·B | ... | B]`` the ``[32, 8L]``
contribution matrix.  ``K`` is built host-side by doubling (log L
GF(2) matmuls) and cached per length; the device then digests n
objects as one ``[n, 8L] x [8L, 32]`` int8 matmul with int32
accumulation, mod-2 parity and a 32-bit repack — the same MXU
bit-matrix idiom as ``ops.gf_jax``.
"""

from __future__ import annotations

import functools

import numpy as np

CRC32C_POLY = 0x1EDC6F41        # Castagnoli, normal form
_POLY = 0x82F63B78              # reflected (LSB-first register)
_MASK = 0xFFFFFFFF


# ---------------------------------------------------------------- tables

def _make_table() -> list[int]:
    tab = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        tab.append(c)
    return tab


_TABLE = _make_table()

# slice-by-8: T8[0] consumes the most-significant of 8 bytes in flight
_T8: list[list[int]] = [_TABLE]
for _k in range(1, 8):
    _prev = _T8[-1]
    _T8.append([(_prev[i] >> 8) ^ _TABLE[_prev[i] & 0xFF]
                for i in range(256)])
_T8.reverse()   # _T8[j] shifts its byte past 7-j later bytes


try:
    from google_crc32c import extend as _crc32c_extend
    from google_crc32c import implementation as _crc32c_impl
    if _crc32c_impl != "c":
        _crc32c_extend = None       # its pure-Python build is slower
except ImportError:                 # pragma: no cover
    _crc32c_extend = None


def _as_bytes(data) -> bytes:
    if isinstance(data, (bytes, bytearray)):
        return bytes(data)
    if isinstance(data, memoryview):
        return data.tobytes()
    arr = np.asarray(data)
    if arr.dtype != np.uint8:
        raise TypeError(f"crc32c wants bytes/uint8, got {arr.dtype}")
    return arr.tobytes()


def crc32c(data, crc: int = 0) -> int:
    """CRC-32C of `data`, chaining from `crc` (``crc32c(b"") == 0``;
    ``crc32c(b, crc32c(a)) == crc32c(a + b)``).

    Through the installed ``google_crc32c`` C extension where there is
    one: the OSD read path checks every shard's hinfo with it, and the
    pure-Python slice-by-8 (`crc32c_slice8`, kept as the oracle) holds
    the GIL for ~0.1 s per 512 KiB chunk — enough to starve heartbeats
    of an in-process cluster under a 4 MiB object stream."""
    if _crc32c_extend is not None:
        return _crc32c_extend(int(crc) & _MASK, _as_bytes(data))
    return crc32c_slice8(data, crc)


def crc32c_slice8(data, crc: int = 0) -> int:
    """The table-driven host CRC-32C (slice-by-8), pure Python."""
    b = _as_bytes(data)
    c = (int(crc) ^ _MASK) & _MASK
    n8 = len(b) & ~7
    t0, t1, t2, t3, t4, t5, t6, t7 = _T8
    for off in range(0, n8, 8):
        lo = c ^ int.from_bytes(b[off:off + 4], "little")
        hi = int.from_bytes(b[off + 4:off + 8], "little")
        c = (t0[lo & 0xFF] ^ t1[(lo >> 8) & 0xFF]
             ^ t2[(lo >> 16) & 0xFF] ^ t3[lo >> 24]
             ^ t4[hi & 0xFF] ^ t5[(hi >> 8) & 0xFF]
             ^ t6[(hi >> 16) & 0xFF] ^ t7[hi >> 24])
    for byte in b[n8:]:
        c = (c >> 8) ^ _TABLE[(c ^ byte) & 0xFF]
    return (c ^ _MASK) & _MASK


# ------------------------------------------------- GF(2) matrix algebra
#
# A 32x32 GF(2) matrix is a list of 32 uint32 columns: col[i] = M·e_i.

def _matrix_times(mat: list[int], vec: int) -> int:
    out = 0
    i = 0
    while vec:
        if vec & 1:
            out ^= mat[i]
        vec >>= 1
        i += 1
    return out


def _matrix_square(mat: list[int]) -> list[int]:
    return [_matrix_times(mat, col) for col in mat]


def _shift_byte_matrix() -> list[int]:
    """A: the raw-register operator for one zero *byte*:
    ``A(r) = (r >> 8) ^ T[r & 0xFF]``."""
    return [((1 << i) >> 8) ^ _TABLE[(1 << i) & 0xFF] for i in range(32)]


_A_COLS = _shift_byte_matrix()


def crc32c_shift(crc: int, nbytes: int) -> int:
    """Apply ``A^nbytes`` (append `nbytes` zero bytes to the *raw*
    register) to a 32-bit value, by square-and-multiply."""
    c = int(crc) & _MASK
    n = int(nbytes)
    if n < 0:
        raise ValueError("negative length")
    mat = _A_COLS
    while n:
        if n & 1:
            c = _matrix_times(mat, c)
        n >>= 1
        if n:
            mat = _matrix_square(mat)
    return c


def crc32c_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """``crc32c(A || B)`` from ``crc32c(A)``, ``crc32c(B)``, ``len(B)``.

    Follows from linearity: conditioning cancels, leaving
    ``crc(A||B) = A^len_b · crc(A) ⊕ crc(B)``.
    """
    if len_b == 0:
        return int(crc_a) & _MASK
    return crc32c_shift(crc_a, len_b) ^ (int(crc_b) & _MASK)


def _matrix_inverse(cols: list[int]) -> list[int]:
    """Invert a 32x32 GF(2) matrix (column-of-uint32 form) by
    Gauss-Jordan elimination.  A is invertible because the CRC
    polynomial has a nonzero constant term (x^0), so the byte-shift
    operator is a bijection on register states."""
    n = len(cols)
    dense = _dense(cols, n).astype(np.uint8)
    aug = np.concatenate([dense, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = col + int(np.argmax(aug[col:, col]))
        if aug[piv, col] == 0:
            raise ValueError("singular GF(2) matrix")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        for row in np.nonzero(aug[:, col])[0]:
            if row != col:
                aug[row] ^= aug[col]
    inv = aug[:, n:]
    return [int(sum(int(inv[r, i]) << r for r in range(n)))
            for i in range(n)]


@functools.lru_cache(maxsize=1)
def _a_inv_cols() -> list[int]:
    return _matrix_inverse(_A_COLS)


def crc32c_unshift(crc: int, nbytes: int) -> int:
    """Inverse of :func:`crc32c_shift`: apply ``A^-nbytes`` (remove
    `nbytes` trailing zero bytes from the *raw* register), by
    square-and-multiply over the inverted shift matrix."""
    c = int(crc) & _MASK
    n = int(nbytes)
    if n < 0:
        raise ValueError("negative length")
    mat = _a_inv_cols()
    while n:
        if n & 1:
            c = _matrix_times(mat, c)
        n >>= 1
        if n:
            mat = _matrix_square(mat)
    return c


@functools.lru_cache(maxsize=None)
def crc32c_zeros(nbytes: int) -> int:
    """``crc32c(b"\\x00" * nbytes)`` without touching the bytes:
    conditioning in, ``A^n``, conditioning out."""
    if nbytes == 0:
        return 0
    return (crc32c_shift(_MASK, nbytes) ^ _MASK) & _MASK


def crc32c_zero_unpad(crc: int, pad: int) -> int:
    """``crc32c(A)`` from ``crc32c(A || 0^pad)`` — strip `pad`
    trailing zero bytes from a digest.

    The batch engine right-pads every member payload to its size
    bucket with zeros before the fused device digest; by
    ``crc(A||0^n) = A^n·crc(A) ⊕ crc(0^n)`` the true digest is
    recovered host-side with two 32-bit GF(2) matrix applications —
    no second pass over the data."""
    if pad == 0:
        return int(crc) & _MASK
    return crc32c_unshift((int(crc) ^ crc32c_zeros(pad)) & _MASK, pad)


# ------------------------------------------------------- batch kernel

def _dense(cols: list[int], rows: int = 32) -> np.ndarray:
    """uint32 columns -> dense 0/1 uint8 matrix [rows, len(cols)]."""
    c = np.asarray(cols, dtype=np.uint64)
    return ((c[None, :] >> np.arange(rows, dtype=np.uint64)[:, None])
            & 1).astype(np.uint8)


_A_DENSE = _dense(_A_COLS)
# B: data-byte injection, column s = T[1<<s] (table additivity)
_B_DENSE = _dense([_TABLE[1 << s] for s in range(8)])


@functools.lru_cache(maxsize=None)
def _contrib(length: int) -> tuple[np.ndarray, np.ndarray]:
    """→ (K [32, 8L] with column 8j+s = A^(L-1-j)·B·e_s, A^L [32, 32]),
    built by doubling: K_2n = [A^n·K_n | K_n]."""
    if length == 1:
        return _B_DENSE, _A_DENSE
    if length % 2:
        k1, a1 = _contrib(length - 1)
        head = (a1 @ _B_DENSE) % 2
        return (np.concatenate([head, k1], axis=1).astype(np.uint8),
                ((_A_DENSE @ a1) % 2).astype(np.uint8))
    kh, ah = _contrib(length // 2)
    return (np.concatenate([_gf2_matmul(ah, kh), kh], axis=1),
            ((ah @ ah) % 2).astype(np.uint8))


def _gf2_matmul(a: np.ndarray, b: np.ndarray,
                cols: int = 1 << 20) -> np.ndarray:
    """(a @ b) mod 2 for 0/1 matrices with a 32-deep contraction, as
    float32 BLAS in column blocks — exact (a sum is at most 32).  The
    uint8 matmul numpy would run is not BLAS: it took 137 s of the
    4 MiB scrub segment's matrix on one core."""
    af = a.astype(np.float32)
    out = np.empty((a.shape[0], b.shape[1]), dtype=np.uint8)
    for lo in range(0, b.shape[1], cols):
        blk = af @ b[:, lo:lo + cols].astype(np.float32)
        out[:, lo:lo + cols] = blk.astype(np.uint8) & 1
    return out


@functools.lru_cache(maxsize=64)
def _batch_program(length: int, mesh=None):
    """Jitted ``([n, L] u8 data, [n] u32 seeds, kt, at, const_row) ->
    [n] u32 crcs`` — the contribution matrices are arguments, not
    closure constants: as constants the 4 MiB scrub segment baked a
    1 GiB ``kt`` into its program (~49 s to compile for v5e).

    With ``mesh`` (hashable — jax Mesh instances are) the batch is
    sharded over the row axis across every mesh device: each row's
    digest is an independent matmul against the replicated
    contribution matrix, so the scrub digest scan is pure data
    parallelism."""
    import jax
    import jax.numpy as jnp

    def crc32c_batch(data, seeds, kt, at, const_row):
        n = data.shape[0]
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((data[:, :, None] >> shifts) & jnp.uint8(1))
        bits = bits.reshape(n, 8 * length).astype(jnp.int8)
        acc = jax.lax.dot_general(
            bits, kt, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        sbits = ((seeds[:, None] >> jnp.arange(32, dtype=jnp.uint32))
                 & jnp.uint32(1)).astype(jnp.int8)
        acc = acc + jax.lax.dot_general(
            sbits, at, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        out_bits = ((acc + const_row) & 1).astype(jnp.uint32)
        return jnp.sum(out_bits << jnp.arange(32, dtype=jnp.uint32),
                       axis=-1, dtype=jnp.uint32)

    if mesh is None:
        return jax.jit(crc32c_batch)
    from jax.sharding import NamedSharding, PartitionSpec
    axes = tuple(mesh.axis_names)
    rows2d = NamedSharding(mesh, PartitionSpec(axes, None))
    rows1d = NamedSharding(mesh, PartitionSpec(axes))
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(crc32c_batch,
                   in_shardings=(rows2d, rows1d, rep, rep, rep),
                   out_shardings=rows1d)


@functools.lru_cache(maxsize=64)
def _batch_kernel(length: int, mesh=None):
    """``([n, L] u8 data, [n] u32 seeds) -> [n] u32 crcs``: the
    program of `_batch_program` with its contribution matrices staged
    on the device(s) once (``kt [8L, 32]``, ``at [32, 32]``, the
    conditioned constant ``A^L·F ⊕ F`` as a 0/1 row)."""
    import jax
    import jax.numpy as jnp

    k_dense, a_dense = _contrib(length)
    ones = np.ones(32, dtype=np.uint8)
    host = (k_dense.T.astype(np.int8), a_dense.T.astype(np.int8),
            (((a_dense @ ones) % 2) ^ ones).astype(np.int32))
    if mesh is None:
        consts = tuple(jnp.asarray(c) for c in host)
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        consts = tuple(jax.device_put(c, rep) for c in host)
    program = _batch_program(length, mesh)

    def kernel(data, seeds):
        return program(data, seeds, *consts)
    return kernel


def crc32c_batch(data, seeds=None, mesh=None) -> np.ndarray:
    """CRC-32C of every row of a ``[n, L]`` uint8 batch → ``[n]`` uint32.

    `seeds` (optional ``[n]`` uint32) chains each row from a prior CRC,
    exactly like the `crc` argument of :func:`crc32c`.  `mesh` shards
    the scan data-parallel over the row axis (rows zero-pad up to a
    device-count multiple; pad digests are discarded) — bit-identical
    to the single-device kernel per row.
    """
    import jax.numpy as jnp

    arr = jnp.asarray(data, dtype=jnp.uint8)
    if arr.ndim != 2:
        raise ValueError(f"crc32c_batch wants [n, L], got {arr.shape}")
    n, length = arr.shape
    if length == 0:
        base = np.zeros(n, dtype=np.uint32)
        if seeds is not None:
            base |= np.asarray(seeds, dtype=np.uint32)
        return base
    if seeds is None:
        s = jnp.zeros(n, dtype=jnp.uint32)
    else:
        s = jnp.asarray(seeds, dtype=jnp.uint32)
    if mesh is not None and mesh.size > 1:
        pad = -n % mesh.size
        if pad:
            arr = jnp.pad(arr, ((0, pad), (0, 0)))
            s = jnp.pad(s, (0, pad))
    else:
        mesh = None
    from ..core.device_profiler import DeviceProfiler
    devices = None
    if mesh is not None:
        from ..parallel.mesh import mesh_device_labels
        devices = mesh_device_labels(mesh)
    misses = _batch_kernel.cache_info().misses
    ln = DeviceProfiler.active().start(
        "crc32c", bytes_in=arr.nbytes, rows=int(arr.shape[0]),
        rows_used=n, devices=devices)
    try:
        out = _batch_kernel(length, mesh)(arr, s)
    except Exception:
        if ln is not None:
            ln.abort()
        raise
    res = np.asarray(out, dtype=np.uint32)[:n]
    if ln is not None:
        ln.finish(bytes_out=res.nbytes,
                  cache_hit=_batch_kernel.cache_info().misses == misses)
    return res
