"""Fused GF(2^8) matmul Pallas kernel — bit-sliced i32 lanes.

GF(2^8) multiply-accumulate is a GF(2) bitmatrix matmul (the
reference's ``galois_w08_region_multiply`` region loop behind
``src/erasure-code/jerasure``; SURVEY.md §4.2).  The layout is chosen
for the TPU: a uint8 array with k or m sublanes pays (32, 128) tiling
padding, and int8 elementwise ops occupy full 32-bit VPU lanes anyway.
So the kernel moves data as int32 words:

    bytes are processed 4-per-lane as int32 words
      data tile  [k, TN/4] int32            (native (8,128) i32 tiling)
      -> expand  [32k, TN/4] int8 planes    (bit j of word = byte j//8,
                                             bit j%8 — 2 VPU ops/plane)
      -> GF(2) matmul on the MXU            ([32m, 32k] x [32k, TN/4],
                                             256-deep contraction @k=8:
                                             2x the MXU's native depth)
      -> mask + weighted re-pack            ([m, TN/4] int32 words)

    so every array in the pipeline has a 32-bit or sublane-aligned
    int8 layout — no uint8 relayouts — and HBM still moves only data
    once in, parity once out.

The GF(2) matrix is the bit-layout matrix (`gf_jax._bit_layout_matrix`)
block-diagonalized 4x over byte position: byte b of a word only ever
multiplies into byte b of the parity word, so block b maps plane rows
[b*8k, (b+1)*8k) to output rows [b*8m, (b+1)*8m).  Word-internal byte
order therefore cancels: whatever order `lax.bitcast_convert_type`
packs bytes into a word, the same order unpacks the parity word, and GF
acts bytewise.

Mosaic constraints: no vector shifts on sub-32-bit ints — bit
extraction is AND + compare, packing is multiply-add (weights wrap
through int32, bit 31 included); traced under `jax.enable_x64(False)`.

Byte-exactness: `tests/test_gf_pallas2.py` (interpret mode vs the
NumPy oracle and the XLA path).  On the TPU,
`chip_smoke.py::phase_ec_kernels` compares the words kernel's encode
and decode with the oracle and asserts `GFLinear`'s backend is
``"pallas"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_WORD = 4                      # bytes per i32 lane
# lanes per tile (i32 words); 2048 words = 8 KiB rows; VMEM per tile at
# k=8,m=3: data 64 KiB + planes 512 KiB int8 + acc 768 KiB i32 < 2 MiB
_MAX_TNW = 2048

# int32 multiply weights for bit j of a word, wrapping at bit 31
_BIT_W = [int(np.int32(np.uint32(1 << j))) for j in range(32)]
_BIT_MASK = [int(np.int32(np.uint32(1 << j))) for j in range(32)]


def block_diag4(bitmat: np.ndarray) -> np.ndarray:
    """Bit-layout matrix [8m, 8k] -> word-sliced [32m, 32k] int8:
    one identical block per in-word byte position."""
    m8, k8 = bitmat.shape
    out = np.zeros((4 * m8, 4 * k8), dtype=np.int8)
    for b in range(4):
        out[b * m8:(b + 1) * m8, b * k8:(b + 1) * k8] = bitmat
    return out


def _gf_kernel2(bdmat_ref, data_ref, out_ref, *, k: int, m: int):
    """One (stripe, word-tile): expand -> 256-deep matmul -> pack."""
    w = data_ref[0]                                   # [k, TNW] int32
    planes = []
    for j in range(32):                               # row b*8k + s*k + i
        mask = jnp.int32(_BIT_MASK[j])
        planes.append(((w & mask) != 0).astype(jnp.int8))
    bits = jnp.concatenate(planes, axis=0)            # [32k, TNW] int8
    acc = jax.lax.dot_general(
        bdmat_ref[...], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)             # [32m, TNW] int32
    acc = acc & 1
    # out word bit (8b+r) of parity j = acc row b*8m + r*m + j; the
    # weighted sum wraps through int32 (bit 31 = the negative weight)
    word = acc[0:m] * jnp.int32(_BIT_W[0])
    for j in range(1, 32):
        word = word + acc[j * m:(j + 1) * m] * jnp.int32(_BIT_W[j])
    out_ref[0] = word


def _pick_tile(nw: int) -> int:
    for tnw in (_MAX_TNW, 1024, 512, 256, _LANES):
        if tnw <= nw and nw % tnw == 0:
            return tnw
    return nw           # nw < 128: single undersized tile


@functools.partial(jax.jit, static_argnames=("k", "m", "interpret"))
def _gf_apply_pallas2(bdmat, words, *, k: int, m: int,
                      interpret: bool = False):
    """bdmat [32m, 32k] int8, words [B, k, nw] int32 -> [B, m, nw]."""
    b, _, nw = words.shape
    tnw = _pick_tile(nw)
    grid = (b, nw // tnw)
    return pl.pallas_call(
        functools.partial(_gf_kernel2, k=k, m=m),
        out_shape=jax.ShapeDtypeStruct((b, m, nw), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((4 * 8 * m, 4 * 8 * k), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k, tnw), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m, tnw), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bdmat, words)


def gf_matmul_pallas2(bitmat: jnp.ndarray, data: jnp.ndarray, m: int,
                      interpret: bool = False,
                      bdmats: dict | None = None) -> jnp.ndarray:
    """Fused GF(2^8) matmul, v2.  data [..., k, n] uint8 -> [..., m, n].

    Accepts unbatched [k, n] and arbitrary leading batch dims; lane
    extents not divisible by 512 bytes (128 i32 words) are zero-padded
    (GF-linear maps send zero bytes to zero bytes).

    bdmats: optional cache dict (GFLinear passes one) holding the
    device [32m, 32k] matrix under key "v2".
    """
    k8 = bitmat.shape[1]
    k = k8 // 8
    lead = data.shape[:-2]
    n = data.shape[-1]
    x = data.reshape((-1, k, n))
    bsz = x.shape[0]
    npad = -n % (_LANES * _WORD)
    if npad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, npad)))
    nw = (n + npad) // _WORD
    bdmat = (bdmats or {}).get("v2")
    if bdmat is None:
        bdmat = jnp.asarray(block_diag4(np.asarray(bitmat)))
        if bdmats is not None:
            bdmats["v2"] = bdmat
    with jax.enable_x64(False):
        words = jax.lax.bitcast_convert_type(
            x.reshape(bsz, k, nw, _WORD), jnp.int32)
        out = _gf_apply_pallas2(bdmat, words, k=k, m=m,
                                interpret=interpret)
        outb = jax.lax.bitcast_convert_type(out, jnp.uint8)
    outb = outb.reshape(bsz, m, nw * _WORD)[:, :, :n]
    return outb.reshape(*lead, m, n)


# -- word-native path: i32 in, i32 out, no byte<->word relayout ------------
#
# Round-5 discovery (measured on v5e): the fused byte-API kernel above
# tops out ~21 GB/s not because of expand/matmul/pack — an empty
# kernel with the same BlockSpecs runs just as slow — but because of
# the data movement AROUND it: (a) `bitcast_convert_type` u8->i32 is a
# real relayout pass on TPU ((32,128) int8 tiles -> (8,128) i32
# tiles), re-paid every call, and (b) a [B, k, n] uint8 operand with
# k=8 sublanes pays 4x (32,128)-tile padding on every HBM read.
# Feeding the SAME kernel i32 words end-to-end measured 66 GB/s raw in
# round 5 (not re-measured on today's code) — 10x the host's
# gf-complete-strength native baseline (the SURVEY §7 target).
#
# Chunk payloads should therefore live as i32 words on device for
# their whole lifetime; `np.ndarray.view("<i4")` converts on the host
# for free (GF(2^8) acts bytewise, so word endianness cancels between
# pack and unpack — same argument as the block-diagonal layout above).

_MAX_TNW_WORDS = 8192


def _pick_tile_words(nw: int, k: int) -> int:
    # VMEM per tile scales with 32k rows; 8192 lanes measured best for
    # k=8 and stays within budget up to clay-sized k
    for tnw in (_MAX_TNW_WORDS, 4096, 2048, 1024, 512, 256, _LANES):
        if tnw <= nw and nw % tnw == 0:
            return tnw
    return nw


@functools.partial(jax.jit, static_argnames=("k", "m", "interpret"))
def _gf_apply_words(bdmat, mrow, words, *, k: int, m: int,
                    interpret: bool = False):
    """bdmat [32m, 32k] int8, mrow [32k, 1] i32, words [B, k, nw] i32
    -> [B, m, nw] i32."""
    b, _, nw = words.shape
    tnw = _pick_tile_words(nw, k)

    def kern(bd_ref, mrow_ref, data_ref, out_ref):
        w = data_ref[0]                               # [k, TNW] i32
        tiled = jnp.tile(w, (32, 1))                  # [32k, TNW]
        bits = ((tiled & mrow_ref[...]) != 0).astype(jnp.int8)
        acc = jax.lax.dot_general(
            bd_ref[...], bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1
        word = acc[0:m] * jnp.int32(_BIT_W[0])
        for j in range(1, 32):
            word = word + acc[j * m:(j + 1) * m] * jnp.int32(_BIT_W[j])
        out_ref[0] = word

    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b, m, nw), jnp.int32),
        grid=(b, nw // tnw),
        in_specs=[
            pl.BlockSpec((4 * 8 * m, 4 * 8 * k), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4 * 8 * k, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k, tnw), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m, tnw), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(bdmat, mrow, words)


def _word_operands(bitmat, k: int, bdmats: dict | None):
    """Device [32m, 32k] matrix + [32k, 1] per-row bit masks, cached."""
    cached = (bdmats or {}).get("words")
    if cached is not None:
        return cached
    bdmat = jnp.asarray(block_diag4(np.asarray(bitmat)))
    mrow = jnp.asarray(np.array(
        [_BIT_MASK[r // k] for r in range(32 * k)],
        dtype=np.int32).reshape(32 * k, 1))
    # don't poison the cache with tracers if a caller hands us a
    # traced bitmat from inside its own jit (np.asarray above raises
    # for tracers, but be explicit about the concrete-only contract)
    if bdmats is not None and not isinstance(bdmat, jax.core.Tracer):
        bdmats["words"] = (bdmat, mrow)
    return bdmat, mrow


def gf_matmul_words(bitmat: jnp.ndarray, words: jnp.ndarray, m: int,
                    interpret: bool = False,
                    bdmats: dict | None = None) -> jnp.ndarray:
    """Fused GF(2^8) matmul over word-resident chunks.

    words: [..., k, nw] int32 — each lane holds 4 consecutive payload
    bytes (host view ``bytes.view("<i4")``).  Returns [..., m, nw]
    int32 parity words.  nw not divisible by the tile is zero-padded
    (zero bytes map to zero bytes under any GF-linear map).
    """
    k8 = bitmat.shape[1]
    k = k8 // 8
    lead = words.shape[:-2]
    nw = words.shape[-1]
    x = words.reshape((-1, k, nw))
    npad = -nw % _LANES
    if npad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, npad)))
    nwp = nw + npad
    bdmat, mrow = _word_operands(bitmat, k, bdmats)
    with jax.enable_x64(False):
        b = x.shape[0]
        if nwp <= 2048 and b > 1 and b * nwp >= 2048:
            # small-stripe fold: at <=64 KiB stripes the grid
            # degenerates into b narrow steps whose per-tile overhead
            # dominates (measured: 4 KiB 14.9->63.8, 64 KiB
            # 46->62 GB/s; at 128 KiB+ the fold's two transposes turn
            # into a slight net loss, hence the nwp <= 2048 cut).
            # GF acts per lane-column, so fold the stripe batch into
            # the lane axis — one transpose each way buys full-width
            # tiles.
            xt = jnp.moveaxis(x, 0, 1).reshape(1, k, b * nwp)
            out = _gf_apply_words(bdmat, mrow, xt, k=k, m=m,
                                  interpret=interpret)
            out = jnp.moveaxis(out.reshape(m, b, nwp), 1, 0)
        else:
            out = _gf_apply_words(bdmat, mrow, x, k=k, m=m,
                                  interpret=interpret)
    out = out[:, :, :nw]
    return out.reshape(*lead, m, nw)


# -- resident bit-planes: expand once, multiply many -----------------------
#
# Recovery and scrub re-multiply the SAME surviving chunks by several
# decode matrices (multi-target reconstruct, verify-then-repair).  The
# fused kernel above re-expands per call because its input is bytes;
# these entry points keep the expansion in device memory across calls
# ("expand once per buffer lifetime").

@functools.partial(jax.jit, static_argnames=())
def gf_expand_words(data: jnp.ndarray) -> jnp.ndarray:
    """[..., k, n] uint8 (n % 512 == 0) -> [..., 32k, n/4] int8 planes
    in the v2 word-sliced layout."""
    *lead, k, n = data.shape
    nw = n // _WORD
    with jax.enable_x64(False):
        words = jax.lax.bitcast_convert_type(
            data.reshape(*lead, k, nw, _WORD), jnp.int32)
        planes = []
        for j in range(32):
            mask = jnp.int32(_BIT_MASK[j])
            planes.append(((words & mask) != 0).astype(jnp.int8))
        # stack as [32, ..., k, nw] then fold (j, k) -> rows b*8k+s*k+i
        bits = jnp.stack(planes, axis=0)
        bits = jnp.moveaxis(bits, 0, -3)          # [..., 32, k, nw]
    return bits.reshape(*lead, 32 * k, nw)


def _gf_planes_kernel(bdmat_ref, planes_ref, out_ref, *, m: int):
    acc = jax.lax.dot_general(
        bdmat_ref[...], planes_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc = acc & 1
    word = acc[0:m] * jnp.int32(_BIT_W[0])
    for j in range(1, 32):
        word = word + acc[j * m:(j + 1) * m] * jnp.int32(_BIT_W[j])
    out_ref[0] = word


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def _gf_apply_planes(bdmat, planes, *, m: int,
                     interpret: bool = False):
    bsz, k32, nw = planes.shape
    tnw = _pick_tile(nw)
    return pl.pallas_call(
        functools.partial(_gf_planes_kernel, m=m),
        out_shape=jax.ShapeDtypeStruct((bsz, m, nw), jnp.int32),
        grid=(bsz, nw // tnw),
        in_specs=[
            pl.BlockSpec((4 * 8 * m, k32), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k32, tnw), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m, tnw), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bdmat, planes)


def gf_matmul_planes(bitmat: jnp.ndarray, planes: jnp.ndarray, m: int,
                     interpret: bool = False,
                     bdmats: dict | None = None) -> jnp.ndarray:
    """Multiply pre-expanded planes ([..., 32k, nw] int8 from
    `gf_expand_words`) -> [..., m, 4*nw] uint8 parity bytes.

    bdmats: optional cache dict shared with `gf_matmul_pallas2` (same
    "v2" key, same matrix) so the multiply-many loop neither rebuilds
    nor re-uploads the device matrix, and the jitted wrapper reuses
    its compiled executable across calls."""
    k32 = planes.shape[-2]
    nw = planes.shape[-1]
    lead = planes.shape[:-2]
    x = planes.reshape((-1, k32, nw))
    bdmat = (bdmats or {}).get("v2")
    if bdmat is None:
        bdmat = jnp.asarray(block_diag4(np.asarray(bitmat)))
        if bdmats is not None:
            bdmats["v2"] = bdmat
    with jax.enable_x64(False):
        out = _gf_apply_planes(bdmat, x, m=m, interpret=interpret)
        outb = jax.lax.bitcast_convert_type(out, jnp.uint8)
    return outb.reshape(*lead, m, nw * _WORD)


class ResidentPlanes:
    """Expand-once/multiply-many survivor planes, resident on device.

    ``gf_matmul_words`` re-expands its byte input into bit-planes on
    every call, but a recovery sweep multiplies the SAME survivor
    batch by several GF(2^8) matrices: the decode matrix for erased
    data rows, the composed coding∘decode matrix for erased parity
    rows, one matrix per hypothesis in scrub culprit attribution.
    This holder runs :func:`gf_expand_words` once and serves any
    number of :meth:`multiply` calls against the resident planes.

    ``mats`` is an optional shared per-matrix operand cache
    ({matrix bytes: bdmats dict}); hand the same dict to every
    ``ResidentPlanes`` of a sweep and the block-diagonal device
    matrices upload once for the whole sweep instead of once per
    batch (the "held across a whole recovery sweep" half of the
    contract — planes live per batch, matrices per sweep).

    ``mesh`` (a jax Mesh) shards a 3-D batch over the batch axis:
    the planes expand once *sharded* and every :meth:`multiply` is a
    ``shard_map`` of the local Pallas kernel — each device multiplies
    only its resident plane slice, matrices replicated as closure
    constants.  Batches that aren't 3-D or don't divide ``mesh.size``
    silently stay single-device (same results, one chip).
    """

    __slots__ = ("planes", "n", "interpret", "_mats", "mesh", "_spec")

    # gf_expand_words tile contract: byte length % 512 == 0 so the
    # word planes split into whole 128-lane tiles
    _ALIGN = 512

    def __init__(self, data, interpret: bool = False,
                 mats: dict | None = None, mesh=None):
        data = jnp.asarray(data, dtype=jnp.uint8)
        n = int(data.shape[-1])
        pad = -n % self._ALIGN
        if pad:
            width = [(0, 0)] * (data.ndim - 1) + [(0, pad)]
            data = jnp.pad(data, width)
        self.n = n
        self.interpret = interpret
        self._mats = mats if mats is not None else {}
        if mesh is not None and (data.ndim != 3
                                 or data.shape[0] % mesh.size):
            mesh = None
        self.mesh = mesh
        self._spec = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._spec = PartitionSpec(tuple(mesh.axis_names),
                                       None, None)
            data = jax.device_put(data, NamedSharding(mesh, self._spec))
        self.planes = gf_expand_words(data)

    def multiply(self, matrix: np.ndarray) -> jnp.ndarray:
        """GF(2^8) matrix [m, k] × resident planes → [..., m, n]
        uint8 (device value, pad stripped; zero padding is exact:
        zero bytes map to zero bytes under any GF-linear map)."""
        from .gf_jax import _bit_layout_matrix
        mat = np.ascontiguousarray(matrix, dtype=np.uint8)
        bdmats = self._mats.setdefault(mat.tobytes(), {})
        bits = _bit_layout_matrix(mat)
        if self.mesh is not None:
            return self._multiply_mesh(bits, mat.shape[0],
                                       bdmats)[..., : self.n]
        out = gf_matmul_planes(bits, self.planes, mat.shape[0],
                               interpret=self.interpret, bdmats=bdmats)
        return out[..., : self.n]

    def _multiply_mesh(self, bits, m: int, bdmats: dict) -> jnp.ndarray:
        """shard_map of the local planes kernel over the batch axis —
        a sharded operand fed straight to the jitted pallas_call would
        be gathered to one device, so the kernel runs *inside* the
        per-device program instead."""
        bdmat = bdmats.get("v2")
        if bdmat is None:
            bdmat = bdmats["v2"] = jnp.asarray(
                block_diag4(np.asarray(bits)))
        interpret = self.interpret

        def local_fn(planes):           # [Bl, 32k, nw] this device
            out = _gf_apply_planes(bdmat, planes, m=m,
                                   interpret=interpret)
            return jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(
                planes.shape[0], m, -1)

        with jax.enable_x64(False):
            return jax.shard_map(local_fn, mesh=self.mesh,
                                 in_specs=self._spec,
                                 out_specs=self._spec,
                                 check_vma=False)(self.planes)
