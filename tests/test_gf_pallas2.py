"""Byte-exactness of the bit-sliced Pallas GF kernel (interpret mode
on CPU).  On the TPU, `chip_smoke.py::phase_ec_kernels` compares the
words kernel with the oracle and asserts `GFLinear`'s backend is
``"pallas"``."""

import numpy as np
import pytest

from ceph_tpu.ops import rs
from ceph_tpu.ops.gf_jax import GFLinear, _bit_layout_matrix
from ceph_tpu.ops.gf_pallas2 import (gf_expand_words, gf_matmul_pallas2,
                                     gf_matmul_planes)


@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3), (8, 4)])
@pytest.mark.parametrize("batch,chunk", [((), 512), ((3,), 1024),
                                         ((2,), 700)])
def test_v2_matches_oracle(k, m, batch, chunk):
    coding = rs.reed_sol_van_matrix(k, m)
    bitmat = _bit_layout_matrix(coding)
    rng = np.random.default_rng(k * 100 + m)
    data = rng.integers(0, 256, size=(*batch, k, chunk), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas2(bitmat, data, m, interpret=True))
    assert got.shape == (*batch, m, chunk)
    flat = data.reshape(-1, k, chunk)
    want = np.stack([rs.encode_oracle(coding, d) for d in flat])
    assert np.array_equal(got.reshape(-1, m, chunk), want)


def test_v2_decode_roundtrip():
    k, m = 8, 4
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
    parity = np.asarray(gf_matmul_pallas2(
        _bit_layout_matrix(coding), data, m, interpret=True))
    erasures = [1, 6, 9]
    dm = rs.decode_matrix(coding, k, erasures)
    survivors = [i for i in range(k + m) if i not in erasures][:k]
    stack = np.stack([data[i] if i < k else parity[i - k]
                      for i in survivors])
    out = np.asarray(gf_matmul_pallas2(
        _bit_layout_matrix(dm), stack, dm.shape[0], interpret=True))
    assert np.array_equal(out[:k], data)


def test_v2_odd_lane_padding():
    """n not divisible by 512 → zero-pad path must stay exact."""
    k, m = 4, 2
    coding = rs.reed_sol_van_matrix(k, m)
    bitmat = _bit_layout_matrix(coding)
    rng = np.random.default_rng(3)
    for n in (4, 100, 513, 4096 + 36):
        data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
        got = np.asarray(gf_matmul_pallas2(bitmat, data, m,
                                           interpret=True))
        want = rs.encode_oracle(coding, data)
        assert np.array_equal(got, want), n


def test_resident_planes_match_fused():
    """expand-once + multiply-many == the fused kernel: the recovery
    path can keep survivors expanded across several decode matrices
    (VERDICT r4 #1 'expand once per buffer lifetime')."""
    k, m = 8, 3
    coding = rs.reed_sol_van_matrix(k, m)
    bitmat = _bit_layout_matrix(coding)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(2, k, 1024), dtype=np.uint8)
    planes = np.asarray(gf_expand_words(data))
    assert planes.shape == (2, 32 * k, 1024 // 4)
    fused = np.asarray(gf_matmul_pallas2(bitmat, data, m,
                                         interpret=True))
    from_planes = np.asarray(gf_matmul_planes(bitmat, planes, m,
                                              interpret=True))
    assert np.array_equal(fused, from_planes)
    # a second matrix over the SAME planes (multi-target reconstruct)
    dm = rs.decode_matrix(coding, k, [0, 2])
    got2 = np.asarray(gf_matmul_planes(
        _bit_layout_matrix(dm), planes, dm.shape[0], interpret=True))
    want2 = np.stack([rs.encode_oracle(dm, d) for d in data])
    assert np.array_equal(got2, want2)


def test_gflinear_pallas_backend_is_v2():
    """GFLinear's production "pallas" backend routes to the v2 kernel
    and stays byte-exact through the class interface."""
    from ceph_tpu.ops.gf_jax import GFLinear
    k, m = 8, 3
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, size=(2, k, 640), dtype=np.uint8)
    enc = GFLinear(coding, backend="pallas-interpret")
    got = np.asarray(enc(data))
    want = np.stack([rs.encode_oracle(coding, d) for d in data])
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3)])
@pytest.mark.parametrize("batch,chunk", [((), 128), ((3,), 256),
                                         ((2,), 200)])
def test_pallas_matches_oracle(k, m, batch, chunk):
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(k * 100 + m)
    data = rng.integers(0, 256, size=(*batch, k, chunk), dtype=np.uint8)
    want = rs.encode_oracle(coding, data.reshape(-1, k, chunk)[0]) \
        if batch else rs.encode_oracle(coding, data)
    enc = GFLinear(coding, backend="pallas-interpret")
    got = np.asarray(enc(data))
    assert got.shape == (*batch, m, chunk)
    ref = GFLinear(coding, backend="xla")
    assert np.array_equal(got, np.asarray(ref(data)))
    if not batch:
        assert np.array_equal(got, want)


def test_pallas_decode_roundtrip():
    k, m = 4, 2
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(k, 384), dtype=np.uint8)
    parity = np.asarray(GFLinear(coding,
                                 backend="pallas-interpret")(data))
    # erase two data chunks, decode from survivors
    erasures = [0, 2]
    dm = rs.decode_matrix(coding, k, erasures)
    survivors = [i for i in range(k + m) if i not in erasures][:k]
    stack = np.stack([data[i] if i < k else parity[i - k]
                      for i in survivors])
    dec = GFLinear(dm, backend="pallas-interpret")
    out = np.asarray(dec(stack))
    assert np.array_equal(out, data)


# -- word-native path (round 5: the 10x production encode kernel) ----------

@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3), (8, 4)])
@pytest.mark.parametrize("batch,chunk", [((), 512), ((3,), 1024),
                                         ((2,), 1664)])
def test_words_matches_oracle(k, m, batch, chunk):
    from ceph_tpu.ops.gf_pallas2 import gf_matmul_words
    coding = rs.reed_sol_van_matrix(k, m)
    bitmat = _bit_layout_matrix(coding)
    rng = np.random.default_rng(k * 10 + m)
    data = rng.integers(0, 256, size=(*batch, k, chunk), dtype=np.uint8)
    words = data.view("<i4")
    got = np.asarray(gf_matmul_words(bitmat, words, m, interpret=True))
    assert got.shape == (*batch, m, chunk // 4)
    assert got.dtype == np.int32
    flat = data.reshape(-1, k, chunk)
    want = np.stack([rs.encode_oracle(coding, d) for d in flat])
    gotb = np.ascontiguousarray(got).view("<u1").reshape(-1, m, chunk)
    assert np.array_equal(gotb, want)


def test_words_class_roundtrip_decode():
    from ceph_tpu.ops.gf_jax import GFLinearWords
    k, m = 8, 3
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(2, k, 2048), dtype=np.uint8)
    enc = GFLinearWords(coding, interpret=True)
    parity = GFLinearWords.to_bytes(
        np.asarray(enc(GFLinearWords.to_words(data))))
    want = np.stack([rs.encode_oracle(coding, d) for d in data])
    assert np.array_equal(parity, want)

    erasures = [0, 9]
    dm = rs.decode_matrix(coding, k, erasures)
    survivors = [i for i in range(k + m) if i not in erasures][:k]
    stack = np.stack([[data[b][i] if i < k else want[b][i - k]
                       for i in survivors] for b in range(2)])
    dec = GFLinearWords(dm, interpret=True)
    rec = GFLinearWords.to_bytes(
        np.asarray(dec(GFLinearWords.to_words(stack))))
    assert np.array_equal(rec, data)


def test_words_matches_byte_api():
    """The word-native path computes the same map as the byte API."""
    from ceph_tpu.ops.gf_pallas2 import gf_matmul_words
    k, m = 4, 2
    coding = rs.reed_sol_van_matrix(k, m)
    bitmat = _bit_layout_matrix(coding)
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, size=(k, 1024), dtype=np.uint8)
    via_bytes = np.asarray(
        gf_matmul_pallas2(bitmat, data, m, interpret=True))
    via_words = np.ascontiguousarray(np.asarray(gf_matmul_words(
        bitmat, data.view("<i4"), m, interpret=True))).view("<u1")
    assert np.array_equal(via_bytes, via_words.reshape(m, -1))
