"""JAX GF engine vs NumPy oracle: byte-exact on every path."""

import numpy as np
import pytest

from ceph_tpu.ops import gf, rs
from ceph_tpu.ops.gf_jax import GFLinear, gf_matmul_bits, gf_matmul_gather, _bit_layout_matrix

import jax.numpy as jnp


@pytest.mark.parametrize("k,m", [(3, 2), (8, 3), (8, 4)])
@pytest.mark.parametrize("use_bits", [True, False])
def test_encode_matches_oracle(k, m, use_bits):
    rng = np.random.default_rng(11)
    coding = rs.reed_sol_van_matrix(k, m)
    data = rng.integers(0, 256, size=(k, 128), dtype=np.uint8)
    expected = rs.encode_oracle(coding, data)
    enc = GFLinear(coding, use_bits=use_bits)
    out = np.asarray(enc(data))
    assert out.dtype == np.uint8
    assert np.array_equal(out, expected)


@pytest.mark.parametrize("use_bits", [True, False])
def test_batched_encode(use_bits):
    rng = np.random.default_rng(12)
    k, m, B, n = 4, 2, 5, 64
    coding = rs.cauchy_good_matrix(k, m)
    data = rng.integers(0, 256, size=(B, k, n), dtype=np.uint8)
    enc = GFLinear(coding, use_bits=use_bits)
    out = np.asarray(enc(data))
    for b in range(B):
        assert np.array_equal(out[b], rs.encode_oracle(coding, data[b]))


def test_decode_via_inverse_matches():
    rng = np.random.default_rng(13)
    k, m, n = 8, 3, 256
    coding = rs.reed_sol_van_matrix(k, m)
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    parity = rs.encode_oracle(coding, data)
    erasures = [1, 5, 9]  # two data + one parity erased
    dm = rs.decode_matrix(coding, k, erasures)
    survivors = [i for i in range(k + m) if i not in erasures][:k]
    stacked = np.stack([data[i] if i < k else parity[i - k] for i in survivors])
    dec = GFLinear(dm)
    rec = np.asarray(dec(stacked))
    assert np.array_equal(rec, data)


def test_gather_vs_bits_paths_agree():
    rng = np.random.default_rng(14)
    coding = rng.integers(0, 256, size=(5, 7), dtype=np.uint8)
    data = rng.integers(0, 256, size=(7, 96), dtype=np.uint8)
    a = np.asarray(gf_matmul_gather(jnp.asarray(coding), jnp.asarray(data)))
    b = np.asarray(gf_matmul_bits(jnp.asarray(_bit_layout_matrix(coding)),
                                  jnp.asarray(data), 5))
    assert np.array_equal(a, b)
    assert np.array_equal(a, gf.gf_matmul(coding, data))


@pytest.mark.parametrize("backend", ["pallas-interp", "words"])
def test_unknown_backend_is_refused(backend):
    """A name outside GFLinear.BACKENDS raises instead of silently
    running the XLA path."""
    with pytest.raises(ValueError, match="unknown GFLinear backend"):
        GFLinear(rs.reed_sol_van_matrix(4, 2), backend=backend)
