"""Native C++ engine tests — byte-equality against the Python oracle.

Reference test model: ``src/test/erasure-code/TestErasureCodeJerasure.cc``
golden-byte assertions (SURVEY.md §5 tier 1), applied across the
language boundary: the C++ gf256/reed_sol_van must agree with
ceph_tpu.ops.{gf,rs} bit-for-bit, and the coalescing ring must produce
identical parity whether the executor is the native CPU engine or a
Python/JAX batch function (the TPU plug-in seam).
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.ops import gf, rs

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module", autouse=True)
def built():
    if not native.available():
        rc = subprocess.run(["make", "-C", str(REPO / "native")],
                            capture_output=True, text=True)
        if rc.returncode or not native.available():
            pytest.skip(f"native build unavailable: {rc.stderr[-500:]}")


def test_mul_table_matches_oracle():
    assert np.array_equal(native.gf256_mul_table(), gf.GF_MUL_TABLE)


@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3), (10, 4)])
def test_coding_matrix_matches_python(k, m):
    ec = native.NativeEC(k, m)
    assert np.array_equal(ec.coding_matrix(), rs.reed_sol_van_matrix(k, m))
    ec.close()


def test_encode_decode_match_oracle():
    k, m = 8, 3
    ec = native.NativeEC(k, m)
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(k, 4096), dtype=np.uint8)
    parity = ec.encode(data)
    assert np.array_equal(parity, rs.encode_oracle(coding, data))
    # erase two data + one parity chunk; native decode vs original
    chunks = {i: data[i] for i in range(k)} | {
        k + j: parity[j] for j in range(m)}
    for gone in (0, 5, k + 1):
        del chunks[gone]
    out = ec.decode(chunks)
    assert np.array_equal(out, data)
    ec.close()


@pytest.mark.parametrize("tier", [1, 2, 3])
@pytest.mark.parametrize("chunk", [1, 17, 63, 64, 65, 100, 511, 4096,
                                   4097])
def test_simd_tiers_bit_exact_at_odd_sizes(tier, chunk):
    """Every dispatch tier (scalar, AVX2 pshufb, GFNI) at every size
    class — below one vector, straddling the vector width, far past
    it — must match the oracle byte-for-byte (r4: the baseline was
    rewritten from autovectorized loops to hand-dispatched SIMD; a
    tail bug would corrupt parity silently, and without forcing the
    tier the fastest one would shadow the others on this host)."""
    if native.gf256_set_tier(tier) < 0:
        pytest.skip(f"tier {tier} unavailable on this CPU")
    try:
        k, m = 8, 3
        ec = native.NativeEC(k, m)
        coding = rs.reed_sol_van_matrix(k, m)
        rng = np.random.default_rng(chunk)
        data = rng.integers(0, 256, size=(k, chunk), dtype=np.uint8)
        assert np.array_equal(ec.encode(data),
                              rs.encode_oracle(coding, data))
        ec.close()
    finally:
        native.gf256_set_tier(0)


def test_encode_batch_matches_per_stripe_and_custom_matrix():
    """encode_batch must equal per-stripe encode, and with a custom
    matrix it must apply exactly that map (decode's inverse-submatrix
    multiply rides this path)."""
    k, m = 8, 3
    ec = native.NativeEC(k, m)
    coding = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(5, k, 1000), dtype=np.uint8)
    got = ec.encode_batch(data)
    for b in range(5):
        assert np.array_equal(got[b], ec.encode(data[b]))
    dm = rs.decode_matrix(coding, k, [0, 9])
    got_dm = ec.encode_batch(data, matrix=dm)
    for b in range(5):
        assert np.array_equal(got_dm[b], rs.encode_oracle(dm, data[b]))
    ec.close()


def test_decode_with_too_few_chunks_rejected():
    ec = native.NativeEC(4, 2)
    chunks = {i: np.zeros(64, dtype=np.uint8) for i in range(3)}  # < k
    with pytest.raises(ValueError):
        ec.decode(chunks)
    ec.close()


def test_bad_profile_rejected():
    with pytest.raises(ValueError):
        native.NativeEC(0, 2)
    with pytest.raises(ValueError):
        native.NativeEC(4, 2, technique="nonsense")


class TestCoalescingRing:
    def test_cpu_executor_batches(self):
        k, m, chunk = 4, 2, 512
        ec = native.NativeEC(k, m)
        ec.ring_open(capacity=32, chunk_size=chunk)
        rng = np.random.default_rng(1)
        stripes = rng.integers(0, 256, size=(10, k, chunk), dtype=np.uint8)
        slots = [ec.ring_submit(s) for s in stripes]
        assert ec.ring_pending() == 10
        with pytest.raises(KeyError):
            ec.ring_parity(slots[0])   # not flushed yet
        assert ec.ring_flush() == 10
        coding = rs.reed_sol_van_matrix(k, m)
        for s, slot in enumerate(slots):
            assert np.array_equal(ec.ring_parity(slot),
                                  rs.encode_oracle(coding, stripes[s]))
        ec.close()

    def test_python_jax_executor(self):
        """The TPU seam: a JAX batch encode registered as the ring
        executor produces byte-identical parity to the CPU engine."""
        import jax
        from ceph_tpu.ops.gf_jax import GFLinear
        k, m, chunk = 4, 2, 256
        ec = native.NativeEC(k, m)
        ec.ring_open(capacity=8, chunk_size=chunk)
        enc = GFLinear(rs.reed_sol_van_matrix(k, m))
        calls = []

        def jax_executor(batch):
            calls.append(batch.shape[0])
            return np.asarray(enc(jax.device_put(batch)))

        ec.ring_set_python_executor(jax_executor)
        rng = np.random.default_rng(2)
        stripes = rng.integers(0, 256, size=(6, k, chunk), dtype=np.uint8)
        slots = [ec.ring_submit(s) for s in stripes]
        assert ec.ring_flush() == 6
        assert calls == [6]           # ONE coalesced launch
        coding = rs.reed_sol_van_matrix(k, m)
        for s, slot in enumerate(slots):
            assert np.array_equal(ec.ring_parity(slot),
                                  rs.encode_oracle(coding, stripes[s]))
        ec.close()

    def test_ring_full_and_reflush(self):
        k, m, chunk = 2, 1, 128
        ec = native.NativeEC(k, m)
        ec.ring_open(capacity=2, chunk_size=chunk)
        a = np.zeros((k, chunk), dtype=np.uint8)
        s0 = ec.ring_submit(a)
        s1 = ec.ring_submit(a)
        with pytest.raises(BufferError):
            ec.ring_submit(a)
        assert ec.ring_flush() == 2
        s2 = ec.ring_submit(a)
        assert ec.ring_flush() == 1
        # earlier batch's parity is gone after the next flush
        with pytest.raises(KeyError):
            ec.ring_parity(s0)
        ec.ring_parity(s2)
        ec.close()

    def test_failing_executor_falls_back_to_cpu(self):
        """A registered executor that fails (device lost, geometry
        mismatch) must not fail the I/O: the ring re-encodes the batch
        on the CPU engine (the reference's ISA-L→jerasure fallback
        shape)."""
        k, m, chunk = 2, 1, 64
        ec = native.NativeEC(k, m)
        ec.ring_open(capacity=4, chunk_size=chunk)
        ec.ring_set_python_executor(
            lambda batch: (_ for _ in ()).throw(RuntimeError("boom")))
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, size=(k, chunk), dtype=np.uint8)
        slot = ec.ring_submit(data)
        assert ec.ring_flush() == 1
        np.testing.assert_array_equal(ec.ring_parity(slot),
                                      ec.encode(data))
        ec.close()
