"""The plain reference agrees with the program's own oracles at toy
sizes (it imports nothing from them)."""

import numpy as np
import pytest

from benchmark.reference import crc32c as ref_crc
from benchmark.reference import ec as ref_ec


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (2, 1), (6, 3)])
def test_coding_matrix_matches_rs_oracle(k, m):
    from ceph_tpu.ops import rs
    assert np.array_equal(ref_ec.reed_sol_van(k, m),
                          rs.reed_sol_van_matrix(k, m))


@pytest.mark.parametrize("k,m,size", [(8, 3, 65536), (4, 2, 1000),
                                      (2, 1, 4096)])
def test_encode_matches_rs_oracle_and_plugin(k, m, size):
    from ceph_tpu.ec import create_erasure_code
    from ceph_tpu.ops import rs
    payload = np.random.default_rng(k * m).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    coding = ref_ec.reed_sol_van(k, m)
    shards = ref_ec.encode(coding, payload)
    data = np.stack([np.frombuffer(s, np.uint8) for s in shards[:k]])
    parity = rs.encode_oracle(rs.reed_sol_van_matrix(k, m), data)
    assert [p.tobytes() for p in parity] == shards[k:]
    plugin = create_erasure_code({"plugin": "jerasure", "k": str(k),
                                  "m": str(m),
                                  "technique": "reed_sol_van"})
    out = plugin.encode(set(range(k + m)), payload)
    assert [np.asarray(out[i]).tobytes() for i in range(k + m)] == shards


def test_crc32c_matches_slice8_oracle():
    from ceph_tpu.scrub.crc32c_jax import crc32c_slice8
    rng = np.random.default_rng(5)
    rows = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (0, 1, 7, 8, 9, 100, 4096, 4096, 65537)]
    got = ref_crc.crc32c_rows(rows)
    assert [int(g) for g in got] == [crc32c_slice8(r) for r in rows]
    assert ref_crc.crc32c(b"123456789") == 0xE3069283   # RFC 3720


def test_chunk_size_pads_to_jerasure_alignment():
    assert ref_ec.chunk_size(8, 4 << 20) == 512 << 10
    assert ref_ec.chunk_size(4, 1000) == 256        # 1000 -> 1024 / 4
