"""Plain reference for the program's ``reed_sol_van`` erasure code, w=8.

A self-contained copy of the construction the program uses: the
extended Vandermonde matrix over GF(2^8) (polynomial 0x11d),
column-eliminated until its top k x k block is the identity, as the
first half of jerasure's ``reed_sol_big_vandermonde_distribution_matrix``
(reed_sol.c) does.  Published jerasure then scales the coding rows so
that the first row and the first column are all ones; the program does
not, so the parity bytes it stores differ from Ceph's jerasure plugin
(for k=8 m=3 jerasure's first coding row is all ones, this one's is
26 132 186 51 231 16 198 39), and this reference checks the program's
own layout, not Ceph's bytes.  Also ``ErasureCodeJerasure::get_chunk_size``.
It imports nothing from the system under test, so a change to the
system cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np

GF_POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of 0")
    return int(_EXP[255 - _LOG[a]])


# MUL[a, b] = a * b in GF(2^8)
MUL = np.array([[gf_mul(a, b) for b in range(256)] for a in range(256)],
               dtype=np.uint8)


def reed_sol_van(k: int, m: int) -> np.ndarray:
    """The [m, k] coding rows: the extended Vandermonde matrix of
    k+m rows, column-eliminated until its top k x k block is the
    identity, bottom m rows (without jerasure's final scaling: see the
    module's docstring)."""
    rows, cols = k + m, k
    if rows > 256:
        raise ValueError("k+m must be <= 256 for w=8")
    d = [[0] * cols for _ in range(rows)]
    d[0][0] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(cols):
            d[i][j] = acc
            acc = gf_mul(acc, i)
    d[rows - 1][cols - 1] = 1
    for i in range(1, cols):
        j = i
        while j < rows and d[j][i] == 0:
            j += 1
        if j >= rows:
            raise ValueError("no distribution matrix for this k, m")
        d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:
            inv = gf_inv(d[i][i])
            for r in range(rows):
                d[r][i] = gf_mul(d[r][i], inv)
        for c in range(cols):
            f = d[i][c]
            if c != i and f:
                for r in range(i, rows):
                    d[r][c] ^= gf_mul(d[r][i], f)
    return np.array(d[k:], dtype=np.uint8)


def chunk_size(k: int, length: int, w: int = 8) -> int:
    """jerasure's chunk size: the object padded to k*w*4 bytes, over k."""
    align = k * w * 4
    return -(-length // align) * align // k


def encode(coding: np.ndarray, payload: bytes) -> list[bytes]:
    """All k+m shards of one object: the zero-padded payload split in
    k data chunks, then m parity chunks, each the GF(2^8) sum over
    data chunks of coefficient x chunk."""
    m, k = coding.shape
    n = chunk_size(k, len(payload))
    buf = np.zeros(k * n, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = buf.reshape(k, n)
    shards = [data[i].tobytes() for i in range(k)]
    for r in range(m):
        acc = np.zeros(n, dtype=np.uint8)
        for c in range(k):
            coef = int(coding[r, c])
            if coef:
                acc ^= MUL[coef][data[c]]
        shards.append(acc.tobytes())
    return shards
