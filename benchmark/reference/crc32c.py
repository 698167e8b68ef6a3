"""Plain reference CRC-32C (Castagnoli, reflected 0x82F63B78, init and
final xor 0xFFFFFFFF): the slice-by-8 table algorithm, run over many
equal-length rows at once with NumPy so a sample of shards is digested
in seconds.  Self-contained: nothing here comes from the system under
test."""

from __future__ import annotations

import numpy as np

_POLY = 0x82F63B78
_MASK = 0xFFFFFFFF


def _table() -> np.ndarray:
    tab = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        tab[i] = c
    return tab


_T = _table()
# _T8[j][b]: byte b followed by j zero bytes
_T8 = [_T]
for _ in range(7):
    _p = _T8[-1]
    _T8.append((_p >> 8) ^ _T[_p & 0xFF])


def crc32c(data: bytes) -> int:
    """CRC-32C of one buffer."""
    return int(crc32c_rows([data])[0])


def crc32c_rows(rows: list[bytes]) -> np.ndarray:
    """CRC-32C of every row; rows of equal length go through one
    vectorised slice-by-8 pass."""
    out = np.zeros(len(rows), dtype=np.uint32)
    by_len: dict[int, list[int]] = {}
    for i, r in enumerate(rows):
        by_len.setdefault(len(r), []).append(i)
    for n, idx in by_len.items():
        a = np.frombuffer(b"".join(rows[i] for i in idx),
                          dtype=np.uint8).reshape(len(idx), n)
        out[idx] = _crc_equal_rows(a)
    return out


def _crc_equal_rows(a: np.ndarray) -> np.ndarray:
    r, n = a.shape
    c = np.full(r, _MASK, dtype=np.uint32)
    n8 = n - n % 8
    if n8:
        w = a[:, :n8].reshape(r, n8 // 8, 8).astype(np.uint32)
        lo = w[:, :, 0] | (w[:, :, 1] << 8) | (w[:, :, 2] << 16) \
            | (w[:, :, 3] << 24)
        hi = w[:, :, 4:]
        z0, z1, z2, z3, z4, z5, z6, z7 = _T8
        for s in range(n8 // 8):
            x = c ^ lo[:, s]
            h = hi[:, s]
            c = (z7[x & 0xFF] ^ z6[(x >> 8) & 0xFF]
                 ^ z5[(x >> 16) & 0xFF] ^ z4[x >> 24]
                 ^ z3[h[:, 0]] ^ z2[h[:, 1]] ^ z1[h[:, 2]] ^ z0[h[:, 3]])
    for j in range(n8, n):
        c = (c >> 8) ^ _T[(c ^ a[:, j]) & 0xFF]
    return c ^ np.uint32(_MASK)
