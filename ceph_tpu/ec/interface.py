"""The erasure-code plugin contract — `ErasureCodeInterface` analog.

Reference behavior re-created (``src/erasure-code/ErasureCodeInterface.h``
and ``ErasureCode.{h,cc}``; SURVEY.md §3.6):

- ``init(profile)`` — configure from a profile mapping (``k=``, ``m=``,
  ``technique=``, ...), as stored in the OSDMap's erasure-code-profile.
- ``get_chunk_count()`` = k+m, ``get_data_chunk_count()`` = k.
- ``get_chunk_size(stripe_width)`` — per-chunk size with the plugin's
  alignment padding (jerasure pads object size up to k*w*4 bytes).
- ``minimum_to_decode(want, available)`` — which chunks must be fetched;
  the base-class rule: if all wanted chunks are available return them,
  else the first k available in id order (LRC/SHEC/Clay override this).
- ``encode(want_to_encode, data)`` — pad + split into k chunks, compute m
  parity chunks, return the requested subset.
- ``decode(want_to_read, chunks)`` — reconstruct the wanted chunks from
  any sufficient subset.
- ``data_extents(size, extents)`` — which byte ranges of which data
  shards hold a logical extent (a ranged read fetches only those).

Data currency here is numpy uint8 arrays (host) — the TPU engine consumes
batches of stripes; see `ceph_tpu.ec.jax_backend`.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field

import numpy as np


class ECError(Exception):
    pass


@dataclass
class ECProfile:
    """Parsed erasure-code profile (reference: profile strings like
    ``k=8 m=3 plugin=jerasure technique=reed_sol_van``, handled by
    ``OSDMonitor`` and passed to ``ErasureCodePlugin::factory``)."""

    plugin: str = "jerasure"
    k: int = 2
    m: int = 2
    technique: str = "reed_sol_van"
    #: None = "not specified" — each technique resolves its own
    #: default (8 for GF(2^8) codes; smallest valid for bitmatrix)
    w: int | None = None
    extra: dict = field(default_factory=dict)

    @classmethod
    def parse(cls, items) -> "ECProfile":
        """Accepts a dict or an iterable of ``key=value`` strings."""
        if isinstance(items, dict):
            kv = {str(key): str(val) for key, val in items.items()}
        else:
            kv = {}
            for item in items:
                if "=" not in item:
                    raise ECError(f"bad profile parameter {item!r}")
                key, val = item.split("=", 1)
                kv[key.strip()] = val.strip()
        prof = cls()
        prof.plugin = kv.pop("plugin", prof.plugin)
        prof.technique = kv.pop("technique", prof.technique)
        for name in ("k", "m", "w"):
            if name in kv:
                setattr(prof, name, int(kv.pop(name)))
        prof.extra = kv
        return prof


class ErasureCodeInterface(abc.ABC):
    """Abstract plugin. Subclasses set self.k / self.m in __init__."""

    k: int
    m: int
    #: MDS property: ANY k of the k+m chunks reconstruct the stripe.
    #: Non-MDS plugins (SHEC, LRC layers) must override to False so
    #: callers don't assume the first-k-survivors decode rule works.
    is_mds: bool = True

    # -- geometry ----------------------------------------------------------
    def get_chunk_count(self) -> int:
        return self.k + self.m

    def get_data_chunk_count(self) -> int:
        return self.k

    def get_coding_chunk_count(self) -> int:
        return self.m

    def get_alignment(self) -> int:
        """Stripe alignment in bytes. jerasure-equivalent default:
        k * w * sizeof(int) (`ErasureCodeJerasure::get_alignment` with
        per_chunk_alignment off), w=8."""
        return self.k * 8 * 4

    def get_chunk_size(self, stripe_width: int) -> int:
        """Bytes per chunk for a logical stripe of ``stripe_width`` bytes,
        after padding up to alignment (reference:
        ``ErasureCodeJerasure::get_chunk_size``)."""
        alignment = self.get_alignment()
        padded = -(-stripe_width // alignment) * alignment
        return padded // self.k

    # -- the contract ------------------------------------------------------
    def minimum_to_decode(self, want_to_read: set[int],
                          available: set[int]) -> set[int]:
        """Base-class rule (``ErasureCode::_minimum_to_decode``): wanted set
        if fully available, else the first k available chunks in id order."""
        if want_to_read <= available:
            return set(want_to_read)
        if len(available) < self.k:
            raise ECError(
                f"cannot decode: {len(available)} available < k={self.k}")
        return set(sorted(available)[: self.k])

    def minimum_to_decode_with_cost(self, want_to_read: set[int],
                                    available: dict[int, int]) -> set[int]:
        """Cost-aware variant; base class ignores costs (as upstream does)."""
        return self.minimum_to_decode(want_to_read, set(available))

    @abc.abstractmethod
    def _encode_chunks(self, data: np.ndarray) -> np.ndarray:
        """data [k, chunk] uint8 -> parity [m, chunk] uint8."""

    @abc.abstractmethod
    def _decode_chunks(self, chunks: dict[int, np.ndarray],
                       chunk_size: int,
                       want: set[int] | None = None) -> dict[int, np.ndarray]:
        """available chunks -> at least the ``want`` chunks (all chunks if
        ``want`` is None).  Locality-aware codes (LRC) use ``want`` to stop
        after the local repair instead of demanding global recoverability."""

    def encode_prepare(self, data: bytes | np.ndarray) -> np.ndarray:
        """Zero-pad the logical payload and split into k chunks
        (``ErasureCode::encode_prepare`` analog). Returns [k, chunk] uint8."""
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) else data.astype(np.uint8, copy=False)
        chunk = self.get_chunk_size(buf.size)
        padded = np.zeros(chunk * self.k, dtype=np.uint8)
        padded[: buf.size] = buf
        return padded.reshape(self.k, chunk)

    def data_extents(self, size: int, extents
                     ) -> dict[int, tuple[int, int]] | None:
        """Where logical bytes of a ``size``-byte object lie on the data
        shards: each ``(off, length)`` extent, clipped to ``size``, maps
        to ``{shard: (shard_off, shard_len)}``, one covering range per
        touched shard.  Data chunk ``i`` is bytes ``[i * chunk, (i + 1)
        * chunk)`` of the padded payload (``encode_prepare``), stored
        raw and whole as shard ``i`` (``encode``).  None for a plugin
        that lays its chunks out otherwise."""
        cls = type(self)
        if cls.encode_prepare is not ErasureCodeInterface.encode_prepare \
                or cls.encode is not ErasureCodeInterface.encode:
            return None
        chunk = self.get_chunk_size(size)
        out: dict[int, tuple[int, int]] = {}
        for off, length in extents:
            x, end = min(off, size), min(off + length, size)
            while x < end:
                s = x // chunk
                lo, hi = x - s * chunk, min(end, (s + 1) * chunk) - s * chunk
                if s in out:
                    lo, hi = min(lo, out[s][0]), max(hi, sum(out[s]))
                out[s] = (lo, hi - lo)
                x = (s + 1) * chunk
        return out

    def encode(self, want_to_encode: set[int],
               data: bytes | np.ndarray) -> dict[int, np.ndarray]:
        chunks = self.encode_prepare(data)
        parity = self._encode_chunks(chunks)
        out = {}
        for i in want_to_encode:
            if i < self.k:
                out[i] = chunks[i]
            elif i < self.k + self.m:
                out[i] = parity[i - self.k]
            else:
                raise ECError(f"chunk id {i} out of range")
        return out

    def decode(self, want_to_read: set[int],
               chunks: dict[int, np.ndarray]) -> dict[int, np.ndarray]:
        if not chunks:
            raise ECError("no chunks supplied")
        # non-degraded read: everything wanted is present — return it
        # directly (upstream ErasureCode::_decode's early-out), so the
        # minimum_to_decode -> fetch -> decode protocol needs no extra reads
        if set(want_to_read) <= set(chunks):
            return {i: np.asarray(chunks[i], dtype=np.uint8)
                    for i in want_to_read}
        sizes = {np.asarray(c).size for c in chunks.values()}
        if len(sizes) != 1:
            raise ECError(f"chunk sizes differ: {sizes}")
        chunk_size = sizes.pop()
        full = self._decode_chunks(
            {i: np.asarray(c, dtype=np.uint8) for i, c in chunks.items()},
            chunk_size, set(want_to_read))
        return {i: full[i] for i in want_to_read}

    def decode_concat(self, chunks: dict[int, np.ndarray]) -> np.ndarray:
        """Recover and concatenate all data chunks (reference
        ``ErasureCodeInterface::decode_concat``)."""
        out = self.decode(set(range(self.k)), chunks)
        return np.concatenate([out[i] for i in range(self.k)])
