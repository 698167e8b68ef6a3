"""A traffic mix read from its data file: the op sequence as a pure
function of (mix, deployment, seed), and the client-side model of what
every object should hold.

A mix file (``benchmark/traffic/mixes/<name>.json``) gives:

- ``surface``: ``rados`` (whole objects ``bench_<n>``) or ``rbd`` (the
  data objects of the deployment's image, addressed in ``op_bytes``
  blocks);
- ``ops``: integer weights per op kind (rados: ``write_full``,
  ``read``; rbd: ``read``, ``write``).  Every run of ``sum(weights)``
  ops holds each kind exactly its weight, shuffled by the seed, so
  every seed does the same work in another order;
- ``op_bytes`` (rbd ops land on ``op_bytes``-aligned offsets) and
  ``keys`` (``cycle``: a seeded permutation of the working set,
  repeated; or ``uniform``);
- ``working_set`` (rados objects; rbd: the whole image),
  ``prefill`` (write every object once in set-up), ``warmup_ops``;
- ``loop`` (``closed`` with ``depth`` ops outstanding, or ``open`` at
  ``rate`` ops/s with ``arrivals`` ``poisson`` or ``fixed``);
- ``fault``: ``null`` or ``{"kill_osd_holding_shard": s}``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

# payload streams: one per purpose, so adding warm-up ops never shifts
# what the window writes
WINDOW, PREFILL = 0, 1
_KINDS, _KEYS, _ARRIVALS = 2, 3, 4


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *words])


def payload(seed: int, stream: int, index: int, length: int) -> bytes:
    return _rng(seed, stream, index).bytes(length)


@dataclass(frozen=True)
class Op:
    index: int
    kind: str
    oid: str
    off: int
    length: int
    key: tuple          # ops on one key never overlap in time
    stream: int = WINDOW


class Mix:
    """The op sequence of one (mix, deployment, seed)."""

    def __init__(self, mix: dict, deployment: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.surface = mix["surface"]
        self.op_bytes = int(mix["op_bytes"])
        weights = {k: int(v) for k, v in mix["ops"].items() if int(v) > 0}
        self._block = [k for k, w in sorted(weights.items())
                       for _ in range(w)]
        self.object_size = int(deployment["object_size"])
        if self.surface == "rados":
            self.n_keys = int(mix["working_set"])
            self.oids = [f"bench_{n:06d}" for n in range(self.n_keys)]
        elif self.surface == "rbd":
            img = deployment["image"]
            self.image = img["name"]
            n_obj = -(-int(img["size"]) // self.object_size)
            self.oids = [f"rbd_data.{self.image}.{n:016x}"
                         for n in range(n_obj)]
            self.n_keys = int(img["size"]) // self.op_bytes
        else:
            raise ValueError(f"unknown surface {self.surface!r}")
        keys = mix.get("keys", {"dist": "uniform"})
        self._dist = keys["dist"]
        if self._dist == "cycle":
            self._perm = _rng(self.seed, _KEYS).permutation(self.n_keys)
        elif self._dist != "uniform":
            raise ValueError(f"unknown key distribution {self._dist!r}")

    def kind(self, i: int) -> str:
        b, j = divmod(i, len(self._block))
        order = _rng(self.seed, _KINDS, b).permutation(len(self._block))
        return self._block[order[j]]

    def _key(self, i: int) -> int:
        if self._dist == "cycle":
            return int(self._perm[i % self.n_keys])
        u = _rng(self.seed, _KEYS, i).random()
        return min(int(u * self.n_keys), self.n_keys - 1)

    def op(self, i: int) -> Op:
        kind, n = self.kind(i), self._key(i)
        if self.surface == "rados":
            length = self.object_size if kind == "write_full" else 0
            return Op(i, kind, self.oids[n], 0, length, (self.oids[n],))
        byte = n * self.op_bytes
        obj, off = divmod(byte, self.object_size)
        return Op(i, kind, self.oids[obj], off, self.op_bytes,
                  (self.oids[obj], off))

    def prefill_ops(self) -> list[Op]:
        """One whole-object write per object of the working set."""
        return [Op(n, "write_full", oid, 0, self.object_size, (oid,),
                   stream=PREFILL)
                for n, oid in enumerate(self.oids)]

    def data(self, op: Op) -> bytes:
        return payload(self.seed, op.stream, op.index, op.length)

    def arrivals(self, rate: float, kind: str, n: int) -> np.ndarray:
        """Due times (s) of the first n ops of an open loop."""
        if kind == "fixed":
            return np.arange(n) / rate
        gaps = _rng(self.seed, _ARRIVALS).exponential(1.0 / rate, n)
        return np.cumsum(gaps) - gaps[0]


class Model:
    """What each object holds after every acknowledged write, applied
    at acknowledgement.  Ops on one key never overlap in time, so a
    read of a key sees exactly the last acknowledged write to it."""

    def __init__(self, mix: Mix):
        self.mix = mix
        self._lock = threading.Lock()
        # oid -> bytearray, or the Op of a whole-object write whose
        # bytes are regenerated on demand
        self._objs: dict[str, object] = {}

    def _bytes(self, oid: str):
        v = self._objs.get(oid)
        if isinstance(v, Op):
            v = self._objs[oid] = bytearray(self.mix.data(v))
        return v

    def written(self) -> list[str]:
        with self._lock:
            return sorted(self._objs)

    def ack(self, op: Op, data: bytes):
        with self._lock:
            if op.kind == "write_full":
                self._objs[op.oid] = op
                return
            buf = self._bytes(op.oid)
            if buf is None:
                buf = self._objs[op.oid] = bytearray()
            end = op.off + len(data)
            if len(buf) < end:
                buf.extend(b"\x00" * (end - len(buf)))
            buf[op.off:end] = data

    def expected(self, oid: str, off: int = 0,
                 length: int | None = None) -> bytes | None:
        """The object's bytes (None: never written)."""
        with self._lock:
            v = self._objs.get(oid)
            if v is None:
                return None
            if isinstance(v, Op) and off == 0 and length is None:
                return self.mix.data(v)
            buf = self._bytes(oid)
            end = len(buf) if length is None else off + length
            return bytes(buf[off:end])
