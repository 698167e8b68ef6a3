"""Versioned binary encoding — the wire/disk codec.

Reference behavior re-created: ``src/include/encoding.h``'s
``ENCODE_START(v, compat, bl)`` / ``DECODE_START`` / ``DECODE_FINISH``
discipline (SURVEY.md §3.1):

- every struct encodes ``(version u8, compat u8, length u32)`` then its
  payload; decoders of an older vintage skip trailing bytes of newer
  encodings, and refuse when ``compat`` exceeds what they understand —
  this is how rolling upgrades interoperate;
- little-endian fixed-width ints, length-prefixed strings/blobs,
  count-prefixed containers — matching the reference's conventions so
  struct layouts translate mechanically.

`Encoder`/`Decoder` wrap a `BufferList`; ``struct_block`` is the
ENCODE_START/FINISH pair as a context manager.  ``Encoder.blob_ref``
appends a blob without copying it in: ``parts()`` hands the encoding
back as a list of buffers with each such blob its own element (the
reference's bufferlist of data segments after a message front).

``encode_json_segments`` / ``decode_json_segments`` decide the one form
of a JSON value whose byte values travel as such data segments: the
messages of ``msg/message.py`` and the object store's WAL record
(``os_store/objectstore.py``) are both written in it.
"""

from __future__ import annotations

import contextlib
import json
import struct

from .buffer import BufferList


class DecodeError(Exception):
    pass


class Encoder:
    def __init__(self):
        self._out = bytearray()
        # blobs kept out of _out: (offset in _out, bytes), in order
        self._refs: list[tuple[int, bytes]] = []
        self._ref_len = 0
        self._compat_pos: list[int] = []   # open struct blocks

    # -- scalars (little-endian, fixed width) ------------------------------
    def u8(self, v: int):
        self._out.append(v & 0xFF)

    def u16(self, v: int):
        self._out += struct.pack("<H", v & 0xFFFF)

    def u32(self, v: int):
        self._out += struct.pack("<I", v & 0xFFFFFFFF)

    def u64(self, v: int):
        self._out += struct.pack("<Q", v & 0xFFFFFFFFFFFFFFFF)

    def s32(self, v: int):
        self._out += struct.pack("<i", v)

    def s64(self, v: int):
        self._out += struct.pack("<q", v)

    def f64(self, v: float):
        self._out += struct.pack("<d", v)

    def boolean(self, v: bool):
        self.u8(1 if v else 0)

    # -- blobs / strings ---------------------------------------------------
    def blob(self, data):
        b = bytes(data)
        self.u32(len(b))
        self._out += b

    def string(self, s: str):
        self.blob(s.encode("utf-8"))

    def raw(self, data):
        self._out += bytes(data)

    def blob_ref(self, data):
        """A length-prefixed blob whose bytes stay a buffer of their own
        (see ``parts``); a mutable buffer is snapshotted."""
        b = data if isinstance(data, bytes) else bytes(data)
        self.u32(len(b))
        if b:
            self._refs.append((len(self._out), b))
            self._ref_len += len(b)

    # -- containers --------------------------------------------------------
    def list_of(self, items, enc_item):
        self.u32(len(items))
        for it in items:
            enc_item(self, it)

    def map_of(self, mapping, enc_key, enc_val):
        self.u32(len(mapping))
        for key, val in mapping.items():
            enc_key(self, key)
            enc_val(self, val)

    # -- ENCODE_START/FINISH ----------------------------------------------
    @contextlib.contextmanager
    def struct_block(self, version: int, compat: int):
        self.u8(version)
        self._compat_pos.append(len(self._out))
        self.u8(compat)
        len_pos = len(self._out)
        ref_len = self._ref_len
        self.u32(0)  # placeholder
        yield self
        self._compat_pos.pop()
        payload = (len(self._out) - len_pos - 4) + (self._ref_len - ref_len)
        self._out[len_pos:len_pos + 4] = struct.pack("<I", payload)

    def require_compat(self, compat: int):
        """Raise the innermost open struct's compat: what was written
        needs a decoder that understands at least this version."""
        pos = self._compat_pos[-1]
        self._out[pos] = max(self._out[pos], compat)

    # -- output ------------------------------------------------------------
    def parts(self) -> list:
        """The encoding as buffers whose concatenation is ``bytes(self)``:
        every ``blob_ref`` blob is an element of its own, uncopied."""
        out, pos = [], 0
        view = memoryview(self._out)
        for off, b in self._refs:
            if off > pos:
                out.append(bytes(view[pos:off]))
            out.append(b)
            pos = off
        if pos < len(self._out) or not out:
            out.append(bytes(view[pos:]))
        view.release()
        return out

    def bl(self) -> BufferList:
        return BufferList(bytes(self))

    def __bytes__(self) -> bytes:
        if not self._refs:
            return bytes(self._out)
        return b"".join(self.parts())


class Decoder:
    def __init__(self, data):
        if isinstance(data, BufferList) and data.num_buffers == 1:
            self._mv = data._ptrs[0].view()  # zero-copy single segment
        elif isinstance(data, (bytes, memoryview)):
            self._mv = memoryview(data)      # a received frame, uncopied
        else:
            self._mv = memoryview(bytes(data))
        self._pos = 0

    def _take(self, n: int) -> memoryview:
        if self._pos + n > len(self._mv):
            raise DecodeError(
                f"buffer exhausted: need {n} at {self._pos}, "
                f"have {len(self._mv)}")
        mv = self._mv[self._pos:self._pos + n]
        self._pos += n
        return mv

    def remaining(self) -> int:
        return len(self._mv) - self._pos

    # -- scalars -----------------------------------------------------------
    def u8(self) -> int:
        return self._take(1)[0]

    def u16(self) -> int:
        return struct.unpack("<H", self._take(2))[0]

    def u32(self) -> int:
        return struct.unpack("<I", self._take(4))[0]

    def u64(self) -> int:
        return struct.unpack("<Q", self._take(8))[0]

    def s32(self) -> int:
        return struct.unpack("<i", self._take(4))[0]

    def s64(self) -> int:
        return struct.unpack("<q", self._take(8))[0]

    def f64(self) -> float:
        return struct.unpack("<d", self._take(8))[0]

    def boolean(self) -> bool:
        return self.u8() != 0

    # -- blobs / strings ---------------------------------------------------
    def blob(self) -> bytes:
        n = self.u32()
        return bytes(self._take(n))

    def string(self) -> str:
        return self.blob().decode("utf-8")

    def raw(self, n: int) -> bytes:
        return bytes(self._take(n))

    # -- containers --------------------------------------------------------
    def list_of(self, dec_item) -> list:
        return [dec_item(self) for _ in range(self.u32())]

    def map_of(self, dec_key, dec_val) -> dict:
        return {dec_key(self): dec_val(self)
                for _ in range(self.u32())}

    # -- DECODE_START/FINISH ----------------------------------------------
    @contextlib.contextmanager
    def struct_block(self, understood_version: int):
        """DECODE_START(understood, bl) ... DECODE_FINISH: refuses if the
        encoder's compat exceeds what we understand; skips trailing bytes
        a newer encoder appended."""
        version = self.u8()
        compat = self.u8()
        length = self.u32()
        if compat > understood_version:
            raise DecodeError(
                f"struct compat {compat} > understood "
                f"{understood_version}")
        end = self._pos + length
        if end > len(self._mv):
            raise DecodeError("struct length overruns buffer")
        block = _Block(self, version, end)
        yield block
        if self._pos > end:
            raise DecodeError("struct overread")
        self._pos = end  # skip newer fields


class _Block:
    """Handle yielded inside a struct_block: exposes the encoded version
    (so decoders can gate per-field reads) and bounds."""

    def __init__(self, dec: Decoder, version: int, end: int):
        self.dec = dec
        self.version = version
        self._end = end

    def has_more(self) -> bool:
        return self.dec._pos < self._end


# -- a JSON front, then its byte values as raw data segments ---------------
# A segment reference in the front is {_SEG: index}.  In a value that
# carries segments, every user dict key starting with NUL gains one more
# NUL, so no user dict can read as a reference.
_NUL = "\x00"
_SEG = _NUL + "seg"
_BULK = (bytes, bytearray, memoryview)
_JSON_NUL = "\\u0000"      # how json.dumps writes a NUL


def _escape_keys(obj):
    if isinstance(obj, dict):
        return {(_NUL + k if isinstance(k, str) and k.startswith(_NUL)
                 else k): _escape_keys(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_escape_keys(v) for v in obj]
    return obj


def _restorer(segs: list, escaped: bool):
    """json object_hook: references → their segments; unescape keys."""
    def restore(d):
        if _SEG in d and len(d) == 1:
            i = d[_SEG]
            if type(i) is not int or not 0 <= i < len(segs):
                raise DecodeError(f"bad data segment reference {i!r}")
            return segs[i]
        if escaped:
            return {(k[1:] if k.startswith(_NUL) else k): v
                    for k, v in d.items()}
        return d
    return restore


def encode_json_segments(enc: Encoder, obj) -> list:
    """Write ``obj`` as its JSON front (a string), then a u32 count and
    each of its byte values (``bytes``, ``bytearray``, ``memoryview``,
    at any depth) as a ``blob_ref`` segment; → the segments.  A value
    with no byte values writes its plain JSON and zero segments."""
    segs = []

    def ref(v):
        if isinstance(v, _BULK):
            segs.append(v)
            return {_SEG: len(segs) - 1}
        raise TypeError(f"{type(v).__name__} is not JSON serializable")

    front = json.dumps(obj, default=ref)
    if segs and front.count(_JSON_NUL) != len(segs):
        # a NUL besides the references: a user key may start with
        # one, so escape the keys (the decoder sees the same count)
        segs.clear()
        front = json.dumps(_escape_keys(obj), default=ref)
    enc.string(front)
    enc.u32(len(segs))
    for seg in segs:
        enc.blob_ref(seg)
    return segs


def decode_json_segments(dec: Decoder, segments: bool = True):
    """The inverse of ``encode_json_segments`` → ``(obj, segments)``;
    ``segments=False`` reads a bare front (a message of the vintage
    before data segments)."""
    front = dec.string()
    segs = [dec.blob() for _ in range(dec.u32())] if segments else []
    if segs:
        obj = json.loads(front, object_hook=_restorer(
            segs, front.count(_JSON_NUL) != len(segs)))
    else:
        obj = json.loads(front)
    return obj, segs
