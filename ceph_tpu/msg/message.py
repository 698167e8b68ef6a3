"""Typed messages — the `Message` hierarchy and registry.

Reference: ``src/msg/Message.{h,cc}`` + the ~150 ``src/messages/*.h``
classes (SURVEY.md §3.2).  Each RPC is a class with a numeric TYPE;
encode/decode run through the versioned codec so message evolution
follows the same compat rules as the reference.

``JsonMessage`` is the base of the mon, MDS and OSD planes: a JSON
front, after which its byte values (``bytes``, ``bytearray``,
``memoryview``, at any depth of a field) ride as raw data segments —
the reference msgr2's front / data segments
(``src/msg/async/frames_v2.h``), so bulk payloads cross the wire as
themselves.
"""

from __future__ import annotations

import json

from ..core.encoding import DecodeError, Decoder, Encoder

MSG_REGISTRY: dict[int, type["Message"]] = {}


def register_message(cls: type["Message"]) -> type["Message"]:
    if cls.TYPE in MSG_REGISTRY and MSG_REGISTRY[cls.TYPE] is not cls:
        raise ValueError(
            f"message type {cls.TYPE} already taken by "
            f"{MSG_REGISTRY[cls.TYPE].__name__}")
    MSG_REGISTRY[cls.TYPE] = cls
    return cls


class Message:
    """Base message: subclasses set TYPE and implement
    encode_payload/decode_payload; header bookkeeping (seq, priority)
    is filled by the connection."""

    TYPE = 0
    VERSION = 1
    COMPAT = 1
    PRIORITY_DEFAULT = 127
    PRIORITY_HIGH = 196
    #: bytes the last encode or decode carried as raw data segments
    data_bytes = 0

    def __init__(self):
        self.seq = 0
        self.priority = self.PRIORITY_DEFAULT
        #: set on received messages: the Connection it arrived on
        self.connection = None

    # subclass hooks ------------------------------------------------------
    def encode_payload(self, enc: Encoder):
        pass

    def decode_payload(self, dec: Decoder, version: int):
        pass

    # framing -------------------------------------------------------------
    def encode_parts(self) -> list:
        """The encoding as buffers: the header and front, and each data
        segment uncopied (``Encoder.parts``)."""
        enc = Encoder()
        enc.u16(self.TYPE)
        enc.u64(self.seq)
        enc.u8(self.priority)
        with enc.struct_block(self.VERSION, self.COMPAT):
            self.encode_payload(enc)
        return enc.parts()

    def encode(self) -> bytes:
        return b"".join(self.encode_parts())

    @staticmethod
    def decode(data) -> "Message":
        dec = Decoder(data)
        mtype = dec.u16()
        cls = MSG_REGISTRY.get(mtype)
        if cls is None:
            raise ValueError(f"unknown message type {mtype}")
        msg = cls.__new__(cls)
        Message.__init__(msg)
        msg.seq = dec.u64()
        msg.priority = dec.u8()
        with dec.struct_block(cls.VERSION) as blk:
            msg.decode_payload(blk.dec, blk.version)
        return msg

    def __repr__(self):
        return f"{type(self).__name__}(seq={self.seq})"


# A segment reference in the front is {_SEG: index}.  In a message that
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


class JsonMessage(Message):
    """Base: one JSON object as payload (the front), then the raw data
    segments its byte values became.  A message with no byte values
    encodes the same front as ever, and zero segments."""

    VERSION = 2          # 2: the segment table after the front
    FIELDS: tuple = ()

    def __init__(self, **kw):
        super().__init__()
        for f in self.FIELDS:
            setattr(self, f, kw.get(f))

    def encode_payload(self, enc):
        segs = []

        def ref(v):
            if isinstance(v, _BULK):
                segs.append(v)
                return {_SEG: len(segs) - 1}
            raise TypeError(f"{type(v).__name__} is not JSON serializable")

        body = {f: getattr(self, f) for f in self.FIELDS}
        front = json.dumps(body, default=ref)
        if segs and front.count(_JSON_NUL) != len(segs):
            # a NUL besides the references: a user key may start with
            # one, so escape the keys (the decoder sees the same count)
            segs.clear()
            front = json.dumps(_escape_keys(body), default=ref)
        enc.string(front)
        enc.u32(len(segs))
        if segs:
            enc.require_compat(2)   # a v1 decoder would keep references
        for seg in segs:
            enc.blob_ref(seg)
        self.data_bytes = sum(memoryview(seg).nbytes for seg in segs)

    def decode_payload(self, dec, version):
        front = dec.string()
        segs = ([dec.blob() for _ in range(dec.u32())]
                if version >= 2 else [])
        if segs:
            data = json.loads(front, object_hook=_restorer(
                segs, front.count(_JSON_NUL) != len(segs)))
        else:
            data = json.loads(front)
        self.data_bytes = sum(map(len, segs))
        for f in self.FIELDS:
            setattr(self, f, data.get(f))


@register_message
class MGenericPing(Message):
    """Generic liveness probe (the MPing shape)."""

    TYPE = 1

    def __init__(self, stamp: float = 0.0):
        super().__init__()
        self.stamp = stamp

    def encode_payload(self, enc):
        enc.f64(self.stamp)

    def decode_payload(self, dec, version):
        self.stamp = dec.f64()


@register_message
class MGenericReply(Message):
    """Generic ack carrying a JSON-ish string result (test scaffolding
    and simple control RPCs)."""

    TYPE = 2

    def __init__(self, what: str = "", result: int = 0):
        super().__init__()
        self.what = what
        self.result = result

    def encode_payload(self, enc):
        enc.string(self.what)
        enc.s32(self.result)

    def decode_payload(self, dec, version):
        self.what = dec.string()
        self.result = dec.s32()
