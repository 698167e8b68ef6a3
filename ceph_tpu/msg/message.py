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
themselves.  That form is ``core/encoding.py``'s
``encode_json_segments``, which the object store's WAL record shares.
"""

from __future__ import annotations

from ..core.encoding import (Decoder, Encoder, decode_json_segments,
                             encode_json_segments)

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
        segs = encode_json_segments(
            enc, {f: getattr(self, f) for f in self.FIELDS})
        if segs:
            enc.require_compat(2)   # a v1 decoder would keep references
        self.data_bytes = sum(memoryview(seg).nbytes for seg in segs)

    def decode_payload(self, dec, version):
        data, segs = decode_json_segments(dec, segments=version >= 2)
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
