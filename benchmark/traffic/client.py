"""The client side of one op: submit through librados, wait for the
reply, check a read against the model, acknowledge a write into it.

Every op goes through librados' asynchronous submission
(``IoCtx.aio_*``), the path ``rados bench`` and librbd take, so a slow
op is waited for, never abandoned at a client deadline while the
cluster may still apply it.  A 4 KiB rbd write is the op librbd sends
for it at stripe count 1: ``write(oid, off, data)`` on the image's data
object; this librados has no public ``aio_write(oid, data, off)``, so it
is built from the same async engine its ``aio_*`` methods use.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from jax.profiler import TraceAnnotation

from .mix import Model, Op

OK, MISMATCH, ERROR, LOST = "ok", "mismatch", "error", "lost"


@dataclass
class Result:
    status: str
    nbytes: int = 0
    detail: str = ""


def _aio_write(io, oid: str, data: bytes, off: int):
    return io._aio(oid, [{"op": "write", "off": off, "data": data.hex()}])


class Client:
    def __init__(self, io, mix, model: Model):
        self.io = io
        self.mix = mix
        self.model = model

    def submit(self, op: Op):
        """→ (completion, payload written or None)."""
        with TraceAnnotation("client_submit"):
            if op.kind == "write_full":
                data = self.mix.data(op)
                return self.io.aio_write_full(op.oid, data), data
            if op.kind == "write":
                data = self.mix.data(op)
                return _aio_write(self.io, op.oid, data, op.off), data
            if op.kind == "read":
                if op.length:
                    return self.io.aio_read(op.oid, op.length,
                                            op.off), None
                return self.io.aio_read(op.oid), None
        raise ValueError(f"unknown op kind {op.kind!r}")

    def finish(self, op: Op, comp, data, deadline: float) -> Result:
        """Wait for the reply until `deadline` (monotonic s)."""
        with TraceAnnotation("client_wait"):
            done = comp.wait_for_complete(
                max(0.0, deadline - time.monotonic()))
        if not done:
            return Result(LOST, detail=f"{op.kind} {op.oid} never "
                                       "completed")
        if comp.rc != 0:
            return Result(ERROR, detail=f"{op.kind} {op.oid} "
                                        f"rc={comp.rc}")
        if data is not None:
            self.model.ack(op, data)
            return Result(OK, len(data))
        got = bytes.fromhex(comp.results[0].get("data", ""))
        want = self.model.expected(op.oid, op.off,
                                   op.length if op.length else None)
        if got != want:
            return Result(MISMATCH, detail=f"read {op.oid}@{op.off}+"
                                           f"{op.length} differs")
        return Result(OK, len(got))
