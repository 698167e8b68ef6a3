"""Client ↔ MDS message types (reference ``src/messages/
MClientRequest.h`` / ``MClientReply.h`` / ``MClientSession.h`` —
SURVEY.md §3.2/§3.9).  JSON-in-frame like the mon plane: metadata RPC
is evolvability-bound, not byte-bound; the data plane never touches
the MDS."""

from __future__ import annotations

from ..msg.message import JsonMessage, register_message


@register_message
class MClientSession(JsonMessage):
    """Session open/close handshake (reference MClientSession).
    op: "request_open" / "request_close" from the client,
    "open" / "close" from the MDS."""
    TYPE = 60
    FIELDS = ("op", "client", "seq")


@register_message
class MClientRequest(JsonMessage):
    """One metadata op.  `op` names the call (mkdir/create/lookup/
    readdir/unlink/rmdir/rename/setattr/getattr), `args` its operands
    (parent ino + dentry name addressing, like the reference's
    filepath-relative ops)."""
    TYPE = 61
    FIELDS = ("tid", "client", "op", "args")


@register_message
class MClientReply(JsonMessage):
    TYPE = 62
    FIELDS = ("tid", "rc", "outs", "result")
