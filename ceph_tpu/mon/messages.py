"""Monitor message types (reference ``src/messages/MMon*.h``,
``MOSDBoot/MOSDFailure/MOSDMap`` — SURVEY.md §3.2/§3.4).  Payloads are
JSON-in-frame (``msg.message.JsonMessage``): the control plane
optimizes for evolvability, not bytes.
"""

from __future__ import annotations

from ..msg.message import JsonMessage, register_message


@register_message
class MMonElection(JsonMessage):
    TYPE = 16
    FIELDS = ("payload",)


@register_message
class MMonPaxos(JsonMessage):
    TYPE = 17
    FIELDS = ("payload",)


@register_message
class MMonCommand(JsonMessage):
    TYPE = 18
    FIELDS = ("tid", "cmd")       # cmd: dict with "prefix" etc.


@register_message
class MMonCommandReply(JsonMessage):
    TYPE = 19
    FIELDS = ("tid", "rc", "outs", "outb")  # status str, output obj


@register_message
class MMonSubscribe(JsonMessage):
    TYPE = 20
    FIELDS = ("what",)            # {"osdmap": start_epoch, ...}


@register_message
class MMonMap(JsonMessage):
    TYPE = 21
    FIELDS = ("monmap",)


@register_message
class MOSDMapMsg(JsonMessage):
    TYPE = 22
    # full map dict (epoch-stamped); `newest` is the mon's current
    # epoch so a subscriber replaying history (start>0 subscriptions
    # get the whole range) can tell catch-up maps from live ones
    FIELDS = ("epoch", "osdmap", "newest")


@register_message
class MOSDBoot(JsonMessage):
    TYPE = 23
    FIELDS = ("osd", "addr", "fwd")


@register_message
class MOSDFailure(JsonMessage):
    TYPE = 24
    FIELDS = ("target", "reporter", "fwd")


@register_message
class MOSDAlive(JsonMessage):
    """A would-be primary asks the mon to record up_thru = want
    before it activates (reference ``src/messages/MOSDAlive.h``)."""
    TYPE = 25
    FIELDS = ("osd", "want", "fwd")


@register_message
class MMDSBeacon(JsonMessage):
    """MDS → mon: liveness + desired state (reference
    ``src/messages/MMDSBeacon.h``).  addr is [host, port] of the MDS's
    client-facing messenger."""
    TYPE = 27
    FIELDS = ("name", "addr", "state", "seq", "fwd")


@register_message
class MFSMapMsg(JsonMessage):
    """Mon → subscriber: full FSMap push (reference MFSMap)."""
    TYPE = 28
    FIELDS = ("epoch", "fsmap")


@register_message
class MMgrBeacon(JsonMessage):
    """mgr → mon: liveness + address (reference
    ``src/messages/MMgrBeacon.h``)."""
    TYPE = 29
    FIELDS = ("name", "addr", "seq", "fwd")


@register_message
class MMgrMapMsg(JsonMessage):
    """Mon → subscriber: full MgrMap push (reference MMgrMap)."""
    TYPE = 30
    FIELDS = ("epoch", "mgrmap")


@register_message
class MLog(JsonMessage):
    """Daemon → mon: batched cluster-log entries (reference
    ``src/messages/MLog.h``).  entries: [{"stamp", "name", "channel",
    "prio", "text"}] — LogClient ships the unsent tail, LogMonitor
    commits through paxos and serves ``ceph log last``."""
    TYPE = 31
    FIELDS = ("entries", "fwd")


@register_message
class MMonEvent(JsonMessage):
    """Mon → "events" subscriber: one live event-stream record (the
    `ceph -w` feed — reference MLog/MMonHealth pushes folded into one
    frame).  kind: "health" | "clog" | "progress"; data: the record;
    fwd set on leader→peer fan-out of non-paxos events (progress)."""
    TYPE = 32
    FIELDS = ("kind", "data", "stamp", "fwd")


@register_message
class MMonPing(JsonMessage):
    """MonClient ↔ mon session keepalive (reference MonClient::tick
    keepalive + hunt).  Client sends ``tid``; the mon echoes it with
    ``ack=1`` and whether it currently sits in quorum — a silent or
    out-of-quorum session makes the client hunt a different mon, which
    is what lets subscribers survive a blacked-out site whose TCP
    links never reset."""
    TYPE = 33
    FIELDS = ("tid", "ack", "quorum")


@register_message
class MPGStats(JsonMessage):
    """Primary OSD → mon: per-PG state/object counts (reference
    MPGStats → PGMap aggregation, ``src/mon/PGMap.cc``).  pg_stats:
    {pgid: {"state", "num_objects", "log_size", "last_scrub",
    "scrub_errors"}}."""
    TYPE = 26
    FIELDS = ("osd", "epoch", "pg_stats", "osd_stats", "fwd")
