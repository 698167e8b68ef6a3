"""Messenger — the distributed communication backend (L2).

Reference: ``src/msg/``, ``src/msg/async/`` (SURVEY.md §3.2).  The
reference's AsyncMessenger is N epoll worker threads; here one asyncio
event loop per Messenger carries all connections (the GIL makes extra
loops pure overhead), with the same externally visible contract:
per-connection ordered delivery, typed messages, authenticated and
CRC-protected frames, reconnect with session resume, fault injection.

It carries the control plane (maps, peering, heartbeats) and the
served data path: client ops, EC sub-writes and sub-reads, recovery
pushes, their payload bytes as raw data segments after each message's
JSON front (``message.JsonMessage``).  Chunk movement between the TPU
shards of one process is XLA collectives over ICI
(``ceph_tpu.parallel``).
"""

from .fault import FaultInjector, FaultRule  # noqa: F401
from .message import (MSG_REGISTRY, JsonMessage, Message,  # noqa: F401
                      MGenericPing, MGenericReply, register_message)
from .messenger import (Connection, Dispatcher, EntityAddr,  # noqa: F401
                        Messenger)
