"""Message payload bytes the OSDs received per client byte (B/B): the
window delta of every OSD's ``msgr_recv_bytes``, over the bytes of the
client ops completed in the window (hex frames, sub-writes, shard
gathers and resends all count)."""

from benchmark.osd_counters import client_ok, delta


def read(run):
    got = delta(run, "msgr_recv_bytes")
    nbytes = sum(r.nbytes for r in client_ok(run))
    return None if got is None or not nbytes else got / nbytes
