"""WAL bytes the OSDs appended per client byte (B/B): the window delta
of every OSD's ``wal_wr_bytes`` (framed records: every shard's
transaction, its PG log and metadata), over the bytes of the client ops
completed in the window."""

from benchmark.osd_counters import client_ok, delta


def read(run):
    got = delta(run, "wal_wr_bytes")
    nbytes = sum(r.nbytes for r in client_ok(run))
    return None if got is None or not nbytes else got / nbytes
