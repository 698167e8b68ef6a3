"""Object store WAL append (ms): a queued transaction's mean time from
``queue_transaction`` entry to its WAL record written and flushed,
before the apply (the store lock's wait, the record's encode and CRC,
the write), the window delta of every OSD's ``wal_append_lat``."""

from benchmark.osd_counters import mean_ms


def read(run):
    return mean_ms(run, "wal_append_lat")
