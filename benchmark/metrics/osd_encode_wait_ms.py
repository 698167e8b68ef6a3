"""Batch engine wait (ms): a client write's mean time from
``encode_submitted`` to ``encoded`` (engine queue, flush deadline,
device, completion thread, the daemon lock again), the window delta of
every OSD's ``op_encode_lat``."""

from benchmark.osd_counters import mean_ms


def read(run):
    return mean_ms(run, "op_encode_lat")
