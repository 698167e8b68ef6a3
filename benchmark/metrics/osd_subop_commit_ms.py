"""OSD sub-op commit wait (ms): a client write's mean time from
``sub_op_sent`` to the last peer's ``sub_op_commit_rec`` (the fan-out,
the peers' WAL commit, the replies), the window delta of every OSD's
``op_subop_commit_lat``."""

from benchmark.osd_counters import mean_ms


def read(run):
    return mean_ms(run, "op_subop_commit_lat")
