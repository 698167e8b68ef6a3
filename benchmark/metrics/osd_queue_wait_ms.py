"""OSD queue wait (ms): a client op's mean time from the messenger's
hand-over to ``reached_pg`` (the op queue, then the daemon lock), the
window delta of every OSD's ``op_before_dequeue_op_lat``."""

from benchmark.osd_counters import mean_ms


def read(run):
    return mean_ms(run, "op_before_dequeue_op_lat")
