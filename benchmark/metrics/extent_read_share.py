"""Client reads served from shard extents, per client read: the window
delta of every OSD's ``op_r_extent`` (EC reads that fetched only the
shard byte ranges their extent touches) over its ``op_r``.  None on a
program without the counter, or with no read in the window."""

from benchmark.osd_counters import delta


def read(run):
    extent, reads = delta(run, "op_r_extent"), delta(run, "op_r")
    return None if extent is None or not reads else extent / reads
