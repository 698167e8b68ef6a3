"""OSD-side op latency (ms): the window's delta of every OSD's
``op_latency`` time-average (sum over count) from ``perf dump``."""


def read(run):
    s = n = 0.0
    for before, after in zip(run.before["perf"], run.after["perf"]):
        (b,), (a,) = before.values(), after.values()
        s += a["op_latency"]["sum"] - b["op_latency"]["sum"]
        n += a["op_latency"]["avgcount"] - b["op_latency"]["avgcount"]
    return 1000.0 * s / n if n else None
