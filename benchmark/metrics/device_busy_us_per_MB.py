"""Device busy time (us, union of device-op intervals in the profiler
trace) per client MiB completed while the trace ran."""


def read(run):
    if run.trace is None:
        return None
    mib = run.traced_bytes() / 2**20
    return 1e6 * run.trace["busy_s"] / mib if mib else None
