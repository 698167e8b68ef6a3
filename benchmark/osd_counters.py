"""Window deltas of the OSDs' own ``perf dump`` counters, summed over
every OSD, for the per-layer metrics that read them.  A counter the
program does not have reads None, never zero."""

from __future__ import annotations


def delta(run, name: str):
    """The counter's change over the window, summed over the OSDs:
    ``(seconds, count)`` for a time average, a number otherwise; None
    when some OSD's dump has no such counter."""
    total = None
    for before, after in zip(run.before["perf"], run.after["perf"]):
        (b,), (a,) = before.values(), after.values()
        if name not in a or name not in b:
            return None
        if isinstance(a[name], dict):
            d = (a[name]["sum"] - b[name]["sum"],
                 a[name]["avgcount"] - b[name]["avgcount"])
            total = d if total is None else (total[0] + d[0],
                                             total[1] + d[1])
        else:
            total = (total or 0) + a[name] - b[name]
    return total


def mean_ms(run, name: str) -> float | None:
    """A time average's window mean, in ms."""
    d = delta(run, name)
    return 1000.0 * d[0] / d[1] if d and d[1] else None


def client_ok(run) -> list:
    """The ok op records that completed inside the window."""
    return [r for r in run.records
            if r.status == "ok" and r.t_done <= run.t_end]
