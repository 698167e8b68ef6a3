"""Objecter resends per client op: the window delta of every OSD's
``op_resent`` (client ops that arrived with ``retry_attempt`` > 0),
over the client ops completed in the window."""

from benchmark.osd_counters import client_ok, delta


def read(run):
    resent = delta(run, "op_resent")
    ops = len(client_ok(run))
    return None if resent is None or not ops else resent / ops
