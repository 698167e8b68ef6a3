"""Share of the traced window (%) in which no operation ran on the
device: 100 x (1 - busy union / traced interval)."""


def read(run):
    return None if run.trace is None else run.trace["idle_pct"]
