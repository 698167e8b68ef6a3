"""Seconds from process start to the window's first op: imports,
cluster up, pool clean, prefill, warm-up and compiles."""


def read(run):
    return run.setup_s
