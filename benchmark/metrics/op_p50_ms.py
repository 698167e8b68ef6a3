"""Median client op latency (ms) over every op the window submitted:
the client layer's typical op, beside the tail ``client_op_p95_ms``."""


def read(run):
    return run.latency_quantile_ms(0.50)
