"""95th percentile of the latency of every op the window submitted,
those still out at the close included (waited for), in ms: the client
layer's tail, beside its typical op ``op_p50_ms``."""


def read(run):
    return run.latency_quantile_ms(0.95)
