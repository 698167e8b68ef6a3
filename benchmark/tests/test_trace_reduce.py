"""The trace reduction, on interval arithmetic and on a small trace
recorded here on the CPU (whose XLA ops stand in for a device plane)."""

import threading
import time

import pytest

from benchmark import trace_reduce as tr


def test_union_merges_overlaps_and_drops_empties():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)]) == \
        [(0, 3), (5, 9)]


def test_peak_table_refuses_an_unknown_device():
    assert tr.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tr.peak("cpu")


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)

    def waiter():
        with TraceAnnotation("client_wait"):
            time.sleep(0.2)

    t = threading.Thread(target=waiter)
    t.start()
    for _ in range(3):
        with TraceAnnotation("client_submit"):
            f(x).block_until_ready()
        time.sleep(0.02)
    t.join()
    jax.profiler.stop_trace()
    return tr.find_xplane(d)


def test_cpu_trace_reduces_to_busy_idle_ops_and_gaps(cpu_trace):
    out = tr.reduce(cpu_trace,
                    device_planes=lambda n: n.startswith("/host:CPU"))
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_pct"] < 100
    assert out["device_events"] >= 3
    names = [n for n, _t in out["device_ops"]]
    assert any("dot" in n for n in names)
    assert sum(t for _n, t in out["device_ops"]) >= out["busy_s"] * 0.999
    gaps = out["idle_gaps"]
    assert gaps and all(t > 0 for _n, t in gaps)
    assert [t for _n, t in gaps] == sorted((t for _n, t in gaps),
                                           reverse=True)
    assert any(n.startswith("client_wait@") for n, _t in gaps)
    assert sum(t for _n, t in gaps) <= out["window_s"] - out["busy_s"] \
        + 1e-9


def test_a_trace_without_device_ops_is_refused(cpu_trace):
    with pytest.raises(ValueError):
        tr.reduce(cpu_trace, device_planes=lambda n: False)
