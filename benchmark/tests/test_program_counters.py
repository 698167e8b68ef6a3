"""The per-layer metrics that read the program's own counters (the
OSD's op stages, its messenger, resends): a toy traced cell reports each
as a finite number, and on a counter dump that lacks them, as a program
without those counters gives, each reader returns None."""

import math
import time

import pytest

from benchmark.run import ROOT, RunData, read_metric, run_cell
from benchmark.tests.conftest import TOY

SEED = 2**31 + 91
COUNTER_METRICS = ("osd_queue_wait_ms", "osd_subop_commit_ms",
                   "osd_encode_wait_ms", "osd_msgr_ms_per_op",
                   "osd_recv_bytes_per_client_byte", "resends_per_op")


def test_toy_traced_cell_reports_each_counter_metric():
    res = run_cell("ec83_write_4m", SEED, 3.0, True, root=ROOT,
                   on_chip=False, overrides=TOY["ec83_write_4m"],
                   t_start=time.monotonic())
    assert res["correct"], res["checks"]
    for name in COUNTER_METRICS:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    # every write is acknowledged after its sub-writes commit, and the
    # OSDs receive at least each write's payload once, as hex
    assert res["metrics"]["osd_subop_commit_ms"]["value"] > 0
    assert res["metrics"]["osd_recv_bytes_per_client_byte"]["value"] > 2


class _Rec:
    status, nbytes, t_done = "ok", 4096, 1.0


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_reader_returns_none_without_the_counters(name):
    def dump(n):
        return {"osd.0": {"op": n, "op_latency": {"avgcount": n,
                                                   "sum": 0.1 * n}}}
    run = RunData(records=[_Rec()] * 4, t0=0.0, t_end=2.0,
                  before={"perf": [dump(1)], "engine": [{}]},
                  after={"perf": [dump(5)], "engine": [{}]})
    assert read_metric(ROOT, name, run) is None
