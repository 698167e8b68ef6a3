"""The object store layer's metrics: every OSD's ``perf dump`` carries
the WAL's ``wal_append_lat`` and ``wal_wr_bytes``, one write moves them,
a toy traced write cell reports both metrics, and on a counter dump
that lacks them, as a program without those counters gives, each reader
returns None."""

import math
import time

import pytest

from benchmark.run import ROOT, RunData, read_metric, run_cell
from benchmark.tests.conftest import TOY

SEED = 2**31 + 26
WAL_METRICS = ("wal_append_ms", "wal_bytes_per_client_byte")


def test_every_osd_dump_has_the_wal_counters_and_one_write_moves_them():
    from ceph_tpu.core.admin_socket import admin_command
    from ceph_tpu.vstart import MiniCluster

    def dumps(c):
        return {i: admin_command(o.admin_socket.path, "perf dump")[
            f"osd.{i}"] for i, o in c.osds.items()}

    c = MiniCluster(n_mons=1, n_osds=3)
    c.start()
    try:
        r = c.rados()
        rc, outs, _ = r.mon_command({
            "prefix": "osd pool create", "pool": "rep", "pg_num": 4,
            "size": 3})
        assert rc == 0, outs
        c.wait_for_clean()
        before = dumps(c)
        data = b"w" * 65536
        r.open_ioctx("rep").write_full("obj", data)
        after = dumps(c)
    finally:
        c.stop()
    assert set(after) == {0, 1, 2}
    for i, a in after.items():
        b = before[i]
        assert a["wal_append_lat"]["avgcount"] > b["wal_append_lat"][
            "avgcount"]
        assert a["wal_append_lat"]["sum"] > b["wal_append_lat"]["sum"]
        # each replica's record holds the object's bytes raw, once
        assert len(data) < a["wal_wr_bytes"] - b["wal_wr_bytes"] \
            < 2 * len(data)


def test_toy_traced_write_cell_reports_the_wal_metrics():
    res = run_cell("ec83_write_4m", SEED, 3.0, True, root=ROOT,
                   on_chip=False, overrides=TOY["ec83_write_4m"],
                   t_start=time.monotonic())
    assert res["correct"], res["checks"]
    for name in WAL_METRICS:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)
    # k=2 m=1: three half-object shards per object, raw, plus metadata
    assert 1.5 < res["metrics"]["wal_bytes_per_client_byte"]["value"] < 2


class _Rec:
    status, nbytes, t_done = "ok", 4096, 1.0


@pytest.mark.parametrize("name", WAL_METRICS)
def test_reader_returns_none_without_the_counters(name):
    def dump(n):
        return {"osd.0": {"op": n, "op_latency": {"avgcount": n,
                                                   "sum": 0.1 * n}}}
    run = RunData(records=[_Rec()] * 4, t0=0.0, t_end=2.0,
                  before={"perf": [dump(1)], "engine": [{}]},
                  after={"perf": [dump(5)], "engine": [{}]})
    assert read_metric(ROOT, name, run) is None
