"""The whole command, rehearsed on the CPU at toy sizes: each cell runs
through the same ``run_cell`` the chip runs (``on_chip=False`` and the
toy overrides exist for these tests only), a cell added as new files
runs without an edit to any existing file, the faults and the control
come out ``correct: false``, and the command refuses to run without a
TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark.run import ROOT, load_cell, run_cell
from benchmark.tests.conftest import TOY
from benchmark.tests.faults import run_broken

SEED = 2**31 + 77
CHECKS = {"read_mismatch", "op_errors", "ops_lost", "readback_mismatch",
          "shard_mismatch", "crc_mismatch"}


def _run(cell, trace=False, seconds=3.0, root=ROOT, overrides=None):
    return run_cell(cell, SEED, seconds, trace, root=root, on_chip=False,
                    overrides=TOY[cell] if overrides is None else overrides,
                    t_start=time.monotonic())


@pytest.mark.parametrize("cell", sorted(TOY))
def test_cell_runs_and_verifies(cell):
    res = _run(cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == CHECKS
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   load_cell(cell)["end_to_end"]}
    assert {"client_MBps", "setup_s"} <= set(res["metrics"])
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


@pytest.mark.parametrize("cell", sorted(TOY))
def test_traced_run_reports_per_layer_metrics_and_breakdown(cell):
    res = _run(cell, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in
                                   load_cell(cell)["per_layer"]}
    assert {"op_p50_ms", "client_op_p95_ms", "osd_op_ms",
            "launches_per_op", "device_busy_us_per_MB",
            "device_idle_pct"} <= set(res["metrics"])
    dev = res["device"]
    assert 0 < dev["busy_s"] < dev["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10
    assert list(res)[-1] == "checks"


# (fault, cell, a check it must trip)
BROKEN = [
    ("control", "ec83_write_4m", "shard_mismatch"),
    ("control", "rbd_ec42_randrw_4k", "shard_mismatch"),
    ("state_unchanged", "ec83_write_4m", "readback_mismatch"),
    ("state_unchanged", "rbd_ec42_randrw_4k", "op_errors"),
    ("half_batch", "ec83_write_4m", "shard_mismatch"),
    ("half_batch", "rbd_ec42_randrw_4k", "shard_mismatch"),
    ("altered_parity", "ec83_write_4m", "shard_mismatch"),
    ("altered_parity", "rbd_ec42_randrw_4k", "shard_mismatch"),
    ("altered_read", "rbd_ec42_randrw_4k", "read_mismatch"),
]


@pytest.mark.parametrize("fault,cell,check", BROKEN)
def test_broken_path_is_not_correct(fault, cell, check):
    res = run_broken(fault, cell, SEED, 3.0, on_chip=False,
                     overrides=TOY[cell])
    assert not res["correct"]
    value, limit = res["checks"][check]
    assert value > limit, res["checks"]


def test_a_cell_added_as_files_runs(tmp_path):
    """A new deployment, traffic mix (an open loop) and per-layer metric:
    new files and new BENCHMARK.json entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = json.loads((root / "benchmark/configs/ec83_4m_rados.json")
                     .read_text())
    cfg.update(osds=4, object_size=32768,
               pool={"type": "erasure", "pg_num": 8,
                     "profile": {"plugin": "jerasure", "k": 3, "m": 1,
                                 "technique": "reed_sol_van"}})
    (root / "benchmark/configs/toy_ec31.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/mixes/toy_rw.json").write_text(json.dumps({
        "surface": "rados", "loop": "open", "rate": 40.0,
        "arrivals": "poisson", "depth": 3,
        "ops": {"write_full": 1, "read": 1}, "op_bytes": 32768,
        "keys": {"dist": "uniform"}, "working_set": 6, "prefill": True,
        "warmup_ops": 4, "fault": None, "verify_sample": 3}))
    (root / "benchmark/metrics/reads_per_s.py").write_text(
        "def read(run):\n"
        "    n = sum(r.kind == 'read' and r.status == 'ok'\n"
        "            for r in run.records)\n"
        "    return n / (run.t_end - run.t0)\n")
    bench["configs"].append({"name": "toy_ec31", "source": "toy",
                             "file": "benchmark/configs/toy_ec31.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toy_cell", "config": "toy_ec31",
                               "traffic": "toy_rw", "chips": 1,
                               "why": "toy"})
    bench["per_layer"].append({"name": "reads_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "client", "moves": "client_MBps",
                               "workloads": ["toy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = _run("toy_cell", trace=True, root=str(root), overrides={})
    assert res["correct"], res["checks"]
    assert res["metrics"]["reads_per_s"]["value"] > 0
    res = _run("toy_cell", root=str(root), overrides={})
    assert res["correct"] and "client_MBps" in res["metrics"]


def test_degraded_read_mix_kills_a_shard_holder():
    """The mix's ``fault``: an OSD down before the window, reads served
    through the reconstruct lane, the dead OSD's shards left unchecked."""
    res = _run("ec83_write_4m", overrides=_merge_toy("ec83_write_4m", {
        # k=2 m=2 on 4 OSDs: one down leaves the PGs above min_size
        "config": {"osds": 4, "pool": {"profile": {"m": 2}}},
        "mix": {"ops": {"read": 1, "write_full": 0}, "prefill": True,
                "keys": {"dist": "uniform"},
                "fault": {"kill_osd_holding_shard": 0}}}))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _merge_toy(cell, extra):
    from benchmark.run import _merge
    return {k: _merge(TOY[cell].get(k, {}), extra.get(k))
            for k in ("config", "mix")}


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ec83_write_4m", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_command_refuses_to_run_outside_the_repo(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ec83_write_4m", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout == ""
