"""chip_smoke.py off the chip: its phases at toy sizes on the CPU, and
its refusal to run (or to report) without a TPU.

The script's phase functions are the bring-up path the chip run drives
at full size; here each runs at a toy size with ``on_chip=False``
(which only skips the chip-only backend checks), so a change that
breaks the path fails tier-1 instead of a chip call.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke as cs

REPO = Path(__file__).resolve().parents[1]


def _run_script(cwd, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _reports_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok") is True:
                return True
        except (json.JSONDecodeError, AttributeError):
            continue
    return False


def test_refuses_cpu():
    """JAX_PLATFORMS=cpu: exit non-zero before any phase, no result."""
    p = _run_script(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert not _reports_ok(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    p = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu",
                               "PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _reports_ok(p.stdout)


def test_phase_ec_kernels_toy():
    r = cs.phase_ec_kernels(batch=4, stripe=8 * 1024, crc_rows=4,
                            crc_len=4096, on_chip=False)
    assert r["sampled_stripes"] == 3 and r["erasures"] == [0, 9]


def test_phase_crush_toy():
    r = cs.phase_crush(racks=2, hosts=4, per_host=4, pg_num=512,
                       sample=64, flat_osds=32, flat_sample=32,
                       on_chip=False)
    assert r["hierarchy"]["engine"] == "tpu-batched"
    assert r["hierarchy"]["shape"] == [512, 3]
    assert r["flat"]["pgs"] == 32 << 6


def test_phase_served_toy():
    """11 OSDs, k=8 m=3: writes, reads, degraded reads through the
    reconstruct lane, revive, deep scrub."""
    r = cs.phase_served(n_objects=8, obj_size=64 * 1024, writers=4,
                        pg_num=8, timeout=120, on_chip=False)
    assert r["scrub_errors"] == 0
    assert r["lanes"]["recon_launches_degraded"] > 0
    assert set(r["lanes"]["out_devices"]) >= {"encode", "recon"}


def test_phase_multichip_toy():
    """The --chips 4 phase on conftest's 8 virtual CPU devices."""
    import jax
    n = len(jax.devices())
    r = cs.phase_multichip(n_devices=n, stripes=2 * n, stripe=8 * 1024,
                           crc_rows=n, crc_len=4096, on_chip=False)
    assert r["write_launches"] == 1
    assert len(r["profiler_launches_per_device"]) == n


def test_phase_check_raises():
    with pytest.raises(cs.SmokeFailure, match="boom"):
        cs.check(False, "boom")
