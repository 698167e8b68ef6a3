"""The benchmark's own tests run on the CPU at toy sizes: the chip path
is the benchmark command itself."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session", autouse=True)
def _export_cache_outside_checkout(tmp_path_factory):
    """Keep the program's jax.export cache out of the checkout."""
    os.environ.setdefault("CEPH_TPU_CACHE_DIR",
                          str(tmp_path_factory.mktemp("export_cache")))

# toy sizes of each cell: 3 OSDs, k=2 m=1, 8 PGs, 64 KiB objects
TOY = {
    "ec83_write_4m": {
        "config": {"osds": 3, "object_size": 65536,
                   "pool": {"pg_num": 8, "profile": {"k": 2, "m": 1}}},
        "mix": {"depth": 4, "working_set": 16, "warmup_ops": 4,
                "verify_sample": 4}},
    "rbd_ec42_randrw_4k": {
        "config": {"osds": 3, "object_size": 65536,
                   "pool": {"pg_num": 8, "profile": {"k": 2, "m": 1}},
                   "image": {"size": 1 << 20}},
        "mix": {"depth": 4, "warmup_ops": 8, "verify_sample": 4}},
}
