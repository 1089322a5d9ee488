"""The timed path broken underneath, one fault at a time: each must turn
the cell's ``correct`` false, through the check named beside it."""

import json
import os
import subprocess
import sys

import pytest

import bench_rehearsal

W4M, P64 = "rados_r6_82.write_4m", "rgw_r6_82.put_64m"


@pytest.mark.parametrize("cell,fault,kind", [
    # state returned unchanged
    (W4M, "store_unchanged", "reads_wrong"),
    (P64, "store_unchanged", "gets_wrong"),
    # half the encode left out
    (W4M, "half_batch", "shards_wrong"),
    (P64, "half_batch", "shards_wrong"),
    # an answer altered where it is made
    (W4M, "parity_flipped", "shards_wrong"),
    (P64, "parity_flipped", "shards_wrong"),
])
def test_fault_is_not_correct(cell, fault, kind):
    out = bench_rehearsal.run(cell, fault=fault)
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0
    assert out["diag"]["mismatches_by_kind"][kind] > 0


@pytest.mark.parametrize("cell", [W4M, P64])
def test_window_off_the_device_is_not_correct(cell):
    """Every dispatch fails and the host serves the window: the bytes are
    right, and the device path's checks see it.  In a process of its
    own: the tripped breakers and the plan's host-fallback count are
    process-wide, and later tests in a worker read them."""
    src = ("import json, sys; sys.path.insert(0, 'tests/benchmark'); "
           "import bench_rehearsal; "
           f"out = bench_rehearsal.run({cell!r}, fault='device_fail'); "
           "print(json.dumps([out['correct'], out['checks']]))")
    r = subprocess.run([sys.executable, "-c", src],
                       cwd=bench_rehearsal.ROOT, capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    correct, checks = json.loads(r.stdout.strip().splitlines()[-1])
    assert correct is False
    assert checks["mismatches"]["value"] == 0
    assert checks["device_faults"]["value"] > 0
    assert checks["executor_idle"]["value"] == 1
