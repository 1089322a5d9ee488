"""A cell's run at a tiny size on the CPU, Pallas interpreted: the same
harness, drivers, checks and faults as on the chip, minus the look for a
chip.  Shared by the rehearsal tests of this directory."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

TINY = {
    "rados": dict(object_bytes=64 << 10, concurrency=4, warm_ops=4,
                  check_sample=4),
    "s3": dict(object_bytes=256 << 10, part_bytes=128 << 10,
               concurrency=4, warm_ops=3, check_sample=2),
}
SEED = 2**31 + 12345          # the driver's seeds pass 32 signed bits


def run(workload: str, trace: bool = False, fault=None,
        seconds: float = 2.0) -> dict:
    from ceph_tpu.common import flags
    from ceph_tpu.ops import crc_pallas, gf_pallas

    spec = harness.load_spec(workload)
    # tiny: each number is capped, so a mix already under it keeps its own
    for key, cap in TINY[spec.traffic["client"]].items():
        spec.traffic[key] = min(int(spec.traffic[key]), cap)
    if spec.config.get("gateway"):
        spec.config["gateway"]["rgw_obj_stripe_size"] = 64 << 10
    saved = gf_pallas.FORCE_INTERPRET, crc_pallas.FORCE_INTERPRET
    gf_pallas.FORCE_INTERPRET = crc_pallas.FORCE_INTERPRET = True
    prev = flags.peek("CEPH_TPU_FUSE_MIN_BYTES")
    flags.set_flag("CEPH_TPU_FUSE_MIN_BYTES", "0")
    try:
        return harness.run_cell(spec, SEED, seconds, trace,
                                time.monotonic(), fault=fault)
    finally:
        gf_pallas.FORCE_INTERPRET, crc_pallas.FORCE_INTERPRET = saved
        if prev is None:
            flags.clear("CEPH_TPU_FUSE_MIN_BYTES")
        else:
            flags.set_flag("CEPH_TPU_FUSE_MIN_BYTES", prev)
