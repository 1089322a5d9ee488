"""Test config: run JAX on a virtual 8-device CPU mesh.

Real-TPU behavior is validated by bench.py and the driver's
__graft_entry__.py compile checks; unit tests must be hermetic and fast, so
they force the CPU backend with 8 virtual devices to exercise the same
sharding code paths the multi-chip mesh uses.

Pallas kernels run in interpret mode on this tier (their tests flip
FORCE_INTERPRET); the same kernels compile for a described v5e in
tests/test_tpu_compile.py, and run on the chip through chip_smoke.py.
jax may already be imported when this file runs, so the platform is
pinned through jax.config as well as JAX_PLATFORMS.
"""

import os

# Arm the runtime lock-order detector for the whole tier (the
# WITH_TSAN-style discipline: detection tooling on in CI, off in
# production).  Set BEFORE ceph_tpu.common.lockdep is imported — it
# reads the env at import time — and mirrored onto the module flag in
# case a plugin already pulled it in.
os.environ.setdefault("CEPH_TPU_LOCKDEP", "1")
import sys  # noqa: E402

if "ceph_tpu.common.lockdep" in sys.modules:
    sys.modules["ceph_tpu.common.lockdep"].enabled = (
        os.environ["CEPH_TPU_LOCKDEP"] == "1")

# Arm the deterministic-interleaving explorer for the WHOLE tier when
# CEPH_TPU_INTERLEAVE=1 (lockdep's schedule twin: every event loop any
# test creates permutes ready-task wakeup order with a seeded PRNG, so
# the entire suite runs under an adversarial-but-replayable schedule).
# Off by default; tests/test_static_analysis.py drives cluster
# scenarios under it explicitly via interleave.explore(seed).
from ceph_tpu.analysis import interleave  # noqa: E402

interleave.install_if_enabled()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process/thrash tier")


# True when the device fault-injection seam is scripted for the whole
# run (the degraded-mode acceptance tier: CEPH_TPU_INJECT_DEVICE_FAIL
# forces dispatches to fail).  Bit-exactness tests must PASS via the
# host fallback in that mode; tests that assert live device-dispatch
# COUNTERS (plans compiled, batches folded, retraces bounded) mark
# themselves skipif(DEVICE_INJECTION) — their subject is definitionally
# absent while every dispatch is scripted to fail.
DEVICE_INJECTION = os.environ.get(
    "CEPH_TPU_INJECT_DEVICE_FAIL", "") not in ("", "0")


flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

try:
    import jax  # noqa: E402
except ImportError:  # jax-free env: ops fall back to numpy, jax tests skip
    pass
else:
    jax.config.update("jax_platforms", "cpu")
