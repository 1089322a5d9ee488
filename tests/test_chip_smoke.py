"""chip_smoke.py off the chip: the CPU rehearsal runs every phase at tiny
size and ends in a parseable line naming the CPU; without the option, a
machine with no TPU gets a non-zero exit and no ok line."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_cpu_rehearsal_in_process(capsys):
    assert chip_smoke.main(["--cpu-rehearsal"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1
    phases = {json.loads(ln)["phase"]: json.loads(ln)
              for ln in lines[:-1]}
    assert phases["read"]["objects_verified"] == \
        chip_smoke.REHEARSAL.objects
    assert phases["degraded_read"]["objects_verified"] == \
        chip_smoke.REHEARSAL.degraded
    assert phases["scrub"]["errors"] == 0
    assert phases["placement"]["diffs"] == 0
    assert phases["placement"]["tier"] == "device"
    assert phases["tiers"]["plan_dispatches_by_executor"][
        "pallas_words+crc"] > 0


def test_no_tpu_exits_nonzero_without_ok_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=120, cwd=str(tmp_path),
                       env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("env_dir", [None, "outside"])
def test_compile_cache_placed_from_outside(tmp_path, env_dir):
    """The entry points' one cache helper: the caller's
    JAX_COMPILATION_CACHE_DIR wins untouched, else <checkout>/.jax_cache;
    either way every compile is kept and the checkout's path is cut from
    source locations (the key must not depend on it).  Run in a child
    so this test process keeps its own jax config."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    src = ("from ceph_tpu.common import jaxcache; import jax; "
           "print(jaxcache.enable()); "
           "print(jax.config.jax_compilation_cache_dir); "
           "print(jax.config.jax_persistent_cache_min_compile_time_secs); "
           "print(jax.config.jax_hlo_source_file_canonicalization_regex)")
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=120, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [want, want, "0",
                                "^" + re.escape(REPO + os.sep)]
