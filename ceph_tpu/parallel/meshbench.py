"""Multi-chip mesh bench + probe: the scale-out proof as one module.

Two entry points, shared by bench.py (the `_mesh_probe` pre-contract
check and the budget-gated `bench_mesh` sweep section), the multichip
driver tail (`__graft_entry__.dryrun_multichip`), and the test tier:

* ``probe_report()`` — correctness: the SAME stripe batch through the
  single-device plan, the N-device mesh plan, and the host numpy
  oracle must be bit-identical; then a scripted sick chip
  (``CEPH_TPU_INJECT_DEVICE_FAIL=sick=<id>``) must shrink the mesh —
  breaker tripped, survivors re-planned, output still bit-exact,
  ZERO host fallbacks.
* ``sweep_report(sizes)`` — throughput: the same fused encode+crc
  workload at mesh sizes 1 -> 2 -> 4 -> 8 (capped at the visible
  device count via CEPH_TPU_MESH_MAX_DEVICES), GiB/s of data bytes
  per size and the speedup over the single-chip leg.  On real
  multi-chip hardware near-linear scaling is the acceptance shape;
  on a single-core host with virtual devices the sweep still proves
  the plans compile and stay bit-exact at every size.
* ``multihost_report(processes)`` — the CROSS-HOST legs (PR-13
  tentpole proof): a ``--processes`` sweep axis spawning real
  ``jax.distributed`` process groups (each worker bootstraps through
  the ``parallel/multihost.py`` seam, devices split per process,
  hybrid DCN x ICI mesh) with bit-exactness vs the single-process
  leg and the host oracle; plus a HOST-LOSS shrink leg over the
  emulated 2-host topology — ``down_host=<H>`` injection must retire
  the host as ONE event (host:<id> breaker, no per-chip storm),
  re-plan on the survivor host in one shrink, zero host(CPU)
  fallbacks, ``fused-crc`` family still closed, output bit-exact.

CLI (``python -m ceph_tpu.parallel.meshbench
--probe|--sweep|--processes 1,2``) prints ONE JSON line — bench.py
runs it as a subprocess pinned to the CPU so the device-count
virtualization (XLA_FLAGS) can be applied before the backend
initializes (a chip serves one process, and bench.py holds it).  ``--worker`` is the internal
per-process entry the ``--processes`` driver spawns.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from typing import List, Optional

import numpy as np

from ceph_tpu.common import flags

_SWEEP_SIZES = (1, 2, 4, 8)


@contextlib.contextmanager
def _mesh_gates_open():
    """Hold the mesh byte gate open for the measurement, RESTORING it
    after: the dryrun driver tail runs these reports in-process, and
    a leaked CEPH_TPU_MESH_MIN_BYTES=0 would make every later tiny
    batch in that process mesh (the 1 MiB floor silently gone)."""
    prev = flags.peek("CEPH_TPU_MESH_MIN_BYTES")
    flags.setdefault("CEPH_TPU_MESH_MIN_BYTES", "0")
    try:
        yield
    finally:
        if prev is None:
            flags.clear("CEPH_TPU_MESH_MIN_BYTES")
        else:
            flags.set_flag("CEPH_TPU_MESH_MIN_BYTES", prev)


def ensure_devices(n: int = 8) -> int:
    """Make >= n devices visible when the platform allows it: real
    accelerator devices are used as-is; the CPU backend is virtualized
    via xla_force_host_platform_device_count (must run before the
    backend initializes — the reason bench.py subprocesses this
    module).  Returns the visible device count."""
    import re

    xla_flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  xla_flags)
    if m is None:
        xla_flags += f" --xla_force_host_platform_device_count={n}"
    elif int(m.group(1)) < n:
        xla_flags = (xla_flags[:m.start()] +
                     f"--xla_force_host_platform_device_count={n}" +
                     xla_flags[m.end():])
    os.environ["XLA_FLAGS"] = xla_flags.strip()

    import jax

    return len(jax.devices())


def _workload(smoke: bool):
    from ceph_tpu.models import reed_solomon as rs

    if smoke:
        k, m, chunk, batch = 4, 2, 16 * 1024, 32
    else:
        k, m, chunk, batch = 8, 3, 256 * 1024, 64
    rng = np.random.default_rng(929)
    data = rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
    return rs.reed_sol_van_matrix(k, m), data, m


def _host_oracle(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    from ceph_tpu.ops import gf

    return np.stack([gf.gf_matmul_host(matrix, data[i])
                     for i in range(data.shape[0])])


def _encode_crc(matrix, data, max_devices: int):
    """One fused encode+crc through the plan cache with the mesh
    capped at `max_devices` chips (0 = single-device plans only)."""
    from ceph_tpu.ec import plan

    prev = flags.peek("CEPH_TPU_MESH_MAX_DEVICES")
    prev_mesh = flags.peek("CEPH_TPU_MESH")
    try:
        if max_devices <= 1:
            flags.set_flag("CEPH_TPU_MESH", "0")
        else:
            flags.set_flag("CEPH_TPU_MESH", "1")
            flags.set_flag("CEPH_TPU_MESH_MAX_DEVICES",
                           str(max_devices))
        return plan.encode_with_crc(matrix, data, sig="meshbench")
    finally:
        for name, val in (("CEPH_TPU_MESH_MAX_DEVICES", prev),
                          ("CEPH_TPU_MESH", prev_mesh)):
            if val is None:
                flags.clear(name)
            else:
                flags.set_flag(name, val)


def probe_report(smoke: bool = True) -> dict:
    """The pre-contract mesh probe: bit-exactness across 1-device /
    N-device / host oracle, then the sick-chip shrink leg.  Raises on
    any violated invariant (the caller reports the probe failed)."""
    with _mesh_gates_open():
        return _probe_report(smoke)


def _probe_report(smoke: bool) -> dict:
    from ceph_tpu.common import circuit
    from ceph_tpu.ec import plan

    n = ensure_devices()
    matrix, data, m = _workload(smoke)
    oracle = _host_oracle(matrix, data)
    circuit.reset_all()
    plan.reset_stats()

    single = _encode_crc(matrix, data, 1)
    meshed = _encode_crc(matrix, data, n)
    bitexact = int(
        single is not None and meshed is not None
        and np.array_equal(single[0], oracle)
        and np.array_equal(meshed[0], oracle)
        and np.array_equal(single[1], meshed[1]))
    mesh_dispatches = plan.stats()["mesh_dispatches"]

    # sick-chip leg: the LAST device starts failing; the dispatch
    # must shrink the mesh (probe -> trip -> re-plan) and stay
    # bit-exact with ZERO host fallbacks.  Not applicable on a
    # single-device environment (no mesh to shrink).
    if n < 2:
        return {
            "devices": n,
            "bitexact": bitexact,
            "mesh_dispatches": mesh_dispatches,
            "sick_chip_shrunk": None,
            "host_fallbacks": plan.stats()["host_fallbacks"],
        }
    sick_chip_shrunk = 0
    host_fallbacks = -1
    prev_inject = flags.peek("CEPH_TPU_INJECT_DEVICE_FAIL")
    try:
        import jax

        sick_id = jax.devices()[-1].id
        flags.set_flag("CEPH_TPU_INJECT_DEVICE_FAIL",
                       f"sick={sick_id}")
        out = _encode_crc(matrix, data, n)
        st = plan.stats()
        host_fallbacks = st["host_fallbacks"]
        # NOTE: no healthy-list assertion — the device breaker's
        # full-jitter backoff is uniform from zero, so the chip may
        # legitimately read re-admittable within milliseconds (its
        # next dispatch is the half-open probe).  The invariants are:
        # the dispatch SUCCEEDED bit-exactly, a shrink happened, the
        # chip's breaker tripped, and nothing fell to host.
        sick_chip_shrunk = int(
            out is not None
            and np.array_equal(out[0], oracle)
            and st["mesh_shrinks"] >= 1
            and host_fallbacks == 0
            and circuit.device_breaker(sick_id).state == "open")
    finally:
        if prev_inject is None:
            flags.clear("CEPH_TPU_INJECT_DEVICE_FAIL")
        else:
            flags.set_flag("CEPH_TPU_INJECT_DEVICE_FAIL",
                           prev_inject)
        circuit.reset_all()
    return {
        "devices": n,
        "bitexact": bitexact,
        "mesh_dispatches": mesh_dispatches,
        "sick_chip_shrunk": sick_chip_shrunk,
        "host_fallbacks": host_fallbacks,
    }


def sweep_report(sizes: Optional[List[int]] = None,
                 smoke: bool = True, iters: int = 3) -> dict:
    """GiB/s of data bytes per mesh size, best-of-`iters` after a
    compile/warm pass, bit-exactness asserted at every size against
    the single-chip leg's parity."""
    with _mesh_gates_open():
        return _sweep_report(sizes, smoke, iters)


def _sweep_report(sizes: Optional[List[int]], smoke: bool,
                  iters: int) -> dict:
    n = ensure_devices()
    matrix, data, m = _workload(smoke)
    nbytes = data.nbytes
    sizes = [s for s in (sizes or _SWEEP_SIZES) if s <= n]
    rows = []
    base_out = None
    base_gibs = None
    for size in sizes:
        out = _encode_crc(matrix, data, size)  # compile + warm
        if out is None:
            rows.append({"devices": size, "gibs": None,
                         "speedup_x": None})
            continue
        if base_out is None:
            base_out = out
        else:
            assert np.array_equal(out[0], base_out[0]), \
                f"mesh size {size} parity != single-chip parity"
            assert np.array_equal(out[1], base_out[1]), \
                f"mesh size {size} crcs != single-chip crcs"
        best = float("inf")
        for _ in range(max(iters, 1)):
            t0 = time.perf_counter()
            _encode_crc(matrix, data, size)
            best = min(best, time.perf_counter() - t0)
        gibs = nbytes / best / (1 << 30)
        if base_gibs is None:
            base_gibs = gibs
        rows.append({"devices": size, "gibs": round(gibs, 3),
                     "speedup_x": round(gibs / base_gibs, 2)
                     if base_gibs else None})
    speedups = [r["speedup_x"] for r in rows
                if r["speedup_x"] is not None]
    return {
        "mesh_sweep": rows,
        "mesh_devices_visible": n,
        "mesh_speedup_max_x": max(speedups) if speedups else None,
        "mesh_workload_bytes": nbytes,
        "mesh_smoke": bool(smoke),
    }


# ---------------------------------------------------------------------------
# Multi-host legs: real process groups + the emulated host-loss shrink
# ---------------------------------------------------------------------------


def host_loss_report(smoke: bool = True) -> dict:
    """The host-loss shrink leg, hermetic in one process: the
    EMULATED 2-host topology (CEPH_TPU_MULTIHOST_HOSTS=2 over the
    virtual devices) with ``down_host=1`` injection.  Losing the host
    must be ONE event — its ``host:<id>`` breaker trips once, every
    chip reads degraded through it with ZERO per-chip breaker trips —
    the dispatch re-plans on the survivor host in ONE shrink, nothing
    falls back to the host CPU path, the ``fused-crc`` family stays
    closed, and the output is bit-exact."""
    from ceph_tpu.common import circuit
    from ceph_tpu.ec import plan
    from ceph_tpu.parallel import multihost

    n = ensure_devices()
    if n < 2:
        return {"multihost_hosts": 1, "host_loss_shrunk": None}
    saved = {k: flags.peek(k) for k in
             ("CEPH_TPU_MULTIHOST_HOSTS",
              "CEPH_TPU_INJECT_DEVICE_FAIL")}
    flags.set_flag("CEPH_TPU_MULTIHOST_HOSTS", "2")
    matrix, data, m = _workload(smoke)
    oracle = _host_oracle(matrix, data)
    try:
        with _mesh_gates_open():
            circuit.reset_all()
            plan.reset_stats()
            clean = _encode_crc(matrix, data, n)
            flags.set_flag("CEPH_TPU_INJECT_DEVICE_FAIL",
                           "down_host=1")
            lost = _encode_crc(matrix, data, n)
            st = plan.stats()
            chip_trips = sum(
                1 for d in range(n)
                if circuit.device_breaker(d).state != circuit.CLOSED)
            return {
                "multihost_hosts": 2,
                "host_loss_bitexact": int(
                    clean is not None and lost is not None
                    and np.array_equal(clean[0], oracle)
                    and np.array_equal(lost[0], oracle)),
                "host_loss_shrunk": int(st["mesh_shrinks"] == 1),
                "host_retirements": st["host_retirements"],
                "host_loss_one_event": int(
                    st["host_retirements"] == 1 and chip_trips == 0),
                "host_loss_host_fallbacks": st["host_fallbacks"],
                "host_loss_fused_crc_closed": int(
                    circuit.breaker("fused-crc").state
                    == circuit.CLOSED),
            }
    finally:
        for k, v in saved.items():
            if v is None:
                flags.clear(k)
            else:
                flags.set_flag(k, v)
        circuit.reset_all()


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_process_group(nproc: int, smoke: bool,
                         timeout_s: float) -> Optional[dict]:
    """Spawn a real ``jax.distributed`` group of `nproc` CPU worker
    processes (2 virtual devices each) running the fused encode+crc
    workload over the hybrid DCN x ICI mesh; returns worker 0's JSON
    report or None.  `timeout_s` is ONE shared deadline for the whole
    group (not per worker), and every worker arms its own
    self-destruct at deadline+margin — if this driver is itself
    killed by an outer timeout, no grandchild stays wedged in a gloo
    collective forever."""
    import subprocess
    import sys as _sys

    port = _free_port()
    procs = []
    env_base = {k: v for k, v in os.environ.items()
                if k != "XLA_FLAGS"}
    for pid in range(nproc):
        env = dict(env_base)
        env.update({
            "CEPH_TPU_MULTIHOST_COORD": f"127.0.0.1:{port}",
            "CEPH_TPU_MULTIHOST_NPROC": str(nproc),
            "CEPH_TPU_MULTIHOST_PID": str(pid),
            "CEPH_TPU_MULTIHOST_LOCAL_DEVICES": "2",
            "CEPH_TPU_MESH_MIN_BYTES": "0",
            "JAX_PLATFORMS": "cpu",
            # orphan bound: the worker exits on its own even when
            # nothing is left alive to kill it
            "CEPH_TPU_MULTIHOST_WORKER_DEADLINE_S":
                str(timeout_s + 30.0),
        })
        if smoke:
            env["CEPH_TPU_BENCH_SMOKE"] = "1"
        procs.append(subprocess.Popen(
            [_sys.executable, "-m", "ceph_tpu.parallel.meshbench",
             "--worker"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env))
    outs = []
    deadline = time.monotonic() + timeout_s
    try:
        for p in procs:
            so, se = p.communicate(
                timeout=max(deadline - time.monotonic(), 0.1))
            outs.append((p.returncode, so, se))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        print(f"# multihost {nproc}-process group timed out",
              file=sys.stderr)
        return None
    for rc, so, se in outs:
        if rc != 0:
            print(f"# multihost worker failed rc={rc}:"
                  f" {se[-800:]}", file=sys.stderr)
            return None
    reports = []
    for _rc, so, _se in outs:
        lines = [ln for ln in so.strip().splitlines() if ln]
        try:
            reports.append(json.loads(lines[-1]) if lines else None)
        except json.JSONDecodeError:
            reports.append(None)
    rep = reports[0]
    if rep is None:
        return None
    # collective cross-check (armed via CEPH_TPU_COLLECTIVE_TRACE=1,
    # inherited by the workers): every process must observe the SAME
    # collective sequence — a divergent trace is the silent-wedge
    # class rules_spmd.py flags statically
    traces = [r.get("collective_trace") if r else None
              for r in reports]
    if all(t is not None for t in traces):
        rep = dict(rep)
        rep["spmd_trace"] = traces[0]
        rep["spmd_order_congruent"] = int(
            all(t == traces[0] for t in traces[1:]))
        rep.pop("collective_trace", None)
    return rep


def worker_report(smoke: bool = True, iters: int = 3) -> dict:
    """One process's leg of the ``--processes`` sweep: bootstrap the
    group through the multihost seam, run the shared workload through
    the plan cache's mesh path (hybrid mesh, pre-sharded global
    arrays, allgathered outputs), check bit-exactness against the
    host oracle every process computes locally."""
    from ceph_tpu.ec import plan
    from ceph_tpu.parallel import multihost

    deadline = flags.get("CEPH_TPU_MULTIHOST_WORKER_DEADLINE_S")
    if deadline:
        import threading

        # self-destruct: a worker orphaned mid-collective (its driver
        # killed by an outer timeout) must not outlive the round
        t = threading.Timer(float(deadline), lambda: os._exit(124))
        t.daemon = True
        t.start()
    if not multihost.bootstrap_from_env():
        ensure_devices()        # single-process leg in the driver
    import jax

    matrix, data, m = _workload(smoke)
    oracle = _host_oracle(matrix, data)
    n = len(jax.devices())
    with _mesh_gates_open():
        out = _encode_crc(matrix, data, n)  # compile + warm
        bitexact = int(out is not None
                       and np.array_equal(out[0], oracle))
        best = float("inf")
        for _ in range(max(iters, 1)):
            t0 = time.perf_counter()
            _encode_crc(matrix, data, n)
            best = min(best, time.perf_counter() - t0)
    st = plan.stats()
    rep = {
        "processes": multihost.process_count(),
        "process_index": multihost.process_index(),
        "devices": n,
        "hosts": multihost.host_count(),
        "bitexact": bitexact,
        "gibs": round(data.nbytes / best / (1 << 30), 3),
        "mesh_dispatches": st["mesh_dispatches"],
        "topology": list(multihost.topology_signature()) or None,
    }
    from ceph_tpu.analysis import interleave

    if interleave.collective_trace_armed():
        rep["collective_trace"] = [
            [r.path, r.line, r.op]
            for r in interleave.collective_records()]
    return rep


def multihost_report(processes: Optional[List[int]] = None,
                     smoke: bool = True) -> dict:
    """The ``--processes`` sweep axis + the host-loss shrink leg —
    the bench_multihost section's body and the `multihost` contract
    key's source."""
    counts = processes or [1, 2]
    # per-leg deadline: strictly below bench.py's subprocess timeouts
    # (probe 180 / sweep 300), so THIS driver always kills and reaps
    # its worker group before the outer timeout kills the driver
    timeout_s = flags.flag_float("CEPH_TPU_MULTIHOST_LEG_TIMEOUT_S")
    rows = []
    all_bitexact = 1
    for nproc in counts:
        if nproc <= 1:
            rep = worker_report(smoke=smoke)
            rep.pop("process_index", None)
        else:
            rep = _spawn_process_group(nproc, smoke, timeout_s)
        if rep is None:
            rows.append({"processes": nproc, "bitexact": None})
            all_bitexact = 0
            continue
        rep.pop("process_index", None)
        rows.append(rep)
        if not rep.get("bitexact"):
            all_bitexact = 0
    out = {
        "process_sweep": rows,
        "multihost_bitexact": all_bitexact,
        "processes_max": max(counts),
    }
    out.update(host_loss_report(smoke=smoke))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="meshbench")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sizes", type=str, default="")
    ap.add_argument("--processes", type=str, default="",
                    help="multihost sweep axis: process counts, e.g."
                    " 1,2")
    ap.add_argument("--worker", action="store_true",
                    help="internal: one process of a --processes"
                    " group")
    args = ap.parse_args(argv)
    smoke = args.smoke or flags.get("CEPH_TPU_BENCH_SMOKE") == "1"
    if args.worker:
        print(json.dumps(worker_report(smoke=smoke)), flush=True)
        return 0
    out = {}
    if args.probe or not (args.sweep or args.processes):
        out.update(probe_report(smoke=smoke))
    if args.sweep:
        sizes = [int(s) for s in args.sizes.split(",") if s] or None
        out.update(sweep_report(sizes=sizes, smoke=smoke))
    if args.processes:
        counts = [int(p) for p in args.processes.split(",") if p]
        out.update(multihost_report(processes=counts, smoke=smoke))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
