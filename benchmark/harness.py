"""One run of one cell: bring the cluster up, drive it, measure, check.

Everything that belongs to one configuration, one traffic mix, one
client kind or one per-layer metric lives in a file of its own, found by
the name that BENCHMARK.json gives:

* ``BENCHMARK.json`` ``configs[].file`` -- the deployment (configs/);
* ``traffic/<traffic>.json`` -- the mix, whose ``client`` names
  ``drivers/<client>.py``;
* ``metrics/<metric>.py`` -- ``read(w)`` of one per-layer metric from the
  window snapshot ``w`` (None where it finds nothing to read);
* ``faults/<name>.py`` -- a planted fault (``install()`` returns its undo),
  which the control and the fault tests use and the benchmark's own runs
  never do.

The end-to-end metrics are the harness's own, taken on the host clock
from the client's side: ``goodput_mibs``, ``op_p95_ms`` and ``setup_s``.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MiB = 1 << 20
DRAIN_S = 90.0          # wait for ops in flight at the close, then give up


class SpecError(Exception):
    pass


@dataclass
class Spec:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}") from e


def load_spec(workload: str, root: str = ROOT) -> Spec:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = next((c for c in bench["configs"]
                if c["name"] == cell["config"]), None)
    if cfg is None:
        raise SpecError(f"no config {cell['config']!r} in BENCHMARK.json")
    config = _read_json(os.path.join(root, cfg["file"]))
    traffic = _read_json(os.path.join(root, "benchmark", "traffic",
                                      cell["traffic"] + ".json"))

    def here(m: Dict[str, Any]) -> bool:
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if here(m) and m["moves"] in names]
    return Spec(cell, config, traffic, e2e, per_layer)


# ---------------------------------------------------------------------------
# Clocks and counters
# ---------------------------------------------------------------------------


class CompileClock:
    """JAX's own compile events, as chip_smoke.CompileClock sums them:
    tracing plus lowering, backend compile, and the persistent cache's
    hits and misses; ``lowerings`` counts programs lowered."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_lower_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }
    _EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.totals = dict.fromkeys(
            ("trace_lower_s", "backend_compile_s", "cache_hits",
             "cache_misses", "lowerings"), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self._DURATIONS:
            self.totals[self._DURATIONS[event]] += duration
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.totals["lowerings"] += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event in self._EVENTS:
            self.totals[self._EVENTS[event]] += 1

    def mark(self) -> Dict[str, float]:
        return dict(self.totals)


def device_summary() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks or [0]))


def counters(cluster, clock: CompileClock) -> Dict[str, Any]:
    """The program's cumulative counters that the window metrics read as
    differences: OSD op and stage self-seconds sums, the encode
    service's batching, the plan's dispatches by executor, compiles."""
    from ceph_tpu.common import circuit
    from ceph_tpu.ec import plan

    stage_s: Dict[str, float] = {}
    ops = 0
    enc = {"requests": 0, "batches": 0, "dispatch_s": 0.0}
    for osd in cluster.osds.values():
        ops += osd.op_tracker.perf()["ops_total"]
        for stage, h in list(osd.tracer.stage_hist.items()):
            stage_s[stage] = stage_s.get(stage, 0.0) + h.total
        for label, st in osd.encode_service.stats()["profiles"].items():
            if label.startswith("encode_hinfo"):
                enc["requests"] += st["requests"]
                enc["batches"] += st["batches"]
                enc["dispatch_s"] += st["dispatch_seconds"]
    ps = plan.stats()
    executors: Dict[str, int] = {}
    for row in ps["per_plan"].values():
        if "executor" in row:
            executors[row["executor"]] = executors.get(
                row["executor"], 0) + int(row["dispatches"])
    faults = {f: {c: st.get(c, 0) for c in
                  ("failures", "fallbacks", "watchdog_timeouts", "trips")}
              for f, st in circuit.stats_all().items()}
    return {"osd": {"ops": ops, "stage_s": stage_s}, "encode": enc,
            "executors": executors,
            "plan_host_fallbacks": ps["host_fallbacks"],
            "breaker": faults, "compile": clock.mark()}


def diff(after: Any, before: Any) -> Any:
    if isinstance(after, dict):
        return {k: diff(v, before.get(k, 0) if isinstance(before, dict)
                        else 0) for k, v in after.items()}
    return after - (before or 0)


# ---------------------------------------------------------------------------
# The closed loop's record
# ---------------------------------------------------------------------------


class Recorder:
    """Ops as the client saw them.  The window opens once ``warm_ops``
    ops have completed and lasts ``seconds``; an op counts when it ends
    inside the window, and ops still in flight at the close do not."""

    def __init__(self, seconds: float, warm_ops: int):
        self.seconds = seconds
        self.warm_ops = warm_ops
        self.completed = 0
        self.t_open: Optional[float] = None
        self.t_close = math.inf
        self.opened = asyncio.Event()
        self.stopping = False
        self.latencies: List[float] = []
        self.payload_bytes = 0
        self.attempted = 0
        self.failed = 0
        self.acked: List[Any] = []      # items the driver can check

    def in_window(self, t: float) -> bool:
        return self.t_open is not None and self.t_open <= t <= self.t_close

    def op(self, t0: float, t1: float, ok: bool) -> None:
        if self.in_window(t1):
            self.attempted += 1
            if ok:
                self.latencies.append(t1 - t0)
            else:
                self.failed += 1
        if ok:
            self.completed += 1
            if self.t_open is None and self.completed >= self.warm_ops:
                self.t_open = time.monotonic()
                self.t_close = self.t_open + self.seconds
                self.opened.set()

    def credit(self, t1: float, nbytes: int, item: Any) -> None:
        """Payload bytes whose op completed at t1: the goodput."""
        if self.in_window(t1):
            self.payload_bytes += nbytes
            self.acked.append(item)


def p95(samples: List[float]) -> float:
    """Exact 95th percentile (nearest rank) of the raw samples."""
    s = sorted(samples)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


class Payloads:
    """Object payloads from the seed: slices of one random pool at odd
    offsets, so every object's bytes differ."""

    STRIDE = 1_000_003

    def __init__(self, seed: int, size: int):
        self.size = size
        pool = max(64 * MiB, 2 * size)
        self.pool = np.random.default_rng([seed, 0]).integers(
            0, 256, pool, dtype=np.uint8).tobytes()
        self.span = pool - size

    def get(self, i: int) -> bytes:
        off = (i * self.STRIDE) % self.span
        return self.pool[off:off + self.size]


class Names:
    """Object names: every seed writes the same names, block by block,
    in its own order, so the seed changes no placement, only the order
    and the bytes."""

    def __init__(self, seed: int, fmt: str, block: int = 64):
        self.fmt = fmt
        self.block = block
        self.perm = np.random.default_rng([seed, 2]).permutation(block)

    def __call__(self, i: int) -> str:
        b = self.block
        return self.fmt.format(i - i % b + int(self.perm[i % b]))


@dataclass
class Ctx:
    """What a driver is handed."""
    spec: Spec
    seed: int
    cluster: Any
    rec: Recorder
    payloads: Payloads


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def rados_object_bytes(spec: Spec) -> int:
    """Bytes of each RADOS object the cell writes: the traffic's object,
    cut at the gateway's stripe size where a gateway stripes it."""
    size = int(spec.traffic["object_bytes"])
    gw = spec.config.get("gateway")
    return min(size, int(gw["rgw_obj_stripe_size"])) if gw else size


def warm_codec(config: Dict[str, Any], object_bytes: int) -> None:
    """Compile the fused encode+CRC plan at the stripe batches the
    encode service forms from this cell's objects (one to four objects
    a batch; the plan buckets stripes to powers of two).  Objects under
    the fuse floor never reach it, and nothing is compiled for them; nor
    for a configuration with no EC pool."""
    if "ec_profile" not in config:
        return
    from ceph_tpu.common import flags
    from ceph_tpu.ec.registry import create_erasure_code
    from ceph_tpu.osd import ec_util

    codec = create_erasure_code(dict(config["ec_profile"]))
    k = int(config["ec_profile"]["k"])
    unit = int(config["osd_config"]["osd_pool_erasure_code_stripe_unit"])
    floor = ec_util._fuse_min_bytes()
    if not codec.use_tpu or floor is None or object_bytes < floor:
        return
    stripes = -(-object_bytes // (k * unit))
    # a cold compile must not trip the dispatch watchdog's breaker
    prev = flags.peek("CEPH_TPU_DEVICE_TIMEOUT_S")
    flags.set_flag("CEPH_TPU_DEVICE_TIMEOUT_S", "1800")
    try:
        for n in (1, 2, 3, 4):
            data = np.zeros((stripes * n, k, unit), dtype=np.uint8)
            codec.encode_batch_with_crc(data)
    finally:
        if prev is None:
            flags.clear("CEPH_TPU_DEVICE_TIMEOUT_S")
        else:
            flags.set_flag("CEPH_TPU_DEVICE_TIMEOUT_S", prev)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


async def _trace_window(rec: Recorder, cluster, clock: CompileClock,
                        tmpdir: str, out: Dict[str, Any]) -> None:
    """Profile a few seconds in the middle of the window."""
    import jax

    await rec.opened.wait()
    length = min(5.0, rec.seconds / 2)
    await asyncio.sleep((rec.seconds - length) / 2)
    jax.profiler.start_trace(os.path.join(tmpdir, "trace"),
                             profiler_options=_profile_options())
    c0 = counters(cluster, clock)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        await asyncio.sleep(length)
    c1 = counters(cluster, clock)
    jax.profiler.stop_trace()
    out["counters"] = diff(c1, c0)


def _driver(spec: Spec):
    kind = spec.traffic["client"]
    return importlib.import_module(f"benchmark.drivers.{kind}")


def _metric(name: str):
    return importlib.import_module(f"benchmark.metrics.{name}")


async def _run(spec: Spec, seed: int, seconds: float, trace: bool,
               t_start: float, clock: CompileClock, tmpdir: str
               ) -> Dict[str, Any]:
    from benchmark import cluster as cluster_mod

    traffic = spec.traffic
    payloads = Payloads(seed, traffic.get("part_bytes",
                                          traffic["object_bytes"]))
    rec = Recorder(seconds, int(traffic["warm_ops"]))
    phases: Dict[str, float] = {"payloads": time.monotonic()}
    cl = await cluster_mod.start(spec.config)
    phases["cluster"] = time.monotonic()
    res: Dict[str, Any] = {"phases": phases}
    try:
        drv = _driver(spec).Driver(Ctx(spec, seed, cl, rec, payloads))
        await drv.start()
        phases["client"] = time.monotonic()
        loop_task = asyncio.create_task(drv.run())
        traced: Dict[str, Any] = {}
        trace_task = asyncio.create_task(
            _trace_window(rec, cl, clock, tmpdir, traced)) if trace \
            else None
        opened = asyncio.create_task(rec.opened.wait())
        await asyncio.wait({loop_task, opened},
                           return_when=asyncio.FIRST_COMPLETED)
        opened.cancel()
        if loop_task.done():
            loop_task.result()
            raise RuntimeError("the client loop ended before the window")
        c_open = counters(cl, clock)
        phases["warm_ops"] = rec.t_open
        res["setup_s"] = rec.t_open - t_start
        res["setup_compile"] = c_open["compile"]
        await asyncio.sleep(max(0.0, rec.t_close - time.monotonic()))
        c_close = counters(cl, clock)
        rec.stopping = True
        if trace_task is not None:
            await trace_task
        await asyncio.wait_for(loop_task, DRAIN_S)
        res["window"] = diff(c_close, c_open)
        res["traced"] = traced
        res["memory_peak_bytes"] = memory_peak_bytes()
        res["mismatches"] = await drv.check()
        await drv.stop()
    finally:
        await cluster_mod.stop(cl)
    res["rec"] = rec
    return res


def window_view(spec: Spec, res: Dict[str, Any],
                trace_summary: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The snapshot every per-layer metric reads."""
    from benchmark import check

    geo = None
    if "ec_profile" in spec.config:
        k, m, unit, _tech = check.geometry(spec.config)
        geo = {"k": k, "m": m, "chunk": unit,
               "object_stripes": -(-rados_object_bytes(spec) // (k * unit))}
    return {"window": res["window"],
            "traced": res["traced"].get("counters"),
            "trace": trace_summary,
            "device_kind": device_summary()["kind"],
            "geometry": geo}


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool,
             t_start: float, fault: Optional[str] = None
             ) -> Dict[str, Any]:
    """One run; returns the result line as a dict (last key: checks)."""
    from benchmark import check, trace as trace_mod

    clock = CompileClock()
    undo = None
    if fault:
        undo = importlib.import_module(
            f"benchmark.faults.{fault}").install()
    tmpdir = tempfile.mkdtemp(prefix="bench-")
    try:
        t_warm = time.monotonic()
        warm_codec(spec.config, rados_object_bytes(spec))
        t_codec = time.monotonic()
        res = asyncio.run(_run(spec, seed, seconds, trace, t_start, clock,
                               tmpdir))
        summary = None
        if trace:
            summary = trace_mod.reduce_dir(os.path.join(tmpdir, "trace"))
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
        if undo is not None:
            undo()
    rec = res["rec"]
    marks = {"imports": t_warm, "codec_warm_up": t_codec, **res["phases"]}
    prev, setup = t_start, {}
    for name, t in marks.items():
        setup[name] = round(t - prev, 3)
        prev = t
    checks = check.compared(res["mismatches"], res["window"],
                            spec.traffic.get("expect_executor"))
    out_diag = {"setup_s_by_phase": setup,
                "setup_compile": res["setup_compile"],
                "window": window_stats(rec),
                "mismatches_by_kind": res["mismatches"]}
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = {
            "goodput_mibs": rec.payload_bytes / MiB / rec.seconds,
            "op_p95_ms": p95(rec.latencies) * 1e3 if rec.latencies
            else None,
            "setup_s": res["setup_s"],
        }
        for m in spec.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        w = window_view(spec, res, summary)
        for m in spec.per_layer:
            v = _metric(m["name"]).read(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = device_summary()
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    out: Dict[str, Any] = {
        "correct": all(c["value"] <= c["limit"]
                       for c in checks.values()),
        "attempted": rec.attempted, "failed": rec.failed,
        "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = summary["breakdown"]
    out["path"] = path_report(res["window"])
    out["diag"] = out_diag
    out["checks"] = checks
    return out


def window_stats(rec: Recorder) -> Dict[str, Any]:
    """The window's latency spread and completions per second, for the
    reader of a run's stderr; no metric is taken from it."""
    s = sorted(rec.latencies)
    if not s:
        return {}

    def q(f):
        return round(s[max(0, math.ceil(f * len(s)) - 1)] * 1e3, 1)

    return {"ops": len(s), "p50_ms": q(0.5), "p90_ms": q(0.9),
            "p95_ms": q(0.95), "p99_ms": q(0.99), "max_ms": q(1.0)}


def path_report(window: Dict[str, Any]) -> Dict[str, Any]:
    """Which executors served the window, and any device fault: printed
    on a line of its own before the result.  ``correct`` judges them
    too (check.compared)."""
    bad = {f: {c: n for c, n in st.items() if n}
           for f, st in window["breaker"].items()
           if any(st.values())}
    return {"dispatches_by_executor": {
        ex: n for ex, n in window["executors"].items() if n},
        "plan_host_fallbacks": window["plan_host_fallbacks"],
        "breaker_faults": bad,
        "window_lowerings": window["compile"]["lowerings"]}


def emit(out: Dict[str, Any], stream=sys.stdout) -> None:
    """The path line, then the result as the last line of stdout, and
    each compared number beside its limit as the last lines of stderr."""
    path = out.pop("path")
    print(json.dumps(out.pop("diag")), file=sys.stderr, flush=True)
    print(json.dumps({"path": path}), file=stream, flush=True)
    print(json.dumps(out), file=stream, flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
