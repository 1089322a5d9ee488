"""ExecPlan cache: the compile-once, dispatch-few EC device path.

Every encode/decode request used to walk ec/dispatch.gf_matmul ->
jax.jit with its *exact* array shapes, so each new (k, m, chunk_bytes,
batch) combination paid a full XLA retrace, small stripes dispatched
one at a time, and parity + hinfo CRC were separate device round
trips.  The XOR-EC literature puts most of the win in this regime in
the scheduling/fusion around the kernel, not the kernel itself
(arXiv:2108.02692), and batched distributed-matmul work argues for
folding many small products into few large ones (arXiv:1804.10331) —
exactly the shape of the many-small-stripes OSD workload.  This module
is that layer:

* **ExecPlan cache** — compiled callables keyed by (codec signature,
  kind, bucketed shape).  A plan is built once (the retrace) and then
  served from the LRU for every request that lands in the same bucket.
* **Shape bucketing** — chunk_bytes rounds up to quarter-octave
  buckets (the next {4,5,6,7}/4 * 2^e multiple, >= 64) and the stripe
  batch to power-of-two buckets; inputs are zero-padded up and outputs
  sliced back down.  Zero columns produce zero parity columns and
  padded stripes are dropped, so padding is invisible to callers while
  real traffic collapses onto a handful of plans.
* **Fused encode + crc32c** — `encode_with_crc` returns parity AND the
  per-chunk (zero-seeded) hinfo crc32c from one dispatch instead of
  two (ECUtil::HashInfo's ledger rides the encode).
* **Mesh-sharded plans** — batches past the mesh gates
  (`CEPH_TPU_MESH_MIN_STRIPES` stripes, `CEPH_TPU_MESH_MIN_BYTES`
  bytes, >= 2 healthy chips) compile onto the LIVE HEALTHY device
  mesh instead of one chip: the plan key carries the device-set
  signature, the stripe batch shards data-parallel over the mesh
  ("stripe" -> dp; "shard" and "byte" stay within-chip — the logical
  axis rules in parallel/striped.py), inputs are device_put
  pre-sharded (SNIPPETS [3]) and parity + fused CRC never re-land on
  host between stages.  A failed mesh dispatch probes each
  participating chip individually (common/circuit.py ``device:<id>``
  breakers): a sick chip trips ITS breaker, the family verdict is
  absolved, and the dispatch re-plans on the surviving set — the mesh
  shrinks, the batch never degrades to host because one chip died.
  Kill switch CEPH_TPU_MESH=0 (bit-identical single-device plans).
* **Observability** — `stats()` exposes hit/miss/retrace counters,
  per-plan dispatch counts and the guarded calls' thread CPU against
  wall time (plus the mesh section: healthy set, dispatches, shrinks);
  bench.py and the erasure-code benchmark CLI surface them.  A guarded
  call made for a batched dispatch marks its stages on that dispatch's
  timeline (`tracing.Stages`): guard, launch, fetch, then the fold.

Direct `jax.jit` on shape-polymorphic EC entry points is flagged by
the `jit-bypass-plan` static-analysis rule; route new compiles through
`tracked_jit` (or a plan kind) so they stay observable and cached.
"""

from __future__ import annotations

import os
import re

from ceph_tpu.common import flags
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ceph_tpu.common import circuit, tracing
from ceph_tpu.ec import xsched
from ceph_tpu.ec.dispatch import LruCache
from ceph_tpu.ec.xsched import matrix_signature
from ceph_tpu.ops import checksum as cks
from ceph_tpu.ops import gf

try:  # plan building needs jax; the module stays importable without it
    import jax
    import jax.numpy as jnp

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

__all__ = [
    "bucket_batch", "bucket_bytes", "clear", "codec_signature",
    "compute_eval", "device_platform", "encode_with_crc", "matmul",
    "matrix_signature", "mesh_enabled", "mesh_dispatches",
    "mesh_info", "plan_key", "quarantine_info", "reset_stats",
    "stats", "tracked_jit",
]

# ---------------------------------------------------------------------------
# State: the plan cache and its counters
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_plans = LruCache(cap=128)
_mbits_cache = LruCache(cap=64)      # matrix signature -> device bit matrix
# device_call_cpu_s / _wall_s: the watchdog thread's CPU time
# (time.thread_time) and wall time over each guarded call's launch and
# fetch — their ratio is the share of the call the thread ran, not
# waiting on the device or the interpreter lock.  codec_compile_s /
# codec_compiles: the `codec_compile` stages, the lowering and compile of
# the fused plan's specialised kernel for a registered matrix
_counters: Dict[str, float] = {"hits": 0, "misses": 0, "retraces": 0,
                               "dispatches": 0, "host_fallbacks": 0,
                               "oom_splits": 0, "quarantines": 0,
                               "mesh_dispatches": 0, "mesh_rows": 0,
                               "mesh_shrinks": 0, "mesh_probes": 0,
                               "host_retirements": 0,
                               "device_call_cpu_s": 0.0,
                               "device_call_wall_s": 0.0,
                               "codec_compile_s": 0.0,
                               "codec_compiles": 0}
_per_plan: Dict[str, Dict[str, object]] = {}
# plan label -> the XOR network of its specialised kernel, written once
# as the plan is built; a property of the plan, so reset_stats keeps it
_networks: Dict[str, Dict[str, int]] = {}
# poisoned-plan quarantine: a compiled callable that keeps failing is
# evicted and its key blacklisted for a TTL (a single bad compile must
# not re-trip the breaker forever while healthy plans keep serving)
_quarantine: Dict[tuple, float] = {}         # key -> expiry (monotonic)
_plan_failures: Dict[tuple, int] = {}        # key -> consecutive fails


def stats() -> dict:
    """Snapshot of plan-cache observability counters.

    hits/misses count plan-cache lookups; retraces counts actual XLA
    traces (each is one compile); per_plan maps plan labels to
    dispatch counts and the executor that served them (e.g.
    ``pallas_words+crc``); a fused plan of a registered matrix adds its
    XOR network (``parity_rows``, ``xor_terms``) and its
    ``codec_compile_s``.  device_call_cpu_s / device_call_wall_s sum
    the guarded calls' launch + fetch on their own thread;
    codec_compile_s / codec_compiles the `codec_compile` stages.
    """
    with _lock:
        out = {
            **_counters,
            "plans": len(_plans),
            "quarantined_plans": len(_quarantine),
            "per_plan": {k: {**v, **_networks.get(k, {})}
                         for k, v in _per_plan.items()},
        }
    # breaker states + trip/probe/fallback counters ride the same
    # snapshot (the device_health admin command and bench read this)
    out["device_health"] = circuit.stats_all()
    # mesh policy + live healthy set (outside the lock: mesh_info
    # takes it itself)
    out["mesh"] = mesh_info()
    # the codec-compiler section (ec/xsched.py): schedules compiled,
    # memo hits, xors_naive vs xors_scheduled.  Its cache is keyed by
    # matrix signature, NOT plan key — plan rebuilds (mesh shrink,
    # quarantine, clear) never cost a recompilation
    out["xsched"] = xsched.stats()
    return out


def reset_stats() -> None:
    with _lock:
        for k in _counters:
            _counters[k] = 0
        _per_plan.clear()


def clear() -> None:
    """Drop every cached plan (tests; production never needs this)."""
    with _lock:
        _plans.clear()
        _networks.clear()
        _mbits_cache.clear()
        _quarantine.clear()
        _plan_failures.clear()


def _note_retrace(label: str) -> None:
    # called from inside traced wrappers: runs once per XLA trace
    with _lock:
        _counters["retraces"] += 1
        entry = _per_plan.setdefault(
            label, {"dispatches": 0, "retraces": 0})
        entry["retraces"] += 1


def _note_dispatch(label: str, executor: str) -> None:
    with _lock:
        _counters["dispatches"] += 1
        entry = _per_plan.setdefault(
            label, {"dispatches": 0, "retraces": 0})
        entry["executor"] = executor
        entry["dispatches"] += 1


def _note_codec_compile(label: str, secs: float) -> None:
    with _lock:
        _counters["codec_compile_s"] += secs
        _counters["codec_compiles"] += 1
        entry = _per_plan.setdefault(
            label, {"dispatches": 0, "retraces": 0})
        entry["codec_compile_s"] = \
            float(entry.get("codec_compile_s", 0.0)) + secs


def _note_device_call(cpu_s: float, wall_s: float) -> None:
    with _lock:
        _counters["device_call_cpu_s"] += cpu_s
        _counters["device_call_wall_s"] += wall_s


def tracked_jit(label: str, fn: Callable, **jit_kwargs):
    """jax.jit with plan-cache observability: the wrapper body runs at
    trace time only, so the retrace counter increments exactly once
    per XLA compile.  All EC-path compiles must route through here (or
    a plan kind) — the jit-bypass-plan lint rule enforces it.

    The jitted function is named after the plan's kind (the label's
    leading identifier, ``encode_crc`` for ``encode_crc[...] r2k8 ...``),
    which names its programs and their ops in a profiler trace."""

    def traced(*args, **kwargs):
        _note_retrace(label)
        return fn(*args, **kwargs)

    traced.__name__ = re.match(r"\w*", label).group(0) or "plan"
    return jax.jit(traced, **jit_kwargs)


# ---------------------------------------------------------------------------
# Bucketing policy
# ---------------------------------------------------------------------------

_MIN_BYTES_BUCKET = 64


def _round_up_quarter_octave(n: int) -> int:
    """Smallest value >= n of the form q * 2^(e-3), q in {5,6,7,8}:
    four buckets per octave, worst-case pad < 25%."""
    if n <= 4:
        return max(n, 1)
    e = (n - 1).bit_length()          # n in (2^(e-1), 2^e]
    step = 1 << max(e - 3, 0)
    return -(-n // step) * step


def bucket_bytes(s: int) -> int:
    """Bucket for the chunk-byte axis: quarter-octave, floor 64 (so
    every bucket is a multiple of 16 — divisible by the mesh sp axis
    and the 4-byte word layout)."""
    return _round_up_quarter_octave(max(int(s), _MIN_BYTES_BUCKET))


def bucket_batch(b: int) -> int:
    """Bucket for the stripe-batch axis: next power of two up to 512
    (ragged arrival batches collapse onto log-many plans), then the
    next multiple of 128 — a big one-shot object must not pad, encode
    and CRC up to 2x its stripes just to hit a power of two (waste is
    bounded < 25% above the cap, and batches that large amortize a
    compile anyway)."""
    b = max(int(b), 1)
    if b <= 512:
        return 1 << (b - 1).bit_length()
    return -(-b // 128) * 128


# ---------------------------------------------------------------------------
# Signatures and keys (stable across processes: plain ints + sha256 hex)
# ---------------------------------------------------------------------------


# matrix_signature is defined in ec/xsched.py (re-exported here
# unchanged): compiled XOR schedules and ExecPlans share ONE sha256
# identity per matrix, so a codec's signature keys both caches.


def codec_signature(technique: str, k: int, m: int, w: int,
                    matrix: np.ndarray) -> str:
    """The ErasureCodeIsaTableCache-style codec signature, hashed so
    it is stable across processes and restarts."""
    return matrix_signature(matrix, extra=f"{technique}/k{k}/m{m}/w{w}")


def plan_key(sig: str, kind: str, rows: int, k: int,
             batch: int, chunk_bytes: int,
             mesh: Tuple[int, ...] = (),
             proc: tuple = ()) -> tuple:
    """Cache key: (codec signature, kind, bucketed shape, mesh,
    process topology).  Pure strings/ints/bools — identical across
    processes for identical profiles (asserted by the key-stability
    test).  `mesh` is the participating device-id set for a
    mesh-sharded plan (a compiled executable binds its devices, so a
    plan built for a set containing a now-dead chip must miss); the
    batch bucket rounds up to a multiple of the mesh size so every
    chip gets whole stripes.  `proc` is the process topology
    (multihost.topology_signature(): process count + per-process
    device-set signature) so plans from different CLUSTER shapes —
    the same 8 chips as 1x8 vs 2x4 — never collide; () is the
    trivial single-host shape, keeping single-process keys
    bit-identical to the pre-multihost form."""
    bb = bucket_batch(batch)
    if mesh:
        bb = -(-bb // len(mesh)) * len(mesh)
    return (sig, kind, int(rows), int(k), bb,
            bucket_bytes(chunk_bytes) if kind not in
            ("encode_crc", "mesh_encode_crc")
            else int(chunk_bytes),
            tuple(int(d) for d in mesh), tuple(proc))


def _label(key: tuple) -> str:
    sig, kind, rows, k, bb, bs, mesh, proc = key
    return f"{kind}[{sig}] r{rows}k{k} B{bb} S{bs}" + \
        (f"+mesh{len(mesh)}" if mesh else "") + \
        (f"+hosts{proc[0]}" if proc else "")


# ---------------------------------------------------------------------------
# ExecPlan
# ---------------------------------------------------------------------------


class ExecPlan:
    """One compiled dispatch unit: a callable plus its dispatch stats.

    Mesh plans carry `sharding` (a NamedSharding over their device
    set) and `devices` (the participating chip ids, the device_call
    attribution set); single-device plans leave both None/().  `host`,
    when set, turns the fetched host arrays into the plan's result (a
    free view: the Pallas plan's words back to bytes), so calling the
    plan launches and nothing more."""

    __slots__ = ("key", "label", "fn", "executor", "sharding",
                 "devices", "host")

    def __init__(self, key: tuple, fn: Callable, executor: str,
                 sharding=None, devices: Tuple[int, ...] = (),
                 host: Optional[Callable] = None):
        self.key = key
        self.label = _label(key)
        self.fn = fn
        self.executor = executor
        self.sharding = sharding
        self.devices = devices
        self.host = host

    def __call__(self, *args):
        out = self.fn(*args)
        _note_dispatch(self.label, self.executor)
        return out


def _get_plan(key: tuple, build: Callable[[], ExecPlan]) -> ExecPlan:
    with _lock:
        hit = _plans.peek(key)
        if hit is not None:
            _counters["hits"] += 1
            return hit
        _counters["misses"] += 1
    plan = build()  # compile outside the lock (can take seconds)
    with _lock:
        _plans.put(key, plan)
    return plan


# ---------------------------------------------------------------------------
# Dispatch guard: breaker + watchdog + OOM splitting + plan quarantine
# ---------------------------------------------------------------------------


def _quarantine_ttl() -> float:
    try:
        return flags.flag_float("CEPH_TPU_PLAN_QUARANTINE_S")
    except ValueError:
        return 30.0


def _plan_fail_limit() -> int:
    try:
        return flags.flag_int("CEPH_TPU_PLAN_FAIL_LIMIT")
    except ValueError:
        return 3


def _quarantined(key: tuple) -> bool:
    """True while a poisoned plan key is blacklisted (callers take the
    host path without rebuilding the callable); an expired entry is
    released so the next request recompiles fresh."""
    with _lock:
        expiry = _quarantine.get(key)
        if expiry is None:
            return False
        if time.monotonic() >= expiry:
            del _quarantine[key]
            _plan_failures.pop(key, None)
            return False
        return True


def _note_plan_failure(key: tuple) -> None:
    """One more dispatch failure for this compiled callable; at the
    limit the plan is evicted from the cache and its key quarantined
    for the TTL (poisoned-plan quarantine)."""
    with _lock:
        n = _plan_failures.get(key, 0) + 1
        _plan_failures[key] = n
        if n >= _plan_fail_limit():
            _plans.pop(key, None)
            _quarantine[key] = time.monotonic() + _quarantine_ttl()
            _plan_failures.pop(key, None)
            _counters["quarantines"] += 1
            tracing.event(f"plan quarantined {_label(key)}")


def quarantine_info() -> dict:
    """Admin view of the poisoned-plan blacklist."""
    now = time.monotonic()
    with _lock:
        return {
            "ttl_s": _quarantine_ttl(),
            "fail_limit": _plan_fail_limit(),
            "entries": [
                {"plan": _label(k),
                 "expires_in_s": round(max(exp - now, 0.0), 3)}
                for k, exp in _quarantine.items()],
        }


def _materialize(out):
    """Force async XLA results to completion INSIDE the guarded body:
    jax dispatch returns placeholder arrays almost immediately, so a
    late runtime error (or a device that wedges mid-execution) would
    otherwise surface at the CALLER's np.asarray — outside the
    watchdog and the breaker's accounting."""
    if out is None:
        return None
    if isinstance(out, tuple):
        return tuple(_materialize(o) for o in out)
    return np.asarray(out)


def _guarded(family: str, key: tuple, plan: ExecPlan, args: tuple,
             batch: int, defer_verdict: bool = False
             ) -> Tuple[str, Optional[object]]:
    """One plan dispatch through the device_call choke point.  Returns
    ("ok", out), ("oom", None) — caller halves the batch — or
    ("fail", None) after recording breaker/quarantine state; callers
    translate "fail" into the bit-exact host path (return None).

    Mesh plans pass defer_verdict=True: a failure there is NOT yet a
    plan failure or a host fallback — the mesh layer first probes the
    participating chips and either shrinks the mesh (chip's fault,
    plan is fine) or falls through to the single-device plan (which
    owns its own accounting)."""

    # the batched dispatch this call serves, if any: the watchdog
    # thread inherits no context, so its stages are marked through
    # this reference
    stages = tracing.current_dispatch.get()

    def run():
        cpu0, wall0 = time.thread_time(), time.perf_counter()
        if stages is not None:
            stages.mark("dispatch_launch", annotated=True)
        # a plan's first launch may mark a stage of its own
        token = tracing.current_dispatch.set(stages)
        try:
            out = plan(*args)
            if stages is not None:
                stages.mark("dispatch_fetch", annotated=True)
            out = _materialize(out)
        finally:
            tracing.current_dispatch.reset(token)
            if stages is not None:
                # on this thread: it closes the annotation it opened
                stages.mark("dispatch_guard")
        _note_device_call(time.thread_time() - cpu0,
                          time.perf_counter() - wall0)
        return out if plan.host is None else plan.host(out)

    if stages is not None:
        # host work between two calls (an OOM halving) packs the next
        stages.mark("dispatch_guard",
                    rename=("dispatch_fold", "dispatch_pack"))
    status, out = circuit.device_call(
        family, run, batch=batch, label=plan.label,
        oom_to_fail=batch <= 1, devices=plan.devices or None)
    if stages is not None:
        stages.mark("dispatch_fold", annotated=True)
    if status == "ok":
        return "ok", out
    if status == "oom":
        with _lock:
            _counters["oom_splits"] += 1
        tracing.event(f"plan oom halving {plan.label}")
        return "oom", None
    if defer_verdict:
        # raw status up: "open" means no dispatch happened (nothing
        # to probe), "fail"/"timeout" mean the mesh layer attributes
        return status, None
    if status in ("fail", "timeout"):
        _note_plan_failure(key)
    with _lock:
        _counters["host_fallbacks"] += 1
    tracing.event(f"plan host fallback {plan.label}")
    return "fail", None


def device_platform() -> Optional[str]:
    """The jax backend platform ('tpu', 'cpu', ...), None when no
    backend initializes (callers gate device-only policies on this)."""
    if not (HAVE_JAX and gf.backend_available()):
        return None
    try:
        return jax.devices()[0].platform
    except Exception:  # pragma: no cover
        return None


def _mbits_for(matrix: np.ndarray):
    # keyed by matrix CONTENT, never by the caller's sig: a sig only
    # buys cache locality, correctness must not depend on callers
    # keeping it matrix-unique.  matrix_signature hashes the buffer
    # in place — the old (shape, tobytes()) key materialized a copy
    # of the matrix on every encode dispatch
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _mbits_cache.get_or_compute(
        matrix_signature(m),
        lambda: jnp.asarray(gf.gf_matrix_to_bits(m)))


def _pad_batch(arr: np.ndarray, bb: int, bs: int) -> np.ndarray:
    b, k, s = arr.shape
    if b == bb and s == bs:
        return arr
    return np.pad(arr, ((0, bb - b), (0, 0), (0, bs - s)))


# ---------------------------------------------------------------------------
# Mesh policy: when a batch rides the multi-chip mesh, and over which
# surviving devices
# ---------------------------------------------------------------------------


def mesh_enabled() -> bool:
    """Multi-chip mesh dispatch kill switch (CEPH_TPU_MESH=0 pins
    every plan to a single device — bit-identical output)."""
    return flags.enabled("CEPH_TPU_MESH")


def _mesh_min_bytes() -> int:
    """Batch-size floor (total data bytes) below which the mesh is
    not worth the fan-out; one chip's plan serves.  Default 1 MiB —
    the same altitude as the fused-CRC floor."""
    try:
        return flags.flag_int("CEPH_TPU_MESH_MIN_BYTES")
    except ValueError:
        return 1 << 20


def _mesh_min_stripes() -> int:
    try:
        return flags.flag_int("CEPH_TPU_MESH_MIN_STRIPES")
    except ValueError:
        return 2


def _mesh_max_devices() -> int:
    """0 = no cap; the bench mesh sweep sets this to measure 1, 2,
    4, 8-chip legs of the SAME workload."""
    try:
        return flags.flag_int("CEPH_TPU_MESH_MAX_DEVICES")
    except ValueError:
        return 0


def _topology() -> tuple:
    """The process-topology plan-key element (multihost seam); () in
    every single-host shape."""
    try:
        from ceph_tpu.parallel import multihost

        return multihost.topology_signature()
    except Exception:  # pragma: no cover - topology layer unavailable
        return ()


def _healthy_jax_devices() -> list:
    """The live healthy device set a mesh plan may bind: every chip
    minus per-chip breaker holdouts minus retired hosts' chips
    (device_degraded consults both), and — in a real multi-process
    group — restricted to the MEMBERSHIP-AGREED set
    (multihost.agreed_healthy: each process publishes its local
    observations through the coordinator KV store; a dead host reads
    as a timeout and is retired, never waited on in a collective), so
    every surviving process derives the same mesh."""
    try:
        devs = list(jax.devices())
    except Exception:
        return []
    healthy = [d for d in devs if not circuit.device_degraded(d.id)]
    try:
        from ceph_tpu.parallel import multihost
    except Exception:  # pragma: no cover - topology tier unavailable
        return healthy
    if not multihost.is_multiprocess():
        return healthy
    try:
        agreed = set(multihost.agreed_healthy(
            [d.id for d in healthy]))
    except Exception:  # pragma: no cover - agreement unavailable
        # the coordinator is unreachable: this process cannot know
        # the group view, and proceeding on its LOCAL view while
        # peers hold the agreed one builds divergent meshes (a
        # cross-process wedge).  Decline the mesh — the caller falls
        # back to the single-device plan and peers retire this
        # process by timeout.
        return []
    return [d for d in healthy if d.id in agreed]


def _mesh_devices(batch: int, nbytes: int) -> Optional[tuple]:
    """The device set a (batch, nbytes) dispatch should shard over,
    or None for the single-device plan: mesh off / too small a batch
    / fewer than two healthy chips.  At most one chip per stripe —
    padding a 3-stripe batch onto 8 chips would compute more zeros
    than data."""
    if not (mesh_enabled() and HAVE_JAX):
        return None
    if batch < _mesh_min_stripes() or nbytes < _mesh_min_bytes():
        return None
    healthy = _healthy_jax_devices()
    cap = _mesh_max_devices()
    if cap:
        healthy = healthy[:cap]
    if len(healthy) < 2:
        return None
    return tuple(healthy[:min(len(healthy), batch)])


def _probe_timeout() -> float:
    try:
        return flags.flag_float("CEPH_TPU_MESH_PROBE_TIMEOUT_S")
    except ValueError:
        return 20.0


def _probe_devices(device_ids: Sequence[int]) -> list:
    """Attribute a failed mesh dispatch: a trivial dispatch PINNED to
    each participating chip, guarded by that chip's own
    ``device:<id>`` breaker (threshold 1 — the probe targeted the
    chip, a failure is decisive and trips it; the sick-device
    injection seam fires here too).  Returns the ids that failed
    their probe."""
    dev_by_id = {d.id: d for d in (jax.devices() if HAVE_JAX else [])}
    sick = []
    for did in device_ids:
        dev = dev_by_id.get(did)
        if dev is None:
            sick.append(did)
            continue

        def probe(d=dev):
            x = jax.device_put(np.arange(8, dtype=np.uint8), d)
            return np.asarray(x + 1)

        status, _ = circuit.device_call(
            f"{circuit.DEVICE_FAMILY_PREFIX}{did}", probe, batch=1,
            label=f"mesh-probe:device{did}", devices=(did,),
            timeout=_probe_timeout())
        with _lock:
            _counters["mesh_probes"] += 1
        if status not in ("ok", "benign", "oom"):
            sick.append(did)
    return sick


def _host_aware() -> bool:
    """True when the topology spans more than one host failure domain
    (a real multi-process group, or the emulated in-process
    CEPH_TPU_MULTIHOST_HOSTS partition) — host-level attribution only
    makes sense then; single-host keeps the PR-9 per-chip path
    bit-identically."""
    try:
        from ceph_tpu.parallel import multihost

        return multihost.host_count() > 1
    except Exception:  # pragma: no cover
        return False


def _attribute_failure(device_ids: Sequence[int]
                       ) -> Tuple[List[int], List[int]]:
    """Host-aware attribution of a failed mesh dispatch: probe each
    LOCALLY-addressable participant verdict-free (circuit.probe_raw —
    watchdog + injection seam, NO breaker recording), then aggregate
    BEFORE any verdict lands:

    * a host ALL of whose participating chips failed is retired as
      ONE ``host:<id>`` breaker event — its chips' own breakers never
      fire (no N-chip breaker storm);
    * chips failing inside a still-alive host trip their own
      threshold-1 breakers (the PR-9 sick-chip semantics);
    * REMOTE hosts (a real multi-process group) are never probed from
      here — the collective-safe membership agreement owns their
      verdict: the memo is invalidated and the next healthy-set
      derivation re-agrees, retiring hosts that no longer answer.

    Returns (retired hosts, sick devices)."""
    from ceph_tpu.parallel import multihost

    # snapshot hosts ALREADY degraded before this round: a host in
    # backoff from an earlier retirement must not be re-reported as
    # this failure's attribution (that would absolve the family
    # breaker forever and spin the shrink loop on an unchanged set)
    pre_degraded = {h for h in multihost.hosts()
                    if circuit.host_degraded(h)}
    by_host: Dict[int, List[int]] = {}
    for did in device_ids:
        by_host.setdefault(multihost.host_of_id(did), []).append(did)
    dev_by_id = {d.id: d for d in (jax.devices() if HAVE_JAX else [])}
    retired: List[int] = []
    sick: List[int] = []
    for host, ids in sorted(by_host.items()):
        if not multihost.local_addressable(host):
            continue  # agreement, not local probes, owns remote hosts
        bad = []
        for did in ids:
            dev = dev_by_id.get(did)

            def probe(d=dev):
                x = jax.device_put(np.arange(8, dtype=np.uint8), d)
                return np.asarray(x + 1)

            ok = dev is not None and circuit.probe_raw(
                f"{circuit.DEVICE_FAMILY_PREFIX}{did}", probe,
                devices=(did,), timeout=_probe_timeout())
            with _lock:
                _counters["mesh_probes"] += 1
            if not ok:
                bad.append(did)
        if not bad:
            continue
        if len(bad) == len(ids):
            # the whole host's complement failed: ONE event
            circuit.retire_host(host)
            retired.append(host)
            with _lock:
                _counters["host_retirements"] += 1
        else:
            for did in bad:
                circuit.device_breaker(did).record_failure()
                sick.append(did)
    if multihost.is_multiprocess():
        multihost.membership_changed()
        healthy = [d.id for d in dev_by_id.values()
                   if not circuit.device_degraded(d.id)]
        multihost.agreed_healthy(healthy)  # retires unreachable hosts
        # report only hosts that became degraded IN THIS round —
        # earlier retirements still in backoff are not this
        # failure's attribution
        retired.extend(h for h in multihost.hosts()
                       if circuit.host_degraded(h)
                       and h not in pre_degraded
                       and h not in retired)
    return retired, sick


def _mesh_dispatch(family: str, key: tuple, plan: ExecPlan,
                   args: tuple, batch: int) -> Tuple[str, object]:
    """One mesh-plan dispatch with sick-chip / lost-host attribution.
    Returns ("ok", out) / ("oom", None) / ("shrunk", None) — a sick
    chip or dead host was found and retired, re-plan on the survivors
    — / ("fail", None) — a genuine (non-chip) failure, fall to the
    single-device plan."""
    status, out = _guarded(family, key, plan, args, batch,
                           defer_verdict=True)
    if status == "ok":
        with _lock:
            _counters["mesh_dispatches"] += 1
            _counters["mesh_rows"] += batch
        return "ok", out
    if status == "oom":
        return "oom", None
    if status == "open":
        return "fail", None
    if _host_aware():
        hosts_lost, sick = _attribute_failure(plan.devices)
    else:
        hosts_lost, sick = [], _probe_devices(plan.devices)
    if hosts_lost or sick:
        # the chip's/host's breaker owns the fault (tripped by its
        # probe / the membership verdict); the family must not stay
        # tripped or every caller would degrade to host — the point
        # of the shrink is that they re-plan instead.  Losing a host
        # is ONE shrink, exactly like losing one chip.
        circuit.breaker(family).absolve()
        with _lock:
            _counters["mesh_shrinks"] += 1
        tracing.event(
            f"mesh shrink: host(s) {hosts_lost} / device(s) {sick}"
            " retired" if hosts_lost else
            f"mesh shrink: sick device(s) {sick} retired")
        return "shrunk", None
    _note_plan_failure(key)
    return "fail", None


def mesh_dispatches() -> int:
    """Monotone mesh-dispatch count (the encode service reads the
    delta around a flush to report mesh_batches)."""
    with _lock:
        return _counters["mesh_dispatches"]


def mesh_info() -> dict:
    """Admin view of the mesh policy + live health: the device_health
    tell command and meshbench surface this."""
    total, healthy = 0, []
    if HAVE_JAX and gf.backend_available():
        try:
            devs = jax.devices()
            total = len(devs)
            healthy = [d.id for d in devs
                       if not circuit.device_degraded(d.id)]
        except Exception:
            pass
    with _lock:
        counters = {k: _counters[k] for k in
                    ("mesh_dispatches", "mesh_rows", "mesh_shrinks",
                     "mesh_probes", "host_retirements")}
    out = {
        "enabled": mesh_enabled(),
        "devices_total": total,
        "healthy": healthy,
        "min_bytes": _mesh_min_bytes(),
        "min_stripes": _mesh_min_stripes(),
        **counters,
    }
    # host failure-domain topology (the multihost seam): process
    # count, per-host device sets, per-host health
    try:
        from ceph_tpu.parallel import multihost

        out["hosts"] = {
            str(h): {"devices": list(ids),
                     "degraded": int(circuit.host_degraded(h))}
            for h, ids in sorted(multihost.hosts().items())}
        out["host_count"] = multihost.host_count()
        out["processes"] = multihost.process_count()
        out["multihost_enabled"] = multihost.enabled()
    except Exception:  # pragma: no cover
        pass
    return out


# ---------------------------------------------------------------------------
# Plan kinds
# ---------------------------------------------------------------------------


def _wrap_gather(jfn: Callable) -> Callable:
    """Cross-process plans hold only their addressable output shards
    per process; materialize through the allgather so _guarded's
    np.asarray (and the watchdog) see the whole result.  Identity in
    every single-process shape."""
    from ceph_tpu.parallel import multihost

    if not multihost.is_multiprocess():
        return jfn

    def run(*args):
        return multihost.gather(jfn(*args))

    return run


def _build_mesh_encode_crc(key: tuple, devices: tuple,
                           chunk_bytes: int) -> ExecPlan:
    """Mesh twin of the fused encode+crc plan (the flush path's
    product shape): parity and the hinfo CRC stay device-resident
    between the stages of ONE stripe-parallel dispatch."""
    from ceph_tpu.parallel import striped

    mesh = striped.stripe_mesh(list(devices))
    jfn, sharding = striped.build_mesh_encode_crc(
        mesh, chunk_bytes, _label(key))
    return ExecPlan(key, _wrap_gather(jfn),
                    f"mesh_bits+crc[{len(devices)}]",
                    sharding=sharding,
                    devices=tuple(d.id for d in devices))


def _mesh_encode_attempt(matrix: np.ndarray, arr: np.ndarray, sig: str,
                         rows: int, k: int, b: int, s: int
                         ) -> Tuple[str, Optional[object]]:
    """Try the fused encode+crc dispatch on the healthy mesh,
    shrinking on sick chips.  Returns ("none", None) — take the
    single-device plan — or ("ok", out) / ("oom", None).  Out is the
    raw padded plan output; callers slice."""
    devices = _mesh_devices(b, b * k * s)
    for _attempt in range(8):       # shrink at most once per domain
        if not devices:
            return "none", None
        ids = tuple(d.id for d in devices)
        key = plan_key(sig, "mesh_encode_crc", rows, k, b, s, mesh=ids,
                       proc=_topology())
        if _quarantined(key):
            return "none", None
        plan = _get_plan(
            key, lambda: _build_mesh_encode_crc(key, devices, s))
        bb, bs = key[4], key[5]
        # shard straight from host bytes in ONE device_put — landing
        # on the default device first and re-scattering would double
        # the transfer on the flush hot path.  Cross-process plans
        # assemble the global array from each process's addressable
        # shards instead (the SPMD contract: every process holds the
        # same logical batch).
        from ceph_tpu.parallel import multihost

        padded = multihost.put_global(_pad_batch(arr, bb, bs),
                                      plan.sharding)
        status, out = _mesh_dispatch(
            "fused-crc", key, plan, (_mbits_for(matrix), padded), b)
        if status in ("ok", "oom"):
            return status, out
        if status != "shrunk":
            return "none", None
        devices = _mesh_devices(b, b * k * s)  # the survivors
    return "none", None


def _build_compute(key: tuple, weights: np.ndarray) -> ExecPlan:
    """The `compute` plan kind: a coded-compute kernel evaluation —
    a row-weighted XOR fold of the (B, rows, lanes) batch of shard
    streams, one trace shared by every wave that lands in the same
    bucket.  The weight row is a COMPILE-TIME constant (the key
    carries its content signature), so all-ones kernels lower to a
    pure XOR reduce instead of a GF table walk."""
    from ceph_tpu.compute import kernels as compute_kernels

    jfn = tracked_jit(_label(key),
                      compute_kernels.make_device_eval(weights))
    return ExecPlan(key, jfn, "xla_fold")


def compute_eval(name: str, weights: np.ndarray, data: np.ndarray,
                 sig: Optional[str] = None,
                 family: str = "compute") -> Optional[np.ndarray]:
    """(B, rows, lanes) uint8 shard batch -> (B, 1, lanes) kernel
    results through the plan cache (kind `compute`, its own breaker
    family so a compute fault never degrades the encode/decode data
    path).  Returns None when no jax backend is available, the plan
    is quarantined, or the guarded dispatch failed — callers take the
    bit-exact numpy host path; RESOURCE_EXHAUSTED halves the batch
    recursively first."""
    if not (HAVE_JAX and gf.backend_available()):
        return None
    arr = np.asarray(data, dtype=np.uint8)
    assert arr.ndim == 3, arr.shape
    b, rows, lanes = arr.shape
    if b == 0 or rows == 0 or lanes == 0:
        return None
    w = np.asarray(weights, dtype=np.uint8)
    sig = sig or matrix_signature(w, extra=f"compute/{name}")
    key = plan_key(sig, "compute", 1, rows, b, lanes)
    if _quarantined(key):
        return None
    plan = _get_plan(key, lambda: _build_compute(key, w))
    bb, bs = key[4], key[5]
    padded = jnp.asarray(_pad_batch(arr, bb, bs))
    status, out = _guarded(family, key, plan, (padded,), b)
    if status == "oom" and b > 1:
        h = b // 2
        first = compute_eval(name, w, arr[:h], sig=sig,
                             family=family)
        second = compute_eval(name, w, arr[h:], sig=sig,
                              family=family)
        if first is None or second is None:
            return None
        return np.concatenate([first, second], axis=0)
    if status != "ok":
        return None
    return np.asarray(out)[:b, :, :lanes]


def _build_inference(key: tuple, arch: str) -> ExecPlan:
    """The `inference` plan kind: batched query-x-shard scoring for
    the coded inference engine — every serving stream's forward pass
    over the query batch in ONE dispatch.  Unlike the compute kind
    the parameters are RUNTIME operands (each stored model differs;
    baking them would compile per model), so one trace per
    (arch, dims, query bucket) serves every model of that shape."""
    if arch == "linear":
        def fwd(tables, q):
            # (B, rows, dim) x (nq, dim) -> (B, nq, rows)
            return jnp.einsum("qd,brd->bqr", q, tables,
                              preferred_element_type=jnp.float32)
    else:
        def fwd(w1, b1, w2, q):
            # (B,h,dim),(B,h),(B,o,h) x (nq,dim) -> (B, nq, o)
            hid = jnp.maximum(
                jnp.einsum("qd,bhd->bqh", q, w1,
                           preferred_element_type=jnp.float32)
                + b1[:, None, :], 0.0)
            return jnp.einsum("bqh,boh->bqo", hid, w2,
                              preferred_element_type=jnp.float32)
    return ExecPlan(key, tracked_jit(_label(key), fwd), "xla_infer")


def inference_eval(arch: str, ops: tuple, queries: np.ndarray,
                   sig: str, family: str = "ec-inference"
                   ) -> Optional[np.ndarray]:
    """Stacked per-stream parameters + (nq, dim) query batch ->
    (B, nq, cols) float32 contributions through the plan cache (kind
    `inference`, its own breaker family so an inference fault never
    trips the encode/decode or compute paths).  The sig must encode
    ALL parameter dims (they are runtime operands, invisible to the
    key otherwise); only the query batch rides the bucketed axis.
    Returns None on no backend / quarantine / guarded failure —
    callers take the bit-exact numpy forward (model.shard_forward);
    RESOURCE_EXHAUSTED halves the query batch recursively first."""
    if not (HAVE_JAX and gf.backend_available()):
        return None
    q = np.asarray(queries, dtype=np.float32)
    nq = q.shape[0]
    nstreams = ops[0].shape[0]
    if nq == 0 or nstreams == 0:
        return None
    key = plan_key(sig, "inference", nstreams, 0, nq, 0)
    if _quarantined(key):
        return None
    plan = _get_plan(key, lambda: _build_inference(key, arch))
    bq = key[4]
    qp = np.pad(q, ((0, bq - nq), (0, 0))) if bq != nq else q
    status, out = _guarded(
        family, key, plan,
        tuple(jnp.asarray(np.asarray(o, dtype=np.float32))
              for o in ops) + (jnp.asarray(qp),), nq)
    if status == "oom" and nq > 1:
        h = nq // 2
        first = inference_eval(arch, ops, q[:h], sig, family=family)
        second = inference_eval(arch, ops, q[h:], sig, family=family)
        if first is None or second is None:
            return None
        return np.concatenate([first, second], axis=1)
    if status != "ok":
        return None
    return np.asarray(out)[:, :nq, :]


def _build_repair(key: tuple, matrix: np.ndarray) -> ExecPlan:
    """The `repair` plan kind: a regenerating-code repair matmul —
    helper-side projection rows or the primary's reconstruction
    matrix — where the matrix is a COMPILE-TIME constant like the
    compute kind's weight row (the key carries its content
    signature, so one plan serves every wave of the same codec +
    erasure pattern).  Repair matrices are tiny (alpha x d), so
    baking them lets XLA fold the bit expansion into the trace
    instead of shipping a runtime operand per dispatch."""
    mbits = jnp.asarray(gf.gf_matrix_to_bits(
        np.ascontiguousarray(matrix, dtype=np.uint8)))
    jfn = tracked_jit(_label(key),
                      lambda d: gf._gf2_matmul_bytes_impl(mbits, d))
    return ExecPlan(key, jfn, "xla_bits_const")


def repair(mat: np.ndarray, data, sig: Optional[str] = None,
           family: str = "ec-repair") -> Optional[np.ndarray]:
    """(B, D, S) or (D, S) uint8 helper fragments x the (R, D) repair
    matrix -> lost sub-chunk rows, plan-cached (kind `repair`).

    The plan key hashes the MATRIX CONTENT (the caller's sig rides as
    a cache-locality extra only) because the matrix is baked into the
    trace — correctness must not depend on callers keeping sigs
    matrix-unique.  Returns None when no jax backend is available,
    the plan key is quarantined, or the guarded dispatch failed
    (callers take the bit-exact host path); RESOURCE_EXHAUSTED halves
    the batch recursively first."""
    if not (HAVE_JAX and gf.backend_available()):
        return None
    if not isinstance(data, np.ndarray):
        return None
    arr = np.asarray(data, dtype=np.uint8)
    squeeze = arr.ndim == 2
    if squeeze:
        arr = arr[None]
    if arr.ndim != 3:
        return None
    b, kk, s = arr.shape
    if s == 0 or b == 0:
        return None
    mat = np.ascontiguousarray(np.asarray(mat, dtype=np.uint8))
    rows = mat.shape[0]
    sig = matrix_signature(mat, extra=sig or "repair")
    key = plan_key(sig, "repair", rows, kk, b, s)
    if _quarantined(key):
        return None
    plan = _get_plan(key, lambda: _build_repair(key, mat))
    padded = jnp.asarray(_pad_batch(arr, key[4], key[5]))
    status, out = _guarded(family, key, plan, (padded,), b)
    if status == "oom" and b > 1:
        h = b // 2
        first = repair(mat, arr[:h], sig=sig, family=family)
        second = repair(mat, arr[h:], sig=sig, family=family)
        if first is None or second is None:
            return None
        out = np.concatenate([first, second], axis=0)
        return out[0] if squeeze else out
    if status != "ok":
        return None
    out = np.asarray(out)[:b, :, :s]
    return out[0] if squeeze else out


def _build_mesh_matmul(key: tuple) -> ExecPlan:
    """Delegate to the healthy-set sharded pipeline (its per-shape
    jits are tracked_jit'd in parallel/striped.py, so retraces land in
    the same counters).  The key's mesh element is the device-id set
    the pipeline rides — it doubles as the device_call attribution
    set, so the sick-device injection seam and per-chip success
    accounting see decode dispatches too."""
    from ceph_tpu.parallel import backend

    return ExecPlan(key, backend.matmul, "mesh", devices=key[6])


def matmul(mat: np.ndarray, data, sig: str = None,
           family: str = "ec-decode") -> Optional[np.ndarray]:
    """Plan-cached device GF(2^8) matmul — the ec/dispatch device
    entry.  Buckets the (B, S) shape, pads, dispatches through the
    cached plan, slices the real shape back out.  Returns None when no
    device path applies, the plan key is quarantined, or the guarded
    dispatch failed (caller falls back to host); RESOURCE_EXHAUSTED
    halves the batch recursively first."""
    if not (HAVE_JAX and gf.backend_available()):
        return None
    if not isinstance(data, np.ndarray):
        return None
    arr = np.asarray(data, dtype=np.uint8)
    squeeze = False
    if arr.ndim == 2:
        arr = arr[None]
        squeeze = True
    b, k, s = arr.shape
    if s == 0:
        return None
    mat = np.asarray(mat, dtype=np.uint8)
    rows = mat.shape[0]
    from ceph_tpu.parallel import backend

    status, out = None, None
    for _attempt in range(8):           # shrink at most once per chip
        # decode matrices cycle per erasure signature: key on shape
        # (matrix as runtime operand) + the LIVE healthy device set —
        # a shrink retires the dead chip's plans by key miss
        mesh_sig = backend.mesh_device_ids()
        key = plan_key(sig or "*", "matmul", rows, k, b, s,
                       mesh=mesh_sig, proc=_topology())
        if _quarantined(key):
            return None
        plan = _get_plan(key, lambda: _build_mesh_matmul(key))
        bb, bs = key[4], key[5]
        args = (mat, _pad_batch(arr, bb, bs))
        if len(mesh_sig) > 1:
            status, out = _mesh_dispatch(family, key, plan, args, b)
            if status == "shrunk":
                continue                # re-plan on the survivors
            if status == "fail":
                with _lock:
                    _counters["host_fallbacks"] += 1
                return None
        else:
            status, out = _guarded(family, key, plan, args, b)
        break
    if status == "oom" and b > 1:
        h = b // 2
        first = matmul(mat, arr[:h], sig=sig, family=family)
        second = matmul(mat, arr[h:], sig=sig, family=family)
        if first is None or second is None:
            return None
        out = np.concatenate([first, second], axis=0)
        return out[0] if squeeze else out
    if status != "ok" or out is None:
        return None
    out = np.asarray(out)[:b, :, :s]
    return out[0] if squeeze else out


def fused_encode_crc_step(mbits, d, consts):
    """THE fused parity + per-chunk zero-seeded crc32c kernel — the
    one trace both the single-device plan and the mesh builders
    (parallel/striped.build_mesh_encode_crc) wrap.  Bit-exact
    single-vs-mesh parity depends on them tracing identical math, so
    there is exactly one definition."""
    parity = gf._gf2_matmul_bytes_impl(mbits, d)
    chunks = jnp.concatenate([d, parity], axis=1)
    bits = cks.crc32c_partial_bits(chunks, consts)
    return parity, cks.crc32c_pack_bits(bits)


def _build_encode_crc(key: tuple, matrix: np.ndarray) -> ExecPlan:
    """Fused parity + per-chunk zero-seeded crc32c in ONE dispatch
    (parity and the ECUtil::HashInfo ledger used to be two round
    trips).  The chunk-byte axis is NOT bucketed here — a CRC is
    length-exact — so the key carries the exact S; only the stripe
    batch pads (padded stripes' crcs are sliced off with the parity).
    Where the Pallas kernels take the shape (a TPU, S a multiple of
    512 and at most 8 KiB) they serve it; the XLA bit-matmul
    otherwise.
    """
    from ceph_tpu.ops import crc_pallas, gf_pallas

    _sig, _kind, rows, k, bb, s = key[:6]
    if gf_pallas.supported((bb, k, s)) and \
            crc_pallas.supported(s, bb * (k + rows)):
        return _build_encode_crc_pallas(key, matrix)
    consts = cks.make_crc_consts(s)

    def impl(mbits, d):
        return fused_encode_crc_step(mbits, d, consts)

    jfn = tracked_jit(_label(key), impl)
    return ExecPlan(key, jfn, "xla_bits+crc")


def fused_encode_crc_words(coeffs: np.ndarray, words):
    """The fused step on the Pallas kernels: the packed-word GF kernel
    (ops/gf_pallas.py) for parity and the MXU crc kernel
    (ops/crc_pallas.py) for every chunk's zero-seeded crc32c, over
    (B, K, S//512, 128) int32 words -> (parity words, (B, K+M) crcs)."""
    from ceph_tpu.ops import crc_pallas, gf_pallas

    # traced inside the plan's tracked_jit, dispatched by _guarded
    parity = gf_pallas.gf_matmul_words(coeffs, words)  # lint: disable=unguarded-device-dispatch
    chunks = jnp.concatenate([words, parity], axis=1)
    b, n, r4, _ = chunks.shape
    s = r4 * 512
    crcs = crc_pallas.crc32c_blocks_words(
        chunks.reshape(b * n, s // 4), s, init=0)
    return parity, crcs.reshape(b, n)


def _codec_compile(label: str, jfn, words) -> None:
    """The `codec_compile` stage: the first launch of a registered
    matrix's fused plan lowers and compiles its specialised kernel here
    (the launch then finds it in jit's cache), inside the launch's
    guard (watchdog and breaker, as any first compile), timed on its
    own into `stats()` and marked on the timeline of the batched
    dispatch it serves.  A compile that fails raises to the guard."""
    stages = tracing.current_dispatch.get()
    if stages is not None:
        stages.mark("codec_compile")
    t0 = time.perf_counter()
    try:
        with tracing.annotate("ceph.codec_compile"):
            jfn.lower(words).compile()
    finally:
        _note_codec_compile(label, time.perf_counter() - t0)
        if stages is not None:
            stages.mark("dispatch_launch", annotated=True)


def _build_encode_crc_pallas(key: tuple, matrix: np.ndarray) -> ExecPlan:
    """The fused plan over the word view of the host bytes (free both
    ways: gf_pallas.words_from_bytes / bytes_from_words).  For a
    registered matrix (the specialised kernel) the plan records the
    kernel's XOR network into `stats()` as it is built, and its first
    launch compiles in the `codec_compile` stage."""
    from ceph_tpu.ops import gf_pallas

    coeffs = np.array(matrix, dtype=np.uint8)
    label = _label(key)
    jfn = tracked_jit(label,
                      lambda words: fused_encode_crc_words(coeffs, words))
    network = gf_pallas.registered(coeffs)
    if network is not None:
        with _lock:
            _networks[label] = dict(network)
    uncompiled = [network is not None]

    def run(_mbits, padded):
        words = gf_pallas.words_from_bytes(padded)
        if uncompiled[0]:
            _codec_compile(label, jfn, words)
            uncompiled[0] = False
        return jfn(words)

    def host(out):
        parity, crcs = out
        return gf_pallas.bytes_from_words(parity), crcs

    return ExecPlan(key, run, "pallas_words+crc", host=host)


def encode_with_crc(matrix: np.ndarray, data: np.ndarray,
                    sig: str = None
                    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(B, K, S) stripes -> (parity (B, M, S), crc (B, K+M) uint32).

    crcs are ZERO-seeded per-chunk crc32c (seed advances are host
    scalars: crc32c(init, chunk) = crc32c_zeros(init, S) ^ crc0);
    callers fold them into cumulative HashInfo ledgers with
    checksum.crc32c_fold_ledger.  Returns None
    when no jax backend is available.
    """
    if not (HAVE_JAX and gf.backend_available()):
        return None
    arr = np.asarray(data, dtype=np.uint8)
    assert arr.ndim == 3, arr.shape
    b, k, s = arr.shape
    if s == 0:
        return None
    rows = int(np.asarray(matrix).shape[0])
    sig = sig or matrix_signature(matrix)
    # mesh attempt first: the encode service's flush batches land
    # here — one stripe-parallel dispatch over the healthy chips,
    # parity + CRC fused on-device
    mstatus, mout = _mesh_encode_attempt(matrix, arr, sig, rows, k, b, s)
    if mstatus == "ok":
        mparity, mcrcs = mout
        return (np.asarray(mparity)[:b],
                np.asarray(mcrcs).astype(np.uint32)[:b])
    if mstatus == "oom" and b > 1:
        h = b // 2
        first = encode_with_crc(matrix, arr[:h], sig=sig)
        second = encode_with_crc(matrix, arr[h:], sig=sig)
        if first is None or second is None:
            return None
        return (np.concatenate([first[0], second[0]], axis=0),
                np.concatenate([first[1], second[1]], axis=0))
    key = plan_key(sig, "encode_crc", rows, k, b, s)
    if _quarantined(key):
        return None
    plan = _get_plan(key, lambda: _build_encode_crc(key, matrix))
    # host bytes go in as they are: the transfer lands inside the
    # guarded body, under the watchdog
    padded = _pad_batch(arr, key[4], s)
    status, out = _guarded("fused-crc", key, plan,
                           (_mbits_for(matrix), padded), b)
    if status == "oom" and b > 1:
        h = b // 2
        first = encode_with_crc(matrix, arr[:h], sig=sig)
        second = encode_with_crc(matrix, arr[h:], sig=sig)
        if first is None or second is None:
            return None
        return (np.concatenate([first[0], second[0]], axis=0),
                np.concatenate([first[1], second[1]], axis=0))
    if status != "ok":
        return None
    parity, crcs = out
    return (np.asarray(parity)[:b],
            np.asarray(crcs).astype(np.uint32)[:b])
