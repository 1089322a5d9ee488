"""Central kill-switch registry: every ``CEPH_TPU_*`` toggle in one
audited seam.

Every fast path in this tree ships with a kill switch (the
cross-cutting invariant in ROADMAP.md), and by PR 19 those switches
had grown into 50+ scattered ``os.environ`` reads — invisible to
introspection, unfindable for the chaos engine's live-flip hazard,
and with per-site default strings that could silently drift.  This
module is the single registry: each flag is declared ONCE with its
default, its scope (whether a live flip takes effect immediately or
only at the next daemon/module start), and a one-line description;
reads go through :func:`get` / :func:`enabled` / :func:`flag_float` /
:func:`flag_int`, and writes through :func:`set_flag` /
:func:`clear` — which fire live-flip hooks and append to a bounded
audit log the chaos engine echoes into its violation reports.

The backing store stays ``os.environ`` on purpose: flags must inherit
into spawned subprocesses (the meshbench multi-process sweeps, the
OSD fault-injection seams) and must keep working for tests/benches
that set ``os.environ`` directly.  The registry adds the declaration,
the audit, and the hooks — it does not invent a second store that
could disagree with the first.

Lint rule ``unregistered-kill-switch`` (analysis/rules.py) closes the
loop: a raw ``os.environ`` read of a ``CEPH_TPU_*`` literal anywhere
in the package outside this module is a finding, with a ZERO
baseline — new switches must land here first.

Scopes:

``process``
    Read on every use; a live flip applies to the next operation.
``startup``
    Read once at daemon/module initialization; a flip needs a
    restart (the chaos kill-switch hazard must not expect these to
    take effect mid-scenario).
``inject``
    Fault-injection seam, re-read per dispatch — the chaos hazards'
    levers.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "register", "get", "enabled", "flag_float", "flag_int",
    "set_flag", "clear", "setdefault", "on_flip", "flips",
    "clear_flips", "registry", "UnregisteredFlag",
]


class UnregisteredFlag(KeyError):
    """A flag name no `register()` call declared: either a typo (the
    loud failure is the point) or a new switch that must be added to
    the registry table below."""


class _Flag:
    __slots__ = ("name", "default", "scope", "desc")

    def __init__(self, name: str, default: Optional[str],
                 scope: str, desc: str):
        self.name = name
        self.default = default
        self.scope = scope
        self.desc = desc


_REGISTRY: Dict[str, _Flag] = {}
_HOOKS: List[Callable[[str, Optional[str], Optional[str]], None]] = []
_FLIPS: List[Dict[str, Any]] = []
_FLIPS_CAP = 4096
_lock = threading.Lock()
_UNSET = object()


def register(name: str, default: Optional[str] = None,
             scope: str = "process", desc: str = "") -> None:
    """Declare a flag.  Idempotent; re-registration with a DIFFERENT
    default is an error (the per-site default drift this registry
    exists to end)."""
    if scope not in ("process", "startup", "inject"):
        raise ValueError(f"unknown flag scope {scope!r}")
    with _lock:
        cur = _REGISTRY.get(name)
        if cur is not None:
            if cur.default != default:
                raise ValueError(
                    f"{name} re-registered with default {default!r}"
                    f" (was {cur.default!r})")
            return
        _REGISTRY[name] = _Flag(name, default, scope, desc)


def _flag(name: str) -> _Flag:
    f = _REGISTRY.get(name)
    if f is None:
        raise UnregisteredFlag(
            f"{name} is not in the kill-switch registry "
            "(ceph_tpu/common/flags.py): register it there")
    return f


def get(name: str, default: Any = _UNSET) -> Optional[str]:
    """Raw string value: the environment override if present, else
    the call-site `default` if given, else the registered default.
    The read is DYNAMIC (per call) so direct ``os.environ`` writes by
    tests and benches keep working."""
    f = _flag(name)
    d = f.default if default is _UNSET else default
    return os.environ.get(name, d)


def peek(name: str) -> Optional[str]:
    """The save/restore idiom's read: the raw environment OVERRIDE
    (None when unset — callers restoring state need unset-vs-default
    distinguished, which :func:`get`'s default substitution hides)."""
    _flag(name)
    return os.environ.get(name)


def enabled(name: str) -> bool:
    """Boolean view: on unless unset-with-falsy-default, empty, or
    ``"0"`` — the ``!= "0"`` convention every default-on kill switch
    in this tree uses."""
    return get(name) not in (None, "", "0")


def flag_float(name: str, default: Any = _UNSET) -> float:
    v = get(name, default)
    return float(v if v is not None else 0.0)


def flag_int(name: str, default: Any = _UNSET) -> int:
    v = get(name, default)
    # int("3.0") raises; route through float like the _env_float
    # helpers this replaces
    return int(float(v if v is not None else 0))


def _audit(name: str, old: Optional[str],
           new: Optional[str]) -> None:
    _FLIPS.append({"t": time.monotonic(), "flag": name,
                   "old": old, "new": new})
    del _FLIPS[:-_FLIPS_CAP]
    for hook in list(_HOOKS):
        try:
            hook(name, old, new)
        except Exception:
            # a broken observer must not turn a kill-switch flip into
            # an op-path failure
            pass


def set_flag(name: str, value: str) -> None:
    """Flip a flag: write the environment (subprocess inheritance),
    record the flip, fire live-flip hooks."""
    f = _flag(name)
    with _lock:
        old = os.environ.get(name, f.default)
        os.environ[name] = str(value)
        _audit(name, old, str(value))


def clear(name: str) -> None:
    """Reset a flag to its registered default (drop the override)."""
    f = _flag(name)
    with _lock:
        old = os.environ.get(name)
        if old is None:
            return
        os.environ.pop(name, None)
        _audit(name, old, f.default)


def setdefault(name: str, value: str) -> str:
    """Set only if unset (the meshbench smoke-floor pattern); returns
    the effective value.  Counted as a flip only when it writes."""
    _flag(name)
    with _lock:
        cur = os.environ.get(name)
        if cur is not None:
            return cur
        os.environ[name] = str(value)
        _audit(name, None, str(value))
        return str(value)


def on_flip(hook: Callable[[str, Optional[str], Optional[str]],
                           None]) -> None:
    """Observe flips: hook(name, old, new) fires inside set_flag /
    clear / first-write setdefault."""
    _HOOKS.append(hook)


def remove_hook(hook: Callable) -> None:
    try:
        _HOOKS.remove(hook)
    except ValueError:
        pass


def flips() -> List[Dict[str, Any]]:
    """The audit log (bounded): every flip since process start /
    last clear_flips(), oldest first."""
    return list(_FLIPS)


def clear_flips() -> None:
    del _FLIPS[:]


def registry() -> Dict[str, Dict[str, Any]]:
    """Introspection snapshot: every declared flag with its default,
    scope, description, and current effective value."""
    return {
        name: {"default": f.default, "scope": f.scope,
               "desc": f.desc,
               "value": os.environ.get(name, f.default)}
        for name, f in sorted(_REGISTRY.items())}


# ---------------------------------------------------------------------
# The registry table: every CEPH_TPU_* flag in the tree.  Grouped by
# subsystem; scope "startup" marks reads that happen once at
# daemon/module init (a live flip does not reach them).
# ---------------------------------------------------------------------

# -- device / kernel tier ---------------------------------------------
register("CEPH_TPU_PALLAS", "1", "process",
         "Pallas word-kernel tier (GF matmul / CRC); 0 = XLA path")
register("CEPH_TPU_BREAKER", "1", "process",
         "circuit breaker around device dispatch; 0 = raw dispatch")
register("CEPH_TPU_BREAKER_THRESHOLD", "3", "process",
         "consecutive failures before a family breaker opens")
register("CEPH_TPU_BREAKER_BACKOFF_S", "0.5", "process",
         "initial open-state backoff seconds")
register("CEPH_TPU_BREAKER_BACKOFF_MAX_S", "30.0", "process",
         "open-state backoff ceiling seconds")
register("CEPH_TPU_DEVICE_BREAKER_THRESHOLD", "1", "process",
         "per-device/host family breaker trip threshold")
register("CEPH_TPU_DEVICE_TIMEOUT_S", "120.0", "process",
         "device dispatch watchdog seconds")
register("CEPH_TPU_INJECT_DEVICE_FAIL", None, "inject",
         "fault injection spec: p | next=N | hang=MS | oom=K | "
         "sick=ID | down_host=H (chaos device/host hazard lever)")

# -- EC plan / mesh / multihost ---------------------------------------
register("CEPH_TPU_PLAN_QUARANTINE_S", "30.0", "process",
         "failed-plan quarantine seconds")
register("CEPH_TPU_PLAN_FAIL_LIMIT", "3", "process",
         "plan failures before quarantine")
register("CEPH_TPU_MESH", "1", "process",
         "multi-chip mesh dispatch; 0 = single-device plans")
register("CEPH_TPU_MESH_MIN_BYTES", str(1 << 20), "process",
         "payload floor below which mesh dispatch is skipped")
register("CEPH_TPU_MESH_MIN_STRIPES", "2", "process",
         "stripe floor for mesh dispatch")
register("CEPH_TPU_MESH_MAX_DEVICES", "0", "process",
         "mesh device cap; 0 = all healthy devices")
register("CEPH_TPU_MESH_PROBE_TIMEOUT_S", "20.0", "process",
         "sick-device probe timeout seconds")
register("CEPH_TPU_MULTIHOST", "1", "process",
         "cross-host data plane; 0 = single-host meshes only")
register("CEPH_TPU_MULTIHOST_LOCAL_DEVICES", None, "startup",
         "per-process visible-device override for workers")
register("CEPH_TPU_MULTIHOST_COORD", "", "startup",
         "coordinator address for the jax.distributed bootstrap")
register("CEPH_TPU_MULTIHOST_NPROC", "1", "startup",
         "process count for the jax.distributed bootstrap")
register("CEPH_TPU_MULTIHOST_PID", "0", "startup",
         "this process's index in the jax.distributed group")
register("CEPH_TPU_MULTIHOST_HOSTS", "1", "process",
         "emulated host count for the host-topology map")
register("CEPH_TPU_MULTIHOST_AGREE_TIMEOUT_S", "10.0", "process",
         "membership-agreement collective timeout seconds")
register("CEPH_TPU_MULTIHOST_WORKER_DEADLINE_S", None, "startup",
         "meshbench worker hard deadline seconds")
register("CEPH_TPU_MULTIHOST_LEG_TIMEOUT_S", "120", "process",
         "meshbench per-leg driver timeout seconds")
register("CEPH_TPU_BENCH_SMOKE", None, "startup",
         "bench smoke mode: small sizes, fast legs")
register("CEPH_TPU_COLLECTIVE_TRACE", None, "startup",
         "record runtime collective traces for the SPMD cross-check")
register("CEPH_TPU_COLLECTIVE_TRACE_FILE", None, "startup",
         "path sink for recorded collective traces")

# -- codec compiler ----------------------------------------------------
register("CEPH_TPU_XSCHED", "1", "process",
         "XOR schedule compiler; 0 = naive row-walk")
register("CEPH_TPU_NATIVE_XSCHED", "1", "process",
         "native fused-tape executor; 0 = python executor")
register("CEPH_TPU_XSCHED_HOST_MAX_ONES", "4096", "process",
         "host-executor density ceiling (ones count)")

# -- subsystem kill switches ------------------------------------------
register("CEPH_TPU_COMPUTE", "1", "process",
         "coded-compute pushdown; 0 = read-then-compute")
register("CEPH_TPU_INFERENCE", "1", "process",
         "coded inference serving; 0 = exact full-decode only")
register("CEPH_TPU_MSR_REPAIR", "1", "process",
         "MSR regenerating repair; 0 = classic k-read rebuild")
register("CEPH_TPU_TIER", "1", "process",
         "hot-set read tier; 0 = every read from the store")
register("CEPH_TPU_HEDGE", "1", "process",
         "hedged shard reads; 0 = single-attempt gathers")
register("CEPH_TPU_TRACE", "1", "process",
         "critical-path span layer; 0 = spans off")
register("CEPH_TPU_ENCODE_SERVICE", "1", "startup",
         "micro-batching encode service; 0 = inline encodes")
register("CEPH_TPU_ENCODE_BATCH_WINDOW_MS", "1.0", "startup",
         "encode-service batch window milliseconds")
register("CEPH_TPU_ENCODE_BATCH_BYTES", str(8 << 20), "startup",
         "encode-service batch byte ceiling")
register("CEPH_TPU_GROUP_COMMIT", "1", "startup",
         "group-commit fsync barriers; 0 = one commit per txn")
register("CEPH_TPU_GROUP_COMMIT_WINDOW_MS", "0.5", "startup",
         "group-commit accumulation window (ms)")
register("CEPH_TPU_GROUP_COMMIT_TXNS", "64", "startup",
         "group-commit max transactions per batch")
register("CEPH_TPU_GROUP_COMMIT_BYTES", str(4 << 20), "startup",
         "group-commit max payload bytes per batch")
register("CEPH_TPU_FUSE_MIN_BYTES", None, "process",
         "object-size floor for the fused encode+crc dispatch")

# -- QoS / scheduling --------------------------------------------------
register("CEPH_TPU_QOS", "1", "startup",
         "per-tenant mClock classes + admission gate; 0 = one "
         "shared client class")
register("CEPH_TPU_DMCLOCK", "1", "process",
         "distributed mClock delta/rho piggybacking: MOSDOp carries "
         "per-tenant service deltas so tags are cluster-consistent; "
         "0 = per-OSD tags only")
register("CEPH_TPU_OP_FAST_LANE", "1", "startup",
         "sub-chunk write fast lane; 0 = every op queues")

# -- store / durability ------------------------------------------------
register("CEPH_TPU_CRASH_INJECT", "1", "process",
         "power-cut synthesis in FaultStore kill paths (chaos "
         "power-cut hazard lever)")

# -- tracing / debug / analysis ---------------------------------------
register("CEPH_TPU_DEBUG", None, "startup",
         "daemon debug logging")
register("CEPH_TPU_LOCKDEP", "0", "startup",
         "runtime lock-order detector")
register("CEPH_TPU_INTERLEAVE", "0", "startup",
         "deterministic-interleaving explorer hooks")
register("CEPH_TPU_INTERLEAVE_SEED", "0", "process",
         "interleaving exploration seed")
register("CEPH_TPU_RGW_TRACE_SAMPLE", "1.0", "process",
         "S3 frontend ingress-span sample rate")
