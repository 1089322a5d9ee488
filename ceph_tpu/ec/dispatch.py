"""Shared device-dispatch helpers for the erasure codecs.

One home for the two patterns every codec repeats (flagged by review):
GF matmul routed host-vs-TPU, and the bounded LRU cache keyed by erasure
signature (the ErasureCodeIsaTableCache role).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Hashable, Optional

import numpy as np

from ceph_tpu.common import circuit
from ceph_tpu.ops import gf


def gf_matmul(mat: np.ndarray, data: np.ndarray, use_tpu: bool,
              min_bytes: int = 1, sig: Optional[str] = None,
              family: str = "ec-encode") -> np.ndarray:
    """(R,K) GF(2^8) matrix x (K,S) or (B,K,S) uint8, device-dispatched.

    The device branch routes through the ExecPlan cache (ec/plan.py):
    shapes bucket onto a handful of compiled plans, and the plan
    delegates to the LIVE HEALTHY device mesh (parallel/backend.py)
    — the daemons' EC path and the multi-chip dryrun compile the same
    program; a single chip is the (1,1) mesh, and a chip whose
    ``device:<id>`` breaker is open is simply absent from the next
    mesh build.  `sig` is the codec's plan signature.  Below the plan
    the mesh-direct and xla-direct dispatches take a product the plan
    declined or failed, before the host fold.

    Every device attempt rides the `family` circuit breaker
    (common/circuit.py): while the breaker is open — or when the
    guarded dispatch fails, times out, or exhausts OOM halving — the
    call degrades to the bit-exact numpy host fold below, so callers
    NEVER see a device error from this entry.
    """
    if use_tpu and gf.backend_available() and data.size >= min_bytes:
        if not circuit.degraded(family):
            out = _device_matmul(mat, data, sig, family)
            if out is not None:
                return out
        else:
            circuit.breaker(family).note_fallback()
    if data.ndim == 2:
        return gf.gf_matmul_host(mat, data)
    # batched host path: the GF matmul is elementwise across columns, so
    # B stripes fold into ONE wide (K, B*S) region op — per-stripe calls
    # would pay kernel setup B times for tiny regions
    b, k, s = data.shape
    flat = np.ascontiguousarray(np.moveaxis(data, 1, 0)).reshape(k, b * s)
    par = gf.gf_matmul_host(mat, flat)
    return np.moveaxis(par.reshape(-1, b, s), 0, 1)


def _device_matmul(mat: np.ndarray, data: np.ndarray,
                   sig: Optional[str], family: str) -> Optional[np.ndarray]:
    """The device tiers in preference order, every dispatch guarded;
    None means 'take the host path'."""
    from ceph_tpu.ec import plan

    out = plan.matmul(mat, data, sig=sig, family=family)
    if out is not None:
        return out
    if circuit.degraded(family):     # the plan attempt may have tripped
        return None
    from ceph_tpu.parallel import backend

    batch = data.shape[0] if data.ndim == 3 else 1
    status, out = circuit.device_call(
        family, backend.matmul, mat, data, batch=batch,
        label="mesh-direct", oom_to_fail=batch <= 1,
        devices=backend.mesh_device_ids() or None)
    if status == "ok" and out is not None:
        return out
    if status == "oom" and batch > 1:
        # np.split hands back views of the same stripes (no byte
        # moves); each half re-dispatches under its own guard
        first_half, second_half = np.split(data, [batch // 2])
        first = _device_matmul(mat, first_half, sig, family)
        second = _device_matmul(mat, second_half, sig, family)
        if first is not None and second is not None:
            return np.concatenate([first, second], axis=0)
        return None
    if status in ("fail", "timeout", "open", "oom"):
        return None
    # mesh declined the shape (ok, None): the single-device XLA kernel.
    # np.asarray INSIDE the guarded body: the dispatch is async, so a
    # late error/wedge must land under the watchdog, not at the caller
    status, out = circuit.device_call(
        family, lambda: np.asarray(gf.gf_matmul_tpu(mat, data)),
        batch=batch, label="xla-direct", oom_to_fail=True)
    return out if status == "ok" else None


def gf_repair_matmul(mat: np.ndarray, data: np.ndarray,
                     use_tpu: bool = True, min_bytes: int = 1,
                     sig: Optional[str] = None,
                     family: str = "ec-repair") -> np.ndarray:
    """Repair-kind twin of gf_matmul for the regenerating-code path:
    helper-side projections (1 x alpha) and primary-side
    reconstructions (alpha x d) dispatch through the `repair` plan
    kind (ec/plan.py), where the small per-erasure-pattern matrix is
    a compile-time constant baked into the trace — memoized by codec
    signature + erasure pattern.  Rides its own `ec-repair` breaker
    family so a repair-path fault never degrades the encode/decode
    data path; while degraded (or when the guarded dispatch fails)
    the call takes the bit-exact numpy host fold below, so callers
    NEVER see a device error from this entry.
    """
    if use_tpu and gf.backend_available() and data.size >= min_bytes:
        if not circuit.degraded(family):
            from ceph_tpu.ec import plan

            out = plan.repair(mat, data, sig=sig, family=family)
            if out is not None:
                return out
        else:
            circuit.breaker(family).note_fallback()
    if data.ndim == 2:
        return gf.gf_matmul_host(mat, data)
    b, k, s = data.shape
    flat = np.ascontiguousarray(np.moveaxis(data, 1, 0)).reshape(k, b * s)
    par = gf.gf_matmul_host(mat, flat)
    return np.moveaxis(par.reshape(-1, b, s), 0, 1)


class LruCache:
    """Tiny bounded LRU (decode tables keyed by erasure signature,
    GF multiply tables, compiled ExecPlans).  Overflow evicts the
    least-recently-used entry only — never the whole store."""

    def __init__(self, cap: int = 256):
        self._store: OrderedDict = OrderedDict()
        self.cap = cap

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._store

    _MISS = object()

    def peek(self, key: Hashable, default=None):
        """Lookup + LRU touch without computing on miss (callers that
        must build outside a lock pair this with put)."""
        hit = self._store.get(key, self._MISS)
        if hit is self._MISS:
            return default
        self._store.move_to_end(key)
        return hit

    def put(self, key: Hashable, value) -> None:
        self._store[key] = value
        self._store.move_to_end(key)
        if len(self._store) > self.cap:
            self._store.popitem(last=False)

    def pop(self, key: Hashable, default=None):
        """Evict one entry (the poisoned-plan quarantine path)."""
        return self._store.pop(key, default)

    def clear(self) -> None:
        self._store.clear()

    def get_or_compute(self, key: Hashable, compute: Callable):
        hit = self._store.get(key, self._MISS)
        if hit is not self._MISS:
            self._store.move_to_end(key)
            return hit
        value = compute()
        self.put(key, value)
        return value


# ---------------------------------------------------------------------------
# Shared decode-rows cache
# ---------------------------------------------------------------------------

# Inverted decode submatrices keyed by (codec signature, survivors,
# erasures) — PROCESS-wide, not per codec instance: pool remounts and
# registry re-resolution build fresh codec objects for identical
# profiles, and a per-instance cache made each of them re-run the
# GF(2) Gaussian elimination for every erasure pattern it had already
# seen.  The signature (xsched.matrix_signature over the generator +
# geometry) makes identical profiles collide on purpose and distinct
# ones never.
_decode_rows = LruCache(cap=512)
_decode_rows_stats = {"hits": 0, "misses": 0}
# decode runs on asyncio.to_thread executor threads (the encode
# service's off-loop workers) as well as the event loop: peek()'s
# get-then-move_to_end is not atomic under concurrent eviction, so
# the process-wide cache takes a lock (the inversion itself runs
# OUTSIDE it — Gaussian elimination can take milliseconds)
_decode_rows_lock = threading.Lock()


def shared_decode_rows(key: Hashable, compute: Callable):
    """Fetch (or invert-and-cache) decode rows for one (codec sig,
    erasure pattern); counters feed decode_rows_stats() so the
    cross-instance reuse is observable."""
    with _decode_rows_lock:
        hit = _decode_rows.peek(key, LruCache._MISS)
        if hit is not LruCache._MISS:
            _decode_rows_stats["hits"] += 1
            return hit
        _decode_rows_stats["misses"] += 1
    value = compute()
    with _decode_rows_lock:
        _decode_rows.put(key, value)
    return value


def decode_rows_stats() -> dict:
    with _decode_rows_lock:
        return {**_decode_rows_stats, "entries": len(_decode_rows)}
