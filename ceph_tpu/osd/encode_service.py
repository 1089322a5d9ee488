"""Async micro-batching encode service for the OSD write path.

PR 2 made EC encode compile-once/dispatch-few (ec/plan.py), but every
client write still called `ec_util.encode_with_hinfo` synchronously,
one object at a time, on the asyncio event loop.  This service is the
missing layer between the cluster datapath and the batched kernels:
concurrent write handlers **await** their encodes here, requests
pool while a dispatch is in flight (an idle bucket dispatches
immediately — adaptive group commit, so the small-op band never
pays an accumulation wait it can't amortize; a ~1ms window and a
byte budget bound the pooling), then ONE flush dispatches the batch
through the plan-cached fused encode+crc path **off-loop**
(asyncio.to_thread, the event loop never blocks on the device) and
resolves each request's future with its own shards + hinfo CRCs.

Pipelining is double-buffered: each profile bucket holds two dispatch
slots, so while batch N computes on device, batch N+1 accumulates and
the sub-write network fan-out of already-completed ops overlaps the
next dispatch.

Mesh scale-out: each flush picks mesh vs single-device through the
plan cache (ec/plan.py) — a batch past the CEPH_TPU_MESH_MIN_BYTES /
_MIN_STRIPES gates shards stripe-parallel over the live healthy chip
mesh, and a sick chip shrinks the mesh (never degrades the flush to
host).  The `mesh_batches` counter reports how many flushes rode the
mesh.

Knobs (read at construction):

  CEPH_TPU_ENCODE_BATCH_WINDOW_MS  accumulation upper bound (the
                                   common path is the adaptive
                                   idle/completion flush), default 1.0
  CEPH_TPU_ENCODE_BATCH_BYTES      flush early once this many bytes
                                   are pending (default 8 MiB)
  CEPH_TPU_ENCODE_SERVICE=0        kill switch — every call runs the
                                   inline (pre-service) path, results
                                   and behavior unchanged from the
                                   un-batched daemon

Degradation policy: batching engages when a batched tier can — the
fused device tier (ec_util.device_fused_available) or, for the
bitmatrix family on the hinfo write path, the packed native XOR-tape
tier (ec_util.bitmatrix_native_available: N objects' regions pack
into ONE arena and the whole bucket executes as a single compiled
tape run, per-shard CRC ledger folded natively over arena spans).
On CPU-only runs with neither tier every request takes the inline
path, so existing behavior is untouched.  Backpressure is a bounded queue
per profile (requests + bytes, counting in-flight batches); overflow
**sheds to the inline path** instead of queueing unboundedly, so a
storm degrades to today's latency rather than deadlocking.

Threading: all bookkeeping (buckets, counters, histograms) runs on
the owning event loop; only the numeric batch body runs in the
to_thread worker, so no lock is needed.
"""

from __future__ import annotations

import asyncio
import os

from ceph_tpu.common import flags
import time
from typing import Dict, Iterable, List, Optional

from ceph_tpu.common import tracing
from ceph_tpu.osd import ec_util, scheduler

__all__ = ["EncodeService"]


def _env_float(name: str, default: float) -> float:
    try:
        return flags.flag_float(name, default)
    except ValueError:
        return default


def _pow2_bucket(n: int) -> int:
    """Histogram bucket for batch sizes: next power of two."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def _buf(d):
    """Pass buffers through to ec_util unchanged; materialize only
    non-buffer payloads.  The old `bytes(d)`-unless-bytes guard copied
    every memoryview payload once per inline encode — unnecessary: the
    write path snapshots caller-mutable buffers BEFORE the service
    sees them (`_op_write_full_locked`/`_op_write`), so a view here is
    already stable, and ec_util slices views zero-copy."""
    return d if isinstance(d, (bytes, bytearray, memoryview)) \
        else bytes(d)


class _Req:
    __slots__ = ("fut", "payload", "nbytes", "t_q", "span_ctx")

    def __init__(self, fut: asyncio.Future, payload, nbytes: int):
        self.fut = fut
        self.payload = payload
        self.nbytes = nbytes
        self.t_q = time.perf_counter()
        # the enqueuing op's span context: the batched flush span
        # LINKS to every op it served (N ops -> 1 device dispatch)
        span = tracing.current_span.get()
        self.span_ctx = span.context if span is not None else None


class _Bucket:
    """Accumulation queue for one (kind, codec profile, geometry)."""

    __slots__ = ("kind", "label", "sinfo", "codec", "pending",
                 "nbytes", "outstanding", "outstanding_bytes",
                 "timer", "sem", "stats", "in_flight", "tier",
                 "last_arrival", "ewma_gap")

    def __init__(self, kind: str, label: str, sinfo, codec,
                 tier: str = "device"):
        self.kind = kind
        self.label = label
        self.sinfo = sinfo
        self.codec = codec
        self.tier = tier
        self.pending: List[_Req] = []
        self.nbytes = 0
        self.outstanding = 0          # queued + in-flight requests
        self.outstanding_bytes = 0
        self.in_flight = 0            # dispatched batches not yet done
        # arrival-density tracking (the bitmatrix hot/cold router),
        # keyed by mClock class: a recovery wave's dense arrivals
        # must not mark the bucket hot for sparse client writes (and
        # a client trickle must not mask a forming recovery batch)
        self.last_arrival: Dict[str, float] = {}
        self.ewma_gap: Dict[str, float] = {}
        self.timer: Optional[asyncio.TimerHandle] = None
        # two dispatch slots: the double buffer — batch N on device,
        # batch N+1 accumulating/launching behind it
        self.sem = asyncio.Semaphore(2)
        # queue_seconds: each request's wait from enqueue to the start
        # of its batch's dispatch, summed exactly
        self.stats: Dict[str, object] = {
            "requests": 0, "batches": 0, "dispatch_seconds": 0.0,
            "queue_seconds": 0.0,
            "batch_size_hist": {}, "fill_pct_hist": {},
        }


class EncodeService:
    """Per-codec-profile micro-batching encode/decode front end."""

    def __init__(self, who: str = "osd",
                 window_ms: Optional[float] = None,
                 max_batch_bytes: Optional[int] = None,
                 max_queue_requests: int = 256,
                 max_queue_bytes: Optional[int] = None):
        self.who = who
        self.enabled = flags.enabled("CEPH_TPU_ENCODE_SERVICE")
        if window_ms is None:
            window_ms = _env_float("CEPH_TPU_ENCODE_BATCH_WINDOW_MS",
                                   1.0)
        self.window_s = max(float(window_ms), 0.0) / 1e3
        if max_batch_bytes is None:
            max_batch_bytes = int(_env_float(
                "CEPH_TPU_ENCODE_BATCH_BYTES", float(8 << 20)))
        self.max_batch_bytes = max(int(max_batch_bytes), 1)
        self.max_queue_requests = max(int(max_queue_requests), 1)
        self.max_queue_bytes = int(max_queue_bytes
                                   if max_queue_bytes is not None
                                   else 4 * self.max_batch_bytes)
        self._buckets: Dict[tuple, _Bucket] = {}
        self._tasks: set = set()
        self._closed = False
        # set by the owning daemon: flush dispatch spans (with links
        # to the ops each batch served) land in this tracer's ring
        self.tracer = None
        self._usable_cache: Dict[int, str] = {}
        self.counters = {"requests": 0, "batched": 0, "inline": 0,
                         "inline_cold": 0, "shed": 0, "batches": 0,
                         "dispatch_errors": 0, "device_fallback": 0,
                         "mesh_batches": 0}

    # -- public API (the daemon's awaited entry points) -------------------

    async def encode_with_hinfo(self, sinfo, codec, data,
                                want: Iterable[int],
                                logical_len: Optional[int] = None):
        """Awaitable twin of ec_util.encode_with_hinfo — identical
        results, but concurrent callers share device dispatches."""
        want = tuple(want)
        self.counters["requests"] += 1
        q = self._bucket_for("encode_hinfo", sinfo, codec)
        if q is not None and self._cold_inline(q):
            self.counters["inline_cold"] += 1
            q = None
        if q is None or not self._admit(q, len(data)):
            self.counters["inline" if q is None else "shed"] += 1
            # intentionally-inline path (kill switch, no batchable
            # tier, a cold bitmatrix bucket, or backpressure shed).
            # The span names the stage — inline codec work must be
            # attributable in the histograms (the xsched bench cites
            # it), not folded invisibly into osd_op self-time
            with tracing.child_span_sync("encode_inline"):
                return ec_util.encode_with_hinfo(
                    sinfo, codec, data, want, logical_len=logical_len)
        return await self._enqueue(q, (data, want, logical_len),
                                   len(data))

    async def encode(self, sinfo, codec, data,
                     want: Iterable[int]) -> Dict[int, bytes]:
        """Awaitable twin of ec_util.encode (plain shards, no hinfo:
        the RMW re-encode and recovery re-encode path)."""
        want = tuple(want)
        self.counters["requests"] += 1
        q = self._bucket_for("encode", sinfo, codec)
        if q is None or not self._admit(q, len(data)):
            self.counters["inline" if q is None else "shed"] += 1
            with tracing.child_span_sync("encode_inline"):
                return ec_util.encode(sinfo, codec, _buf(data), want)
        return await self._enqueue(q, (data, want), len(data))

    async def decode(self, sinfo, codec, to_decode) -> bytes:
        """Awaitable twin of ec_util.decode: concurrent reads and
        recovery reconstructions sharing a survivor set batch into one
        device dispatch (the decode_many service path)."""
        self.counters["requests"] += 1
        nbytes = sum(len(v) for v in to_decode.values())
        k = codec.get_data_chunk_count()
        # all data shards present = pure host interleave, no device
        # work to batch — keep it inline (the common read fast path)
        all_data = not codec.get_chunk_mapping() and \
            all(i in to_decode for i in range(k))
        q = None if all_data else self._bucket_for("decode", sinfo,
                                                   codec)
        if q is None or not self._admit(q, nbytes):
            self.counters["inline" if q is None else "shed"] += 1
            with tracing.child_span_sync("decode_inline"):
                return ec_util.decode(sinfo, codec, to_decode)
        return await self._enqueue(q, dict(to_decode), nbytes)

    async def decode_many(self, sinfo, codec, maps) -> list:
        """N decode requests at once (the recovery-wave entry):
        returns one outcome per request — the decoded bytes, or the
        Exception that request raised (callers isolate failures per
        object).  Batchable requests enqueue individually and group in
        the flush; the inline tier keeps today's one-host-fold-per-
        survivor-group behavior via ec_util.decode_many."""
        maps = list(maps)
        if not maps:
            return []
        q = self._bucket_for("decode", sinfo, codec)
        if q is not None:
            return await asyncio.gather(
                *(self.decode(sinfo, codec, m) for m in maps),
                return_exceptions=True)
        self.counters["requests"] += len(maps)
        self.counters["inline"] += len(maps)
        try:
            return ec_util.decode_many(sinfo, codec, maps)
        except Exception:
            outs: list = []
            for m in maps:
                try:
                    outs.append(ec_util.decode(sinfo, codec, m))
                except Exception as e:
                    outs.append(e)
            return outs

    async def stop(self) -> None:
        """Flush everything pending and await in-flight dispatches —
        every caller blocked on a future resolves (no deadlock);
        requests arriving after stop() run inline."""
        self._closed = True
        for q in list(self._buckets.values()):
            self._flush(q)
        while self._tasks:
            await asyncio.gather(*list(self._tasks),
                                 return_exceptions=True)

    def stats(self) -> dict:
        """Observability snapshot: aggregate counters, live queue
        depth, and per-profile batch-size / fill-ratio histograms with
        the exact queue-wait and dispatch second sums (the
        admin-socket `encode_service` command and the bench contract
        line surface this)."""
        return {
            "enabled": self.enabled,
            **self.counters,
            "queue_depth": sum(q.outstanding
                               for q in self._buckets.values()),
            "queue_bytes": sum(q.outstanding_bytes
                               for q in self._buckets.values()),
            "window_ms": self.window_s * 1e3,
            "max_batch_bytes": self.max_batch_bytes,
            "profiles": {q.label: {k: (dict(v) if isinstance(v, dict)
                                       else v)
                                   for k, v in q.stats.items()}
                         for q in self._buckets.values()},
        }

    # -- internals --------------------------------------------------------

    def _usable(self, codec) -> str:
        """The batching tier this codec can ride: "device" (fused
        encode+crc plan), "bitmatrix" (packed native XOR tape —
        ec_util._encode_many_bitmatrix), or "" (inline only)."""
        if not self.enabled or self._closed:
            return ""
        key = id(codec)
        hit = self._usable_cache.get(key)
        if hit is None:
            if ec_util.device_fused_available(codec):
                hit = "device"
            elif ec_util.bitmatrix_native_available(codec):
                hit = "bitmatrix"
            else:
                hit = ""
            self._usable_cache[key] = hit
        return hit

    def _bucket_for(self, kind: str, sinfo, codec
                    ) -> Optional[_Bucket]:
        tier = self._usable(codec)
        if not tier:
            return None
        # the packed native tape tier only exists for the hinfo write
        # path: plain encode / decode stay inline for bitmatrix
        if tier == "bitmatrix" and kind != "encode_hinfo":
            return None
        if kind == "decode" and not hasattr(codec, "decode_batch"):
            return None
        sig = codec.plan_signature() if hasattr(codec,
                                                "plan_signature") \
            else getattr(codec, "_sig", None) or str(id(codec))
        key = (kind, sig, sinfo.get_stripe_width(),
               sinfo.get_chunk_size())
        q = self._buckets.get(key)
        if q is None:
            label = f"{kind}[{sig[:8]}] w{sinfo.get_stripe_width()}" \
                    f" c{sinfo.get_chunk_size()}"
            q = _Bucket(kind, label, sinfo, codec, tier=tier)
            self._buckets[key] = q
        return q

    def _admit(self, q: _Bucket, nbytes: int) -> bool:
        """Backpressure: bound queued + in-flight work per profile."""
        return (q.outstanding < self.max_queue_requests
                and q.outstanding_bytes + nbytes
                <= self.max_queue_bytes)

    def _cold_inline(self, q: _Bucket) -> bool:
        """Hot/cold router for the packed bitmatrix tape tier.  A
        singleton tape batch pays the off-loop hop (task + to_thread
        round trip, ~ms under load) to save ~0.1 ms of codec work —
        a pure loss, so a COLD bucket (observed inter-arrival EWMA
        wider than the batch window) runs the encode inline on the
        caller, where the fused native tape is still one C++ call.
        Once arrivals pack well inside the window (a true burst — the
        hot bar is a quarter-window, so Poisson flukes at light load
        don't seed doomed singleton batches) — or a batch is already
        pooling/in flight to join — requests take the packed
        multi-object path.  The device tier never routes here: its
        per-op dispatch cost is exactly what batching amortizes."""
        if q.tier != "bitmatrix":
            return False
        # per-mClock-class arrival density: the op's scheduler class
        # rides the contextvar set by scheduler.run() ('' outside any
        # grant); tenant classes fold so the dicts stay bounded
        cls = scheduler.stage_class(scheduler.current_class())
        now = time.perf_counter()
        last = q.last_arrival.get(cls)
        if last is not None:
            gap = now - last
            prev = q.ewma_gap.get(cls)
            q.ewma_gap[cls] = gap if prev is None \
                else 0.5 * prev + 0.5 * gap
        q.last_arrival[cls] = now
        if q.pending or q.in_flight:
            return False        # a batch is forming: join it
        gap = q.ewma_gap.get(cls)
        return gap is None or gap > self.window_s / 4.0

    async def _enqueue(self, q: _Bucket, payload, nbytes: int):
        loop = asyncio.get_running_loop()
        req = _Req(loop.create_future(), payload, nbytes)
        q.pending.append(req)
        q.nbytes += nbytes
        q.outstanding += 1
        q.outstanding_bytes += nbytes
        q.stats["requests"] += 1                # type: ignore[operator]
        self.counters["batched"] += 1
        if (q.nbytes >= self.max_batch_bytes or self.window_s == 0.0
                or q.in_flight == 0):
            # adaptive group commit: an idle bucket dispatches NOW —
            # the small-op band must not pay the accumulation window
            # when there is nothing to accumulate behind.  Batching
            # still emerges under pressure: while a dispatch is in
            # flight, arrivals pool here and the completion hook in
            # _dispatch flushes them as one batch.
            self._flush(q)
        elif q.timer is None:
            # upper bound only — the completion-triggered flush is
            # the common path; the timer catches a wedged dispatch
            q.timer = loop.call_later(self.window_s, self._flush, q)
        # accumulation wait + shared dispatch, as the op saw it: one
        # stage span from enqueue to future resolution
        wait_span = tracing.start_child("encode_wait", kind=q.kind)
        try:
            return await req.fut
        except asyncio.CancelledError:
            wait_span.set_attr("cancelled", True)
            raise
        finally:
            wait_span.finish()

    def _flush(self, q: _Bucket) -> None:
        if q.timer is not None:
            q.timer.cancel()
            q.timer = None
        if not q.pending:
            return
        batch, q.pending = q.pending, []
        nbytes, q.nbytes = q.nbytes, 0
        q.in_flight += 1
        task = asyncio.get_running_loop().create_task(
            self._dispatch(q, batch, nbytes))
        self._tasks.add(task)

        def _done(t, q=q):
            self._tasks.discard(t)
            q.in_flight -= 1
            # completion-triggered flush: everything that pooled
            # while this batch computed goes out as the next batch
            if q.pending and not self._closed:
                self._flush(q)
        task.add_done_callback(_done)

    async def _dispatch(self, q: _Bucket, batch: List[_Req],
                        nbytes: int) -> None:
        async with q.sem:   # double buffer: at most 2 batches in flight
            t0 = time.perf_counter()
            waits = [t0 - r.t_q for r in batch]
            # the batched device dispatch is ONE span serving N ops:
            # span LINKS carry the attribution (it parents none of
            # them — their own encode_wait spans cover the wall time)
            flush_span = self.tracer.start(
                f"encode_flush {q.label}") if self.tracer is not None \
                else tracing.NULL_SPAN
            flush_span.set_attr("requests", len(batch))
            flush_span.set_attr("bytes", nbytes)
            for r in batch:
                flush_span.link(r.span_ctx)
            token = tracing.current_span.set(flush_span) \
                if flush_span else None
            # the dispatch's stages, as children of the flush span:
            # handoff (loop -> worker), pack, guard, launch, fetch,
            # fold, resume (worker -> loop); they divide dt
            stages = tracing.Stages(flush_span) if flush_span else None
            stoken = tracing.current_dispatch.set(stages)
            try:
                if stages is not None:
                    stages.mark("dispatch_handoff")
                try:
                    outs = await asyncio.to_thread(
                        self._run_batch, q,
                        [r.payload for r in batch])
                except BaseException as e:
                    self.counters["dispatch_errors"] += 1
                    outs = [e] * len(batch)
                if stages is not None:
                    stages.close()
                dt = time.perf_counter() - t0
                flush_span.set_attr("dispatch_ms", round(dt * 1e3, 3))
            finally:
                tracing.current_dispatch.reset(stoken)
                if token is not None:
                    tracing.current_span.reset(token)
                if stages is not None:
                    stages.close()      # no-op unless the hop raised
                if self.tracer is not None:
                    self.tracer.finish(flush_span)
            if stages is not None:
                # the root's own self-time is not a stage: encode_flush
                # never reaches the histograms
                self.tracer.record_stages(stages.stage_us())
                for w in waits:
                    self.tracer.record_stages(
                        {"encode_queue": int(w * 1e6)})
            self.counters["batches"] += 1
            q.stats["batches"] += 1             # type: ignore[operator]
            q.stats["dispatch_seconds"] += dt   # type: ignore[operator]
            q.stats["queue_seconds"] += sum(waits)  # type: ignore[operator]
            sh = q.stats["batch_size_hist"]
            sk = str(_pow2_bucket(len(batch)))
            sh[sk] = sh.get(sk, 0) + 1
            fh = q.stats["fill_pct_hist"]
            fill = min(nbytes * 100 // self.max_batch_bytes, 100)
            fk = str(min((fill // 10) * 10 + 10, 100))
            fh[fk] = fh.get(fk, 0) + 1
            for r, out in zip(batch, outs):
                q.outstanding -= 1
                q.outstanding_bytes -= r.nbytes
                if r.fut.done():
                    continue
                if isinstance(out, BaseException):
                    r.fut.set_exception(out)
                else:
                    r.fut.set_result(out)

    def _run_batch(self, q: _Bucket, payloads: list) -> list:
        """Thread-side batch body: one fused dispatch for the whole
        batch.  Flush-failure semantics: a DEVICE fault during the
        batch must never surface on the per-request futures — the
        whole accumulated batch sheds to the inline path, where the
        breaker guard (common/circuit.py) degrades each item to the
        bit-exact numpy host tier; only genuine host-path errors (bad
        geometry, malformed payloads) reach a future.  Device trouble
        during the flush — a batch-level exception OR guard-level
        fallbacks recorded while it ran — counts once under
        device_fallback."""
        from ceph_tpu.common import circuit
        from ceph_tpu.ec import plan as ec_plan

        stages = tracing.current_dispatch.get()
        if stages is not None:
            # host work up to the device call; the plan marks the call
            # itself and the fold after it (ec/plan._guarded)
            stages.mark("dispatch_pack", annotated=True)
        # scoped to the EC families this batch can actually touch — an
        # unscoped delta would attribute a concurrent hitset/CRUSH
        # fault to this flush
        fams = ("ec-encode", "ec-decode", "fused-crc")
        faults_before = circuit.fault_events(fams)
        # whether THIS flush rode the multi-chip mesh (plan.py picks
        # mesh vs single-device per flush from batch size + mesh
        # health; the delta surfaces the choice per batch)
        mesh_before = ec_plan.mesh_dispatches()
        outs: Optional[list] = None
        try:
            if q.kind == "encode_hinfo":
                outs = ec_util.encode_many_with_hinfo(
                    q.sinfo, q.codec, payloads)
            elif q.kind == "encode":
                outs = ec_util.encode_many(
                    q.sinfo, q.codec, [p[0] for p in payloads],
                    [p[1] for p in payloads])
            else:
                outs = ec_util.decode_many(q.sinfo, q.codec, payloads)
        except Exception:
            # shed the batch to the inline host path: per-item, so one
            # bad request cannot fail its neighbours, and each retry
            # rides the guard's host degradation
            outs = []
            for p in payloads:
                try:
                    outs.append(self._run_one(q, p))
                except Exception as e:
                    outs.append(e)
        if circuit.fault_events(fams) > faults_before:
            self.counters["device_fallback"] += 1
        if ec_plan.mesh_dispatches() > mesh_before:
            self.counters["mesh_batches"] += 1
        if stages is not None:
            stages.mark("dispatch_resume")
        return outs

    def _run_one(self, q: _Bucket, payload):
        if q.kind == "encode_hinfo":
            d, w, l = payload
            return ec_util.encode_with_hinfo(q.sinfo, q.codec, d, w,
                                             logical_len=l)
        if q.kind == "encode":
            d, w = payload
            return ec_util.encode(q.sinfo, q.codec, _buf(d), w)
        return ec_util.decode(q.sinfo, q.codec, payload)
