"""Distributed tracing spans (blkin/zipkin role) + critical-path
attribution: one client op's trace context propagates client ->
primary -> replica sub-ops, each daemon's collected spans link into a
tree by parent span id, the critical-path reducer attributes every
instant of a finished op to exactly one stage, and the tail keeps its
full explanation (exemplar retention) even at head-sample rate 0.

Mirrors the reference's blkin tracepoint coverage
(/root/reference/src/blkin/, osd_blkin_trace_all): the point is the
CAUSAL CHAIN across daemons, not any single daemon's log."""

import asyncio
import os
import time

import numpy as np
import pytest

from cluster_helpers import Cluster

from ceph_tpu.common import tracing
from ceph_tpu.common.tracing import (
    NULL_SPAN, Tracer, critical_path, critical_path_spans,
    current_span,
)

EC22 = {"plugin": "ec_jax", "technique": "reed_sol_van",
        "k": "2", "m": "2", "crush-failure-domain": "osd"}


class _FullDraw:
    """A messenger delay source that always draws the whole injected
    delay (the stock one draws uniformly below it)."""

    def random(self):
        return 1.0


def _span(sid, parent, name, t0, dur, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name,
            "t0_us": t0, "duration_us": dur, "attrs": attrs}


def test_stages_divide_the_interval_across_threads():
    """Marks from several threads make consecutive child spans of the
    root with no gap or overlap; a renamed stage keeps its time under
    its new name; marks after close are dropped; with no profiler
    running an annotated stage opens nothing."""
    import threading

    root = Tracer("svc").start("encode_flush x")
    st = tracing.Stages(root)
    st.mark("dispatch_handoff")
    t = threading.Thread(target=lambda: (
        st.mark("dispatch_pack", annotated=True),
        st.mark("dispatch_guard"),
        st.mark("dispatch_fold", annotated=True),
        st.mark("dispatch_guard", rename=("dispatch_fold",
                                          "dispatch_pack")),
        st.mark("dispatch_fold", annotated=True),
        st.mark("dispatch_resume")))
    t.start()
    t.join(10)
    assert not t.is_alive()
    st.close()
    st.mark("dispatch_late")
    names = [sp.name for sp in st.spans]
    assert names == ["dispatch_handoff", "dispatch_pack", "dispatch_guard",
                     "dispatch_pack", "dispatch_guard", "dispatch_fold",
                     "dispatch_resume"]
    for a, b in zip(st.spans, st.spans[1:]):
        assert a._end == b._t0
    assert all(sp.parent_id == root.span_id for sp in st.spans)
    us = st.stage_us()
    assert set(us) == set(names)
    span_us = (st.spans[-1]._end - st.spans[0]._t0) * 1e6
    assert sum(us.values()) == pytest.approx(span_us, abs=len(us))
    assert tracing.annotate("ceph.x").__class__.__name__ == "nullcontext"


def test_tracer_unit():
    t = Tracer("svc", max_spans=4)
    root = t.start("root")
    assert root.trace_id and root.span_id and root.parent_id == 0
    child = t.start("child", context=root.context)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    child.event("did a thing")
    t.finish(child)
    t.finish(root)
    spans = t.dump()
    assert len(spans) == 2
    assert spans[0]["name"] == "child"
    assert spans[0]["parent_id"] == spans[1]["span_id"]
    assert spans[0]["events"][0]["what"] == "did a thing"
    assert spans[0]["duration_us"] >= 0
    # ring bound: old spans fall off
    for i in range(10):
        t.finish(t.start(f"s{i}"))
    assert len(t.dump()) == 4
    # trace_id filter
    only = t.dump(trace_id=root.trace_id)
    assert all(s["trace_id"] == f"{root.trace_id:016x}" for s in only)


def test_contextvar_isolation():
    """Two concurrent tasks each see their OWN current span."""
    async def run():
        t = Tracer("svc")
        seen = {}

        async def task(name):
            span = t.start(name)
            current_span.set(span)
            await asyncio.sleep(0.01)
            seen[name] = current_span.get().name

        await asyncio.gather(task("a"), task("b"))
        assert seen == {"a": "a", "b": "b"}

    asyncio.run(run())


def test_trace_propagates_client_to_replicas():
    async def run():
        cluster = Cluster(num_osds=3, osds_per_host=1)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "tp", size=3, pg_num=4)
            io = cluster.client.open_ioctx("tp")
            cluster.client.trace_all = True
            await io.write_full("traced-obj", b"x" * 8192)
            cluster.client.trace_all = False

            client_spans = cluster.client.tracer.dump()
            assert client_spans, "client recorded no spans"
            cspan = next(s for s in client_spans
                         if "traced-obj" in s["name"])
            trace_id = cspan["trace_id"]
            assert any("sent to osd" in e["what"]
                       for e in cspan["events"])

            # gather every OSD's spans for this trace over the tell
            # surface (the dump_traces asok command)
            by_osd = {}
            for osd in range(3):
                rc, doc = await cluster.client.osd_command(
                    osd, {"prefix": "dump_traces",
                          "trace_id": trace_id})
                assert rc == 0
                by_osd[osd] = doc["spans"]
            all_spans = [s for spans in by_osd.values()
                         for s in spans]
            assert all(s["trace_id"] == trace_id for s in all_spans)

            # primary op span: parented by the CLIENT span
            op_spans = [s for s in all_spans
                        if s["name"].startswith("osd_op")]
            assert len(op_spans) == 1, op_spans
            assert op_spans[0]["parent_id"] == cspan["span_id"]

            # the primary's per-peer subwrite stage spans (the ack
            # wait) parent to the op span...
            sub_local = [s for s in all_spans
                         if s["name"].startswith("subwrite")]
            assert len(sub_local) >= 2, sub_local
            for s in sub_local:
                assert s["parent_id"] == op_spans[0]["span_id"]
            # ...and replica sub-writes parent to the PER-PEER span
            # (the v3 tail field carried the sub-write span's context),
            # on size=3 at least the two REMOTE replicas contributed
            local_ids = {s["span_id"] for s in sub_local}
            sub_spans = [s for s in all_spans
                         if s["name"].startswith("sub_write")
                         and "_rbgen_" not in s["name"]]
            assert len(sub_spans) >= 2, sub_spans
            for s in sub_spans:
                assert s["parent_id"] in local_ids, s
            # the awaited rollback-trim removes attribute to their own
            # stage span, not to osd_op self-time
            trim = [s for s in all_spans if s["name"] == "rollback_trim"]
            rb_remote = [s for s in all_spans
                         if s["name"].startswith("sub_write")
                         and "_rbgen_" in s["name"]]
            if rb_remote:
                trim_ids = {s["span_id"] for s in trim}
                for s in rb_remote:
                    assert s["parent_id"] in trim_ids, s
            # spans came from more than one daemon
            contributing = {osd for osd, spans in by_osd.items()
                            if spans}
            assert len(contributing) >= 2, by_osd
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(run(), 120))


# -- critical-path reducer -------------------------------------------------


def test_critical_path_hedged_children():
    """Parallel hedged sub-reads: the LONGEST child owns the wait, the
    cancelled straggler is off the path even though it spans the whole
    op, and the gaps are the parent's self-time."""
    tree = [
        _span("r", "", "osd_op obj", 0, 10_000),
        _span("q", "r", "queue.client", 0, 2_000),
        # three parallel sub-reads from t=2ms: 3ms, 7ms, and a
        # straggler cancelled at 9.5ms (nothing waited for it)
        _span("a", "r", "subread osd.1", 2_000, 3_000),
        _span("b", "r", "subread osd.2", 2_000, 7_000),
        _span("c", "r", "subread osd.3", 2_000, 7_500,
              cancelled=True),
    ]
    cp = critical_path(tree)
    assert cp["total_us"] == 10_000
    # b (ends 9ms) is the latest-ending live child; a is fully
    # shadowed by b; the root keeps [9, 10]ms = 1ms self
    assert cp["stages"] == {"queue.client": 2_000, "subread": 7_000,
                            "osd_op": 1_000}
    names = [e["name"] for e in cp["path"]]
    assert "subread osd.2" in names
    assert "subread osd.3" not in names  # cancelled: off the path
    assert "subread osd.1" not in names  # shadowed by the longer read
    # path is root-first
    assert names[0] == "osd_op obj"


def test_critical_path_nested_and_sequential():
    """Sequential children hand the cursor back through the parent;
    a grandchild attributes inside its parent's interval."""
    tree = [
        _span("r", "", "osd_op w", 0, 12_000),
        _span("e", "r", "encode_wait x", 1_000, 4_000),
        _span("s", "r", "subwrite osd.1", 6_000, 5_000),
        _span("k", "s", "kv_commit", 7_000, 2_000),
    ]
    cp = critical_path(tree)
    assert cp["stages"]["encode_wait"] == 4_000
    assert cp["stages"]["kv_commit"] == 2_000
    assert cp["stages"]["subwrite"] == 3_000       # 5ms minus the kv
    assert cp["stages"]["osd_op"] == 3_000         # the gaps
    assert sum(cp["stages"].values()) == cp["total_us"]


def test_critical_path_spans_fast_lane_matches_dicts():
    """The allocation-light Span-tree reduction and the dict-based
    reducer agree on the same tree."""
    tr = Tracer("svc")
    root = tr.start("osd_op o")
    q = root.child("queue.client")
    time.sleep(0.002)
    q.finish()
    a = root.child("subread osd.1")
    b = root.child("subread osd.2")
    time.sleep(0.002)
    a.finish()
    b.set_attr("cancelled", True)
    b.finish()
    time.sleep(0.001)
    tr.finish(root)
    fast = critical_path_spans(root)
    slow = critical_path(root.tree_dicts())
    assert fast["stages"] == slow["stages"]
    assert fast["total_us"] == slow["total_us"]
    assert fast["path"] == []          # fast lane skips the rendering
    assert slow["path"]


def test_span_clocks_survive_wall_clock_step(monkeypatch):
    """Satellite regression: durations come from time.monotonic();
    an NTP step mid-span (time.time jumping backward) must not
    corrupt them — the wall clock is a display anchor only."""
    tr = Tracer("svc")
    span = tr.start("osd_op o")
    span.event("before step")
    # simulate a 1-hour backward NTP step
    real_time = time.time
    monkeypatch.setattr(time, "time", lambda: real_time() - 3600.0)
    time.sleep(0.005)
    tr.finish(span)
    d = span.to_dict()
    assert d["duration_us"] >= 5_000           # monotonic, unpoisoned
    assert d["duration_us"] < 60_000_000
    assert d["events"][0]["offset_us"] >= 0


def test_child_span_helpers_and_null_discipline():
    """child_span/child_span_sync attach to the current span, finish
    on every path (incl. cancellation, annotated), and no-op cleanly
    when untraced."""
    async def main():
        tr = Tracer("svc")
        root = tr.start("osd_op o")
        tok = current_span.set(root)
        try:
            async with tracing.child_span("stagea") as sp:
                assert current_span.get() is sp
            with tracing.child_span_sync("stageb", k=1) as sp2:
                assert sp2.attrs["k"] == 1

            async def cancelled_stage():
                async with tracing.child_span("stagec"):
                    await asyncio.sleep(30)

            t = asyncio.get_running_loop().create_task(
                cancelled_stage())
            await asyncio.sleep(0.01)
            t.cancel()
            with pytest.raises(asyncio.CancelledError):
                await t
        finally:
            current_span.reset(tok)
        tr.finish(root)
        names = {s.name: s for s in root._tree}
        assert {"stagea", "stageb", "stagec"} <= set(names)
        assert names["stagec"].attrs.get("cancelled") is True
        # untraced context: helpers yield the NULL_SPAN, nothing leaks
        assert current_span.get() is None
        async with tracing.child_span("ghost") as ghost:
            assert ghost is NULL_SPAN
        assert tracing.start_child("ghost2") is NULL_SPAN
        tracing.event("into the void")  # must not raise

    asyncio.run(main())


def test_kill_switch_and_sampling(monkeypatch):
    """CEPH_TPU_TRACE=0 makes start() return the NULL_SPAN; sample
    rate 0 still BUILDS spans (stage histograms + tail exemplars need
    them) but retains nothing in the ring."""
    monkeypatch.setenv("CEPH_TPU_TRACE", "0")
    tr = Tracer("svc")
    assert tr.start("osd_op o") is NULL_SPAN
    monkeypatch.delenv("CEPH_TPU_TRACE", raising=False)
    tr2 = Tracer("svc", sample_rate=0.0)
    sp = tr2.start("osd_op o")
    assert sp is not NULL_SPAN and not sp.sampled
    tr2.finish(sp)
    assert tr2.dump() == []            # unsampled: not retained
    tr2.record_stages(critical_path_spans(sp)["stages"])
    assert tr2.counters["stage_samples"] >= 1
    # a wire context inherits the sender's (positive) decision
    sp3 = tr2.start("osd_op o", context=(123, 456))
    assert sp3.sampled
    tr2.finish(sp3)
    assert tr2.dump(trace_id=123)


# -- encode-service span links ---------------------------------------------


def test_encode_flush_span_links_batched_ops(monkeypatch):
    """N concurrent traced encodes share one batched flush: the
    dispatch span carries LINKS to the N ops it served, and each op's
    own tree gets an encode_wait stage span."""
    monkeypatch.setenv("CEPH_TPU_FUSE_MIN_BYTES", "0")
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.encode_service import EncodeService

    codec = ErasureCodePluginRegistry.instance().factory(
        "ec_jax", {"plugin": "ec_jax", "technique": "reed_sol_van",
                   "k": "4", "m": "2"})
    sinfo = ec_util.StripeInfo(4, 4 * 4096)
    rng = np.random.default_rng(7)
    bufs = [rng.integers(0, 256, 32 << 10, dtype=np.uint8).tobytes()
            for _ in range(8)]

    async def main():
        svc = EncodeService()
        tr = Tracer("osd.test")
        svc.tracer = tr
        roots = []

        async def one_op(buf):
            root = tr.start(f"osd_op o{len(roots)}")
            roots.append(root)
            tok = current_span.set(root)
            try:
                return await svc.encode_with_hinfo(
                    sinfo, codec, buf, range(6), logical_len=len(buf))
            finally:
                current_span.reset(tok)
                tr.finish(root)

        outs = await asyncio.gather(*(one_op(b) for b in bufs))
        await svc.stop()
        return outs, roots, tr

    outs, roots, tr = asyncio.run(asyncio.wait_for(main(), 120))
    assert len(outs) == 8
    flushes = [s for s in tr.dump()
               if s["name"].startswith("encode_flush")]
    assert flushes, "no flush spans retained"
    linked = [lk for s in flushes for lk in s.get("links", [])]
    # every op context that was linked is one of our roots
    root_ctxs = {f"{r.trace_id:016x}/{r.span_id:016x}" for r in roots}
    assert linked and set(linked) <= root_ctxs
    # batching actually shared dispatches: fewer flushes than ops,
    # with at least one flush serving multiple ops
    assert len(flushes) < 8
    assert max(s["attrs"]["requests"] for s in flushes) >= 2
    # and each op's own tree saw the encode_wait stage
    for r in roots:
        assert any(s.name == "encode_wait" for s in r._tree)


# -- cross-wire propagation (hedged EC sub-reads) --------------------------


def test_trace_propagates_through_hedged_ec_subreads():
    """An EC read's trace crosses the wire on MOSDSubRead v4: the
    primary's per-peer subread spans parent the REPLICA-side sub_read
    spans, all under the client's trace id."""
    async def main():
        cluster = Cluster(num_osds=5, osds_per_host=5)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool(
                "ec", profile=EC22, pg_num=4)
            io = cluster.client.open_ioctx("ec")
            payload = b"x" * 20_000
            await io.write_full("traced", payload)
            cluster.client.trace_all = True
            got = await io.read("traced")
            cluster.client.trace_all = False
            assert bytes(got) == payload

            cspan = next(
                s for s in cluster.client.tracer.dump()
                if "traced" in s["name"] and "read" in s["name"])
            trace_id = cspan["trace_id"]
            all_spans = []
            for osd in cluster.osds:
                rc, doc = await cluster.client.osd_command(
                    osd, {"prefix": "dump_traces",
                          "trace_id": trace_id})
                assert rc == 0
                all_spans.extend(doc["spans"])
            op_spans = [s for s in all_spans
                        if s["name"].startswith("osd_op")]
            assert len(op_spans) == 1
            assert op_spans[0]["parent_id"] == cspan["span_id"]
            # the primary's per-peer subread stage spans live in the
            # same tree, under the op span
            sub_local = [s for s in all_spans
                         if s["name"].startswith("subread")]
            assert len(sub_local) >= 2, sub_local
            for s in sub_local:
                assert s["parent_id"] == op_spans[0]["span_id"]
            # replica-side sub_read spans parent to the PRIMARY'S
            # per-peer spans (the v4 tail field carried the context
            # of the sub-read span, not of the whole op)
            sub_remote = [s for s in all_spans
                          if s["name"].startswith("sub_read")]
            assert sub_remote, "no replica-side sub_read spans"
            local_ids = {s["span_id"] for s in sub_local}
            for s in sub_remote:
                assert s["trace_id"] == trace_id
                assert s["parent_id"] in local_ids
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(main(), 120))


# -- tail-exemplar retention ------------------------------------------------


def test_tail_exemplar_attributes_straggler_subread():
    """THE acceptance scenario: a slow EC read under injected slow
    peers keeps its FULL span tree (head sampling 0), and the
    critical-path breakdown pins the delay on the sub-read stage —
    not on queue/admission/encode — with the hedge visible.  EVERY
    non-primary acting member is slow, so the op genuinely waits for
    a straggling sub-read (hedging fires spares but every spare is
    slow too — the completed straggler's span owns the delay; the
    rest are cancelled and annotated)."""
    async def main():
        cluster = Cluster(
            num_osds=5, osds_per_host=5,
            osd_config={"osd_trace_sample_rate": 0.0,
                        "osd_op_complaint_time": 0.05,
                        "osd_tier_enable": False})
        await cluster.start()
        try:
            await cluster.client.create_ec_pool(
                "ec", profile=EC22, pg_num=4)
            io = cluster.client.open_ioctx("ec")
            payload = b"y" * 30_000
            oid = "slowpoke"
            await io.write_full(oid, payload)
            pg = io.object_pg(oid)
            acting, primary = \
                cluster.mon.osdmap.pg_to_acting_osds(pg)
            slow_peers = [o for o in acting if o != primary]
            # client trace so we know the trace id (retention itself
            # is decided by the PRIMARY's tail policy, not sampling)
            cluster.client.trace_all = True
            # STAGGERED delays: identical delays can complete in one
            # event-loop wave, leaving no straggler in flight to
            # cancel — one peer must win, the rest must be cut loose.
            # The messenger draws each delay uniformly below the knob;
            # a full draw holds every peer at its knob, since a draw
            # near zero answers under the complaint time and the read
            # is then no tail op at all
            for i, o in enumerate(slow_peers):
                cluster.osds[o].msgr.inject_internal_delays = \
                    0.15 + 0.1 * i
                cluster.osds[o].msgr._inject_rng = _FullDraw()
            try:
                got = await io.read(oid)
            finally:
                for o in slow_peers:
                    cluster.osds[o].msgr.inject_internal_delays = 0
                cluster.client.trace_all = False
            assert bytes(got) == payload
            cspan = next(s for s in cluster.client.tracer.dump()
                         if oid in s["name"])
            trace_id = cspan["trace_id"]

            # retention runs in the op handler's finally AFTER the
            # reply is sent (the design: the client never waits on the
            # exemplar pipeline), so a fast client can query before
            # the primary's finish hook lands — poll briefly
            for _ in range(50):
                rc, doc = await cluster.client.osd_command(
                    primary, {"prefix": "dump_op_trace",
                              "trace_id": trace_id})
                if rc == 0 and "error" not in doc:
                    break
                await asyncio.sleep(0.01)
            assert rc == 0, doc
            assert "error" not in doc, doc
            cp = doc["critical_path"]
            stages = cp["stages"]
            # the delay belongs to the sub-read fan-out, not to the
            # queue/admission/encode stages
            sub_us = stages.get("subread", 0)
            assert sub_us >= 0.5 * cp["total_us"], stages
            for quiet in ("queue.client", "admission", "encode_wait"):
                assert stages.get(quiet, 0) < sub_us / 2, stages
            assert doc["rendered"]          # the operator's tree view
            # the hedge fired around the straggler and is visible on
            # the op span's events
            op_span = next(s for s in doc["spans"]
                           if s["name"].startswith("osd_op"))
            events = " ".join(e["what"] for e in op_span["events"])
            assert "hedge" in events, events
            # a cancelled straggler sub-read is annotated in the tree
            cancelled = [s for s in doc["spans"]
                         if s["name"].startswith("subread")
                         and (s.get("attrs") or {}).get("cancelled")]
            assert cancelled, doc["spans"]

            # the historic ring shows the same per-stage breakdown
            rc, hist = await cluster.client.osd_command(
                primary, {"prefix": "dump_historic_ops"})
            assert rc == 0
            traced_ops = [o for o in hist["ops"] if "stages_us" in o]
            assert any(o.get("trace_id") == trace_id
                       for o in traced_ops)

            # per-stage histograms ride the perf dump
            rc, perf = await cluster.client.osd_command(
                primary, {"prefix": "perf dump"})
            assert rc == 0
            tr = perf["trace"]
            assert tr["enabled"] == 1
            assert tr["stage_samples"] >= 1
            assert "subread" in tr["stage"]
            hist_row = tr["stage"]["subread"]["self_seconds"]
            assert hist_row["count"] >= 1
            assert len(hist_row["bounds"]) == len(hist_row["buckets"])
            assert perf["op_tracker"]["ops_total"] >= 2
            assert perf["op_tracker"]["tail_exemplars"] >= 1
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(main(), 120))


def test_trace_kill_switch_bit_parity(monkeypatch):
    """CEPH_TPU_TRACE=0: identical op results, zero spans collected,
    zero stage histograms — the off path is the off path."""
    monkeypatch.setenv("CEPH_TPU_TRACE", "0")

    async def main():
        cluster = Cluster(num_osds=5, osds_per_host=5)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool(
                "ec", profile=EC22, pg_num=4)
            io = cluster.client.open_ioctx("ec")
            payload = b"z" * 25_000
            await io.write_full("dark", payload)
            got = await io.read("dark")
            assert bytes(got) == payload
            for osd in cluster.osds.values():
                assert osd.tracer.dump() == []
                assert osd.tracer.stage_hist == {}
                assert osd.tracer.counters["traces"] == 0
            rc, perf = await cluster.client.osd_command(
                0, {"prefix": "perf dump"})
            assert rc == 0 and perf["trace"]["enabled"] == 0
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(main(), 120))
