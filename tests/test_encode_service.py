"""Async micro-batching encode service tier (osd/encode_service.py).

The acceptance shape: N concurrent same-profile writes produce
bit-exact shards/hinfo vs the sequential inline path while the plan
cache records far fewer device dispatches than N; backpressure sheds
into the inline path without deadlock (including stop() with requests
in flight); the kill switch and the no-device-tier default keep
today's behavior unchanged; and the OSD daemon's write path rides the
service end to end.
"""

from __future__ import annotations

import asyncio
import os

import numpy as np
import pytest

import conftest

jax = pytest.importorskip("jax")

from ceph_tpu.ec import plan  # noqa: E402
from ceph_tpu.ec.registry import ErasureCodePluginRegistry  # noqa: E402
from ceph_tpu.osd import ec_util  # noqa: E402
from ceph_tpu.osd.encode_service import EncodeService  # noqa: E402

RNG = np.random.default_rng(17)


def _codec(k=4, m=2, **extra):
    profile = {"plugin": "ec_jax", "technique": "reed_sol_van",
               "k": str(k), "m": str(m), **extra}
    return ErasureCodePluginRegistry.instance().factory(
        "ec_jax", profile)


def _sinfo(k=4, chunk=4096):
    return ec_util.StripeInfo(k, k * chunk)


@pytest.fixture
def fused(monkeypatch):
    """Engage the fused device tier off-TPU (what a real TPU backend
    gets by default with its 1 MiB floor)."""
    monkeypatch.setenv("CEPH_TPU_FUSE_MIN_BYTES", "0")


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


def _dispatches() -> int:
    return plan.stats()["dispatches"]


# -- the acceptance bound ---------------------------------------------------


def test_64_concurrent_writes_bit_exact_with_few_dispatches(fused):
    """A burst of 64 concurrent same-profile 64 KiB writes completes
    with <= 8 plan dispatches (vs 64 inline) and bit-identical
    shards/hinfo/data-crc to the sequential path."""
    codec = _codec()
    sinfo = _sinfo()
    bufs = [RNG.integers(0, 256, 64 << 10, dtype=np.uint8).tobytes()
            for _ in range(64)]
    want = list(range(6))
    expect = [ec_util.encode_with_hinfo(sinfo, codec, b, want,
                                        logical_len=len(b))
              for b in bufs]

    async def main():
        svc = EncodeService()
        outs = await asyncio.gather(
            *(svc.encode_with_hinfo(sinfo, codec, b, want,
                                    logical_len=len(b))
              for b in bufs))
        st = svc.stats()
        await svc.stop()
        return outs, st

    plan.reset_stats()
    outs, st = run(main())
    used = _dispatches()
    assert used <= 8, f"{used} plan dispatches for 64 writes"
    assert st["batched"] == 64 and st["inline"] == 0
    assert st["batches"] >= 1
    for (shards, hinfo, crc), (ws, wh, wc) in zip(outs, expect):
        assert crc == wc
        assert hinfo.total_chunk_size == wh.total_chunk_size
        assert hinfo.cumulative_shard_hashes == \
            wh.cumulative_shard_hashes
        for i in range(6):
            assert bytes(shards[i]) == bytes(ws[i])


DISPATCH_STAGES = ("dispatch_handoff", "dispatch_pack", "dispatch_guard",
                   "dispatch_launch", "dispatch_fetch", "dispatch_fold",
                   "dispatch_resume")


def test_burst_records_the_seven_dispatch_stages(fused):
    """Every batched dispatch feeds its seven stages to the tracer's
    stage histograms; they divide dispatch_seconds (within 2 %), the
    flush root's own self-time is no stage, and each request's queue
    wait lands both in the exact queue_seconds sum and, one sample a
    request, in the encode_queue histogram."""
    from ceph_tpu.common.tracing import Tracer

    codec = _codec()
    sinfo = _sinfo()
    bufs = [RNG.integers(0, 256, 64 << 10, dtype=np.uint8).tobytes()
            for _ in range(24)]

    async def main():
        svc = EncodeService()
        svc.tracer = Tracer("osd.test")
        await asyncio.gather(*(svc.encode_with_hinfo(
            sinfo, codec, b, range(6), logical_len=len(b)) for b in bufs))
        await svc.stop()
        return svc

    svc = run(main())
    (prof,) = svc.stats()["profiles"].values()
    hist = svc.tracer.stage_hist
    assert set(DISPATCH_STAGES) <= set(hist), sorted(hist)
    assert not any(s.startswith("encode_flush") for s in hist)
    for s in DISPATCH_STAGES:
        assert hist[s].count == prof["batches"], s
    total = sum(hist[s].total for s in DISPATCH_STAGES)
    assert total == pytest.approx(prof["dispatch_seconds"], rel=0.02)
    assert hist["encode_queue"].count == prof["requests"] == 24
    assert hist["encode_queue"].total == pytest.approx(
        prof["queue_seconds"], rel=0.02, abs=24e-6)
    assert "wait_ms_hist" not in prof


def test_encode_and_decode_kinds_batch_and_match(fused):
    """Plain-encode (the RMW/recovery re-encode kind) and decode (the
    recovery/read kind) both batch and stay bit-exact."""
    codec = _codec()
    sinfo = _sinfo(chunk=1024)
    bufs = [RNG.integers(0, 256, 16 << 10, dtype=np.uint8).tobytes()
            for _ in range(12)]

    async def main():
        svc = EncodeService()
        encs = await asyncio.gather(
            *(svc.encode(sinfo, codec, b, range(6)) for b in bufs))
        # erase shard 0 everywhere: decode requests share one survivor
        # set and must fold into few dispatches
        reqs = [{i: sh[i] for i in (1, 2, 3, 4)} for sh in encs]
        decs = await asyncio.gather(
            *(svc.decode(sinfo, codec, m) for m in reqs))
        st = svc.stats()
        await svc.stop()
        return encs, decs, st

    plan.reset_stats()
    encs, decs, st = run(main())
    assert st["batches"] >= 2 and st["batched"] == 24
    for b, sh, d in zip(bufs, encs, decs):
        ref = ec_util.encode(sinfo, codec, b, range(6))
        assert all(bytes(sh[i]) == bytes(ref[i]) for i in range(6))
        assert d == b


def test_decode_many_isolates_per_request_failures(fused):
    """decode_many returns one outcome per request: a malformed map
    surfaces as its own Exception while its neighbours decode."""
    codec = _codec()
    sinfo = _sinfo(chunk=512)
    bufs = [RNG.integers(0, 256, 4096, dtype=np.uint8).tobytes()
            for _ in range(3)]
    shards = [ec_util.encode(sinfo, codec, b, range(6)) for b in bufs]
    maps = [{i: sh[i] for i in (1, 2, 3, 4)} for sh in shards]
    # below k survivors (2 of 4 data shards, real lengths): undecodable
    maps[1] = {1: maps[1][1], 2: maps[1][2]}

    async def main():
        svc = EncodeService()
        outs = await svc.decode_many(sinfo, codec, maps)
        await svc.stop()
        return outs

    outs = run(main())
    assert outs[0] == bufs[0] and outs[2] == bufs[2]
    assert isinstance(outs[1], BaseException)


# -- degradation paths ------------------------------------------------------


def test_backpressure_sheds_inline_without_deadlock(fused):
    codec = _codec()
    sinfo = _sinfo(chunk=512)
    bufs = [RNG.integers(0, 256, 8192, dtype=np.uint8).tobytes()
            for _ in range(32)]

    async def main():
        svc = EncodeService(window_ms=50, max_queue_requests=4)
        outs = await asyncio.gather(
            *(svc.encode_with_hinfo(sinfo, codec, b, range(6))
              for b in bufs))
        st = svc.stats()
        await svc.stop()
        return outs, st

    outs, st = run(main())
    assert len(outs) == 32
    assert st["shed"] > 0, "queue bound never triggered"
    assert st["shed"] + st["batched"] == 32
    for b, (shards, hinfo, _crc) in zip(bufs, outs):
        ref = ec_util.encode(sinfo, codec, b, range(6))
        assert all(bytes(shards[i]) == bytes(ref[i]) for i in range(6))


def test_stop_with_requests_in_flight_resolves_everything(fused):
    codec = _codec()
    sinfo = _sinfo(chunk=512)
    bufs = [RNG.integers(0, 256, 4096, dtype=np.uint8).tobytes()
            for _ in range(8)]

    async def main():
        # a window far beyond the test timeout: only stop() flushes
        svc = EncodeService(window_ms=60_000)
        tasks = [asyncio.ensure_future(
            svc.encode_with_hinfo(sinfo, codec, b, range(6)))
            for b in bufs]
        await asyncio.sleep(0)
        await svc.stop()
        return await asyncio.gather(*tasks)

    outs = run(main())
    assert len(outs) == 8
    assert all(h.total_chunk_size > 0 for _s, h, _c in outs)


def test_kill_switch_restores_inline_behavior(fused, monkeypatch):
    monkeypatch.setenv("CEPH_TPU_ENCODE_SERVICE", "0")
    codec = _codec()
    sinfo = _sinfo()
    buf = RNG.integers(0, 256, 32768, dtype=np.uint8).tobytes()

    async def main():
        svc = EncodeService()
        out = await svc.encode_with_hinfo(sinfo, codec, buf, range(6),
                                          logical_len=len(buf))
        st = svc.stats()
        await svc.stop()
        return out, st

    (shards, hinfo, crc), st = run(main())
    assert not st["enabled"]
    assert st["inline"] == 1 and st["batches"] == 0
    ws, wh, wc = ec_util.encode_with_hinfo(sinfo, codec, buf, range(6),
                                           logical_len=len(buf))
    assert crc == wc
    assert hinfo.cumulative_shard_hashes == wh.cumulative_shard_hashes
    assert all(bytes(shards[i]) == bytes(ws[i]) for i in range(6))


def test_no_device_tier_stays_inline(monkeypatch):
    """Without a fuse floor (the CPU-only default) the service never
    batches — CPU runs keep the pre-service path exactly."""
    monkeypatch.delenv("CEPH_TPU_FUSE_MIN_BYTES", raising=False)
    codec = _codec()
    sinfo = _sinfo()
    buf = RNG.integers(0, 256, 16384, dtype=np.uint8).tobytes()

    async def main():
        svc = EncodeService()
        out = await svc.encode_with_hinfo(sinfo, codec, buf, range(6))
        st = svc.stats()
        await svc.stop()
        return out, st

    (_shards, hinfo, _crc), st = run(main())
    assert st["inline"] == 1 and st["batched"] == 0
    assert hinfo.total_chunk_size == 16384 // 4


# -- the ec_util many-helpers (the service's thread-side body) --------------


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_encode_many_with_hinfo_matches_per_item(fused):
    codec = _codec()
    sinfo = _sinfo(chunk=512)
    items = [(RNG.integers(0, 256, n * 4 * 512,
                           dtype=np.uint8).tobytes(),
              tuple(range(6)), 100 + n)
             for n in (1, 3, 2, 5)]
    plan.reset_stats()
    outs = ec_util.encode_many_with_hinfo(sinfo, codec, items)
    assert _dispatches() == 1, "ragged batch did not fold into one"
    for (d, w, l), (shards, hinfo, crc) in zip(items, outs):
        ws, wh, wc = ec_util.encode_with_hinfo(sinfo, codec, d, w,
                                               logical_len=l)
        assert crc == wc
        assert hinfo.cumulative_shard_hashes == \
            wh.cumulative_shard_hashes
        assert all(bytes(shards[i]) == bytes(ws[i]) for i in range(6))


def test_encode_many_and_decode_many_host_fallback(monkeypatch):
    """The many-helpers stay bit-exact on the pure host tiers too."""
    monkeypatch.delenv("CEPH_TPU_FUSE_MIN_BYTES", raising=False)
    codec = _codec(tpu="false")
    sinfo = _sinfo(chunk=256)
    datas = [RNG.integers(0, 256, n * 4 * 256,
                          dtype=np.uint8).tobytes()
             for n in (2, 1, 4)]
    outs = ec_util.encode_many(sinfo, codec, datas,
                               [range(6)] * len(datas))
    for d, sh in zip(datas, outs):
        ref = ec_util.encode(sinfo, codec, d, range(6))
        assert all(bytes(sh[i]) == bytes(ref[i]) for i in range(6))
    # heterogeneous wants: slice offsets must advance for every union
    # shard per item, not only the shards an item asked for
    wants = [{0}, {0, 1, 5}, {4}]
    mixed = ec_util.encode_many(sinfo, codec, datas, wants)
    for d, w, sh in zip(datas, wants, mixed):
        ref = ec_util.encode(sinfo, codec, d, w)
        assert set(sh) == set(ref)
        assert all(bytes(sh[i]) == bytes(ref[i]) for i in w)
    maps = [{i: sh[i] for i in (1, 2, 3, 5)} for sh in outs]
    decs = ec_util.decode_many(sinfo, codec, maps)
    assert decs == list(bytes(d) for d in datas)


# -- daemon end to end ------------------------------------------------------


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_daemon_write_path_rides_the_service(fused):
    """Concurrent client writes through a live cluster batch their
    encodes (fewer plan dispatches than objects) and read back
    bit-exact; the admin surface exposes the counters."""
    from cluster_helpers import Cluster

    EC = {"plugin": "ec_jax", "technique": "reed_sol_van",
          "k": "2", "m": "1", "crush-failure-domain": "osd",
          "stripe_unit": "4096"}
    n_objs = 12
    payloads = [RNG.integers(0, 256, 32 << 10,
                             dtype=np.uint8).tobytes()
                for _ in range(n_objs)]

    async def main():
        cluster = Cluster(num_osds=4)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool("svc", profile=EC,
                                                pg_num=8)
            io = cluster.client.open_ioctx("svc")
            plan.reset_stats()
            await asyncio.gather(
                *(io.write_full(f"o{i}", payloads[i])
                  for i in range(n_objs)))
            # only count the fused write-path plans, not read decodes
            crc_dispatches = sum(
                p["dispatches"]
                for label, p in plan.stats()["per_plan"].items()
                if label.startswith("encode_crc"))
            for i in range(n_objs):
                assert await io.read(f"o{i}") == payloads[i]
            svc_stats = [osd.encode_service.stats()
                         for osd in cluster.osds.values()]
            return crc_dispatches, svc_stats
        finally:
            await cluster.stop()

    crc_dispatches, svc_stats = run(main())
    assert 0 < crc_dispatches < n_objs, (
        f"{crc_dispatches} fused dispatches for {n_objs} writes")
    assert sum(s["batched"] for s in svc_stats) == n_objs
    assert sum(s["batches"] for s in svc_stats) >= 1
