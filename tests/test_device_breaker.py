"""Device-tier fault tolerance: the circuit breaker state machine,
the guarded dispatch choke point (watchdog, OOM halving, poisoned-plan
quarantine), the scripted fault-injection seam, and the degradation
contract — a device fault NEVER surfaces to a caller, the bit-exact
numpy host path serves instead, and a half-open probe re-closes the
breaker once the device heals.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu.common import circuit
from ceph_tpu.ec import dispatch as ec_dispatch
from ceph_tpu.ec import plan
from ceph_tpu.models import reed_solomon as rs

try:
    import jax  # noqa: F401

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="needs jax")


@pytest.fixture(autouse=True)
def _clean_device_state(monkeypatch):
    """Every test starts with closed breakers, an empty plan cache,
    and no inherited injection spec — and leaks none of them to the
    next test module (breakers are process-global)."""
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL", raising=False)
    circuit.reset_all()
    plan.clear()
    plan.reset_stats()
    yield
    circuit.reset_all()
    plan.clear()


class FakeClock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _mk_breaker(clk, threshold=2, base=1.0, cap=8.0, rng=lambda: 0.5):
    return circuit.CircuitBreaker("test", fail_threshold=threshold,
                                  base_backoff=base, max_backoff=cap,
                                  clock=clk, rng=rng)


# -- breaker state machine -------------------------------------------------


def test_trip_half_open_reclose_state_machine():
    clk = FakeClock()
    br = _mk_breaker(clk)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"          # below threshold
    br.record_failure()                  # 2nd consecutive: trip
    assert br.state == "open" and br.counters["trips"] == 1
    # open with unexpired backoff (rng=0.5 * ceiling 1.0 => 0.5s)
    clk.t = 0.4
    assert not br.allow() and br.degraded()
    # backoff expired: exactly ONE probe is admitted
    clk.t = 0.6
    assert br.allow()
    assert br.state == "half_open" and br.counters["probes"] == 1
    assert not br.allow()                # concurrent caller refused
    br.record_success()                  # probe ok: re-close
    assert br.state == "closed" and br.counters["recoveries"] == 1
    assert br.allow() and not br.degraded()


def test_failed_probe_reopens_with_larger_backoff():
    clk = FakeClock()
    br = _mk_breaker(clk)
    br.record_failure()
    br.record_failure()                  # trip #1: ceiling 1.0 -> 0.5
    clk.t = 0.6
    assert br.allow()                    # the probe
    br.record_failure()                  # probe failed: reopen
    assert br.state == "open" and br.counters["trips"] == 2
    # exponential: ceiling now base * 2^1 = 2.0, jittered to 1.0
    assert br.stats()["retry_in_s"] == pytest.approx(1.0, abs=0.01)
    clk.t = 0.6 + 0.9
    assert not br.allow()
    clk.t = 0.6 + 1.1
    assert br.allow()
    br.record_success()
    # success resets the backoff exponent: next trip starts small again
    br.record_failure()
    br.record_failure()
    assert br.stats()["retry_in_s"] == pytest.approx(0.5, abs=0.01)


def test_watchdog_timeout_trips_immediately():
    clk = FakeClock()
    br = _mk_breaker(clk, threshold=5)
    br.record_failure(timeout=True)      # one hang beats the threshold
    assert br.state == "open"
    assert br.counters["watchdog_timeouts"] == 1


def test_force_open_and_force_probe():
    clk = FakeClock()
    br = _mk_breaker(clk)
    br.force_open(duration=100.0)
    assert br.degraded() and not br.allow()
    br.force_probe()
    assert br.allow() and br.state == "half_open"


# -- injection spec --------------------------------------------------------


def test_injection_spec_parsing():
    assert circuit.parse_injection(None) is None
    assert circuit.parse_injection("") is None
    assert circuit.parse_injection("0") is None
    assert circuit.parse_injection("1.0")["p"] == 1.0
    assert circuit.parse_injection("0.25")["p"] == 0.25
    spec = circuit.parse_injection("p=0.5,next=3,hang=20,oom=8")
    assert spec == {"p": 0.5, "next": 3, "hang_ms": 20.0,
                    "oom_batch": 8, "sick_device": None,
                    "down_host": None}
    assert circuit.parse_injection("sick=3")["sick_device"] == 3
    assert circuit.parse_injection("down_host=1")["down_host"] == 1
    with pytest.raises(ValueError):
        circuit.parse_injection("bogus=1")


def test_device_call_statuses(monkeypatch):
    calls = []

    def fn(x):
        calls.append(x)
        return x * 2

    # ok
    assert circuit.device_call("test-fam", fn, 21) == ("ok", 42)
    # fail (p=1.0)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    status, err = circuit.device_call("test-fam", fn, 1)
    assert status == "fail" and isinstance(err, circuit.DeviceFault)
    # fail-next-N heals after N
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "next=2")
    assert circuit.device_call("test-fam2", fn, 1)[0] == "fail"
    assert circuit.device_call("test-fam2", fn, 1)[0] == "fail"
    assert circuit.device_call("test-fam2", fn, 1) == ("ok", 2)
    # oom above batch k; oom_to_fail at the floor
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "oom=4")
    status, err = circuit.device_call("test-fam3", fn, 1, batch=8)
    assert status == "oom" and circuit.is_resource_exhausted(err)
    assert circuit.device_call("test-fam3", fn, 1, batch=2) == \
        ("ok", 2)
    status, _ = circuit.device_call("test-fam3", fn, 1, batch=8,
                                    oom_to_fail=True)
    assert status == "fail"
    # hang drives the watchdog; the breaker trips on one timeout
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "hang=500")
    status, _ = circuit.device_call("test-fam4", fn, 1, timeout=0.05)
    assert status == "timeout"
    assert circuit.breaker("test-fam4").state == "open"
    # open breaker refuses without running fn
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
    n = len(calls)
    status, _ = circuit.device_call("test-fam4", fn, 1)
    assert status == "open" and len(calls) == n
    assert circuit.breaker("test-fam4").counters["fallbacks"] == 1
    # benign exceptions bypass breaker accounting
    def unsupported():
        raise NotImplementedError("rule")

    status, err = circuit.device_call("test-fam5", unsupported,
                                      benign=(NotImplementedError,))
    assert status == "benign"
    assert circuit.breaker("test-fam5").counters["failures"] == 0


def test_probe_slot_released_on_oom_and_benign(monkeypatch):
    """A half-open probe that ends in OOM (to be batch-halved) or a
    benign exception carries no health verdict: the probe slot must be
    handed back, not leaked — a leaked slot wedges the breaker in
    half_open forever (every later allow() refused)."""
    br = circuit.breaker("test-leak")
    br.force_open(duration=0.0)           # probe due immediately
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "oom=1")
    status, _ = circuit.device_call("test-leak", lambda: 1, batch=4)
    assert status == "oom"
    assert br.state == "half_open" and not br.degraded()
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
    status, out = circuit.device_call("test-leak", lambda: 1, batch=4)
    assert (status, out) == ("ok", 1) and br.state == "closed"

    def unsupported():
        raise NotImplementedError("rule")

    br2 = circuit.breaker("test-leak2")
    br2.force_open(duration=0.0)
    status, _ = circuit.device_call("test-leak2", unsupported,
                                    benign=(NotImplementedError,))
    assert status == "benign"
    assert br2.state == "half_open" and not br2.degraded()
    status, out = circuit.device_call("test-leak2", lambda: 2)
    assert (status, out) == ("ok", 2) and br2.state == "closed"


def test_kill_switch_restores_raw_dispatch(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_BREAKER", "0")

    def boom():
        raise RuntimeError("raw")

    # guard bypassed: exceptions propagate, injection seam is off
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    assert circuit.device_call("test-kill", lambda: 7) == ("ok", 7)
    with pytest.raises(RuntimeError):
        circuit.device_call("test-kill", boom)


# -- host degradation through the EC dispatch layers -----------------------


@needs_jax
def test_gf_matmul_degrades_bit_exactly_and_recovers(monkeypatch):
    mat = rs.reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (8, 4, 64), dtype=np.uint8)
    host = ec_dispatch.gf_matmul(mat, data, use_tpu=False)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    for _ in range(6):   # past the trip threshold and into open state
        out = ec_dispatch.gf_matmul(mat, data, use_tpu=True)
        assert np.array_equal(out, host)   # bit-exact, no exception
    br = circuit.breaker("ec-encode")
    assert br.stats()["trips"] >= 1
    # injection clears: a forced half-open probe re-closes the breaker
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
    br.force_probe()
    out = ec_dispatch.gf_matmul(mat, data, use_tpu=True)
    assert np.array_equal(out, host)
    st = br.stats()
    assert st["state"] == "closed" and st["recoveries"] >= 1 \
        and st["probes"] >= 1
    # ... and the transitions are visible through plan.stats()
    health = plan.stats()["device_health"]["ec-encode"]
    assert health["trips"] >= 1 and health["recoveries"] >= 1


@needs_jax
def test_decode_family_trips_independently(monkeypatch):
    from ceph_tpu.ec.registry import create_erasure_code

    codec = create_erasure_code(
        {"plugin": "ec_jax", "technique": "reed_sol_van",
         "k": "4", "m": "2"})
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (6, 4, 128), dtype=np.uint8)
    parity = codec.encode_batch(data)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    survivors = np.concatenate([data[:, 2:, :], parity], axis=1)
    have, erased = (2, 3, 4, 5), (0, 1)
    for _ in range(4):
        recovered = codec.decode_batch(have, erased, survivors)
        assert np.array_equal(np.asarray(recovered), data[:, :2, :])
    assert circuit.breaker("ec-decode").stats()["failures"] >= 1
    # the decode storm tripped ec-decode, not the encode family
    assert circuit.breaker("ec-encode").stats()["trips"] == 0


@needs_jax
def test_oom_halving_bit_exact_vs_numpy_oracle(monkeypatch):
    mat = rs.reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (32, 4, 64), dtype=np.uint8)
    oracle = ec_dispatch.gf_matmul(mat, data, use_tpu=False)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "oom=4")
    out = plan.matmul(mat, data, family="ec-encode")
    # the split bottomed out at batches <= 4, each dispatched on
    # device, and the reassembled parity is bit-exact
    assert out is not None and np.array_equal(out, oracle)
    st = plan.stats()
    assert st["oom_splits"] >= 3          # 32 -> 16 -> 8 -> 4
    assert circuit.breaker("ec-encode").stats()["trips"] == 0


@needs_jax
def test_oom_halving_fused_crc(monkeypatch):
    mat = rs.reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (16, 4, 96), dtype=np.uint8)
    want = plan.encode_with_crc(mat, data)
    assert want is not None
    plan.clear()
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "oom=2")
    got = plan.encode_with_crc(mat, data)
    assert got is not None
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


@needs_jax
def test_oom_at_single_stripe_floor_falls_back_to_host(monkeypatch):
    mat = rs.reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, (4, 4, 64), dtype=np.uint8)
    host = ec_dispatch.gf_matmul(mat, data, use_tpu=False)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "oom=0")
    # every batch size OOMs, even a single stripe: the floor gives up
    # and the caller rides the host path — still bit-exact, no raise
    assert plan.matmul(mat, data, family="ec-encode") is None
    out = ec_dispatch.gf_matmul(mat, data, use_tpu=True)
    assert np.array_equal(out, host)


@needs_jax
def test_watchdog_contains_wedged_dispatch(monkeypatch):
    mat = rs.reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (4, 4, 64), dtype=np.uint8)
    host = ec_dispatch.gf_matmul(mat, data, use_tpu=False)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "hang=400")
    monkeypatch.setenv("CEPH_TPU_DEVICE_TIMEOUT_S", "0.05")
    t0 = time.monotonic()
    out = ec_dispatch.gf_matmul(mat, data, use_tpu=True)
    elapsed = time.monotonic() - t0
    assert np.array_equal(out, host)
    assert elapsed < 5.0                  # bounded, not the full hang
    br = circuit.breaker("ec-encode").stats()
    assert br["watchdog_timeouts"] >= 1 and br["state"] == "open"


# -- poisoned-plan quarantine ----------------------------------------------


@needs_jax
def test_poisoned_plan_quarantine_and_expiry(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_PLAN_QUARANTINE_S", "0.25")
    mat = rs.reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, (8, 4, 64), dtype=np.uint8)
    # keep the breaker out of the way: this test is about the PLAN
    # failure counter, which needs failures to keep reaching the key
    circuit.breaker("ec-encode").fail_threshold = 10_000
    # one device: a failed mesh dispatch would probe and retire chips
    # first (test_mesh_plan's subject) and move the plan to a new key
    monkeypatch.setenv("CEPH_TPU_MESH", "0")
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    for _ in range(3):                   # CEPH_TPU_PLAN_FAIL_LIMIT
        assert plan.matmul(mat, data, family="ec-encode") is None
    st = plan.stats()
    assert st["quarantines"] == 1 and st["quarantined_plans"] == 1
    assert plan.quarantine_info()["entries"]
    # injection clears, but the key stays blacklisted until the TTL:
    # callers keep riding the host path without rebuilding the plan
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
    misses_before = plan.stats()["misses"]
    assert plan.matmul(mat, data, family="ec-encode") is None
    assert plan.stats()["misses"] == misses_before  # cache untouched
    time.sleep(0.3)                       # TTL expiry releases the key
    out = plan.matmul(mat, data, family="ec-encode")
    assert out is not None
    assert np.array_equal(
        out, ec_dispatch.gf_matmul(mat, data, use_tpu=False))
    assert plan.stats()["quarantined_plans"] == 0


# -- hitset device hashing -------------------------------------------------


@needs_jax
def test_hitset_positions_degrade_bit_exactly(monkeypatch):
    from ceph_tpu.osd import hitset as hm

    hashes = np.array([hm.hash_oid(f"o{i}") for i in range(64)],
                      dtype=np.uint32)
    nbits, nhash = hm.bloom_geometry(1024, 0.05)
    host = hm.bloom_positions(hashes, nbits, nhash, xp=np)
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    got = hm.positions_for(hashes, nbits, nhash, device=True)
    assert np.array_equal(got, host)
    assert circuit.breaker("hitset-hash").stats()["failures"] >= 1


# -- encode service flush shedding -----------------------------------------


@needs_jax
def test_encode_service_flush_sheds_to_host(monkeypatch):
    """A device fault during _flush must NOT fail the per-request
    futures: the accumulated batch re-runs on the bit-exact host path
    and the shed is counted under device_fallback."""
    from ceph_tpu.ec.registry import create_erasure_code
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.encode_service import EncodeService

    monkeypatch.setenv("CEPH_TPU_FUSE_MIN_BYTES", "0")
    codec = create_erasure_code(
        {"plugin": "ec_jax", "technique": "reed_sol_van",
         "k": "4", "m": "2"})
    sinfo = ec_util.StripeInfo(4, 4 * 1024)
    rng = np.random.default_rng(7)
    bufs = [rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()
            for _ in range(8)]
    want = [ec_util.encode_with_hinfo(sinfo, codec, b, range(6),
                                      logical_len=len(b))
            for b in bufs]

    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")

    async def run():
        svc = EncodeService(who="t")
        outs = await asyncio.gather(
            *(svc.encode_with_hinfo(sinfo, codec, b, range(6),
                                    logical_len=len(b))
              for b in bufs),
            return_exceptions=True)
        st = svc.stats()
        await svc.stop()
        return outs, st

    outs, st = asyncio.run(asyncio.wait_for(run(), 60))
    for b, out, (ws, wh, wc) in zip(bufs, outs, want):
        assert not isinstance(out, BaseException), out   # zero errors
        shards, hinfo, crc = out
        assert crc == wc
        assert hinfo.cumulative_shard_hashes == \
            wh.cumulative_shard_hashes
        assert all(bytes(shards[i]) == bytes(ws[i]) for i in range(6))
    assert st["device_fallback"] >= 1


# -- scrub repair under device faults --------------------------------------


def _run(coro):
    asyncio.run(asyncio.wait_for(coro, 180))


@needs_jax
def test_scrub_repair_survives_device_faults():
    """fail-next-N injection mid-scrub: the repair decode rides the
    host path, the object is repaired (not counted unrepaired), and a
    decode_many exception from the service is retried inline on host
    (_batch_reconstruct's resilience seam)."""
    from ceph_tpu.os import ObjectId, Transaction
    from ceph_tpu.osd.osdmap import PgId  # noqa: F401 (parity import)
    from ceph_tpu.rados.embedded import shard_collection

    from cluster_helpers import Cluster

    async def main():
        import os

        cluster = Cluster(num_osds=5)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool(
                "ec", profile={"plugin": "ec_jax",
                               "technique": "reed_sol_van",
                               "k": "2", "m": "2",
                               "crush-failure-domain": "osd"},
                pg_num=8)
            io = cluster.client.open_ioctx("ec")
            data = bytes(np.random.default_rng(8).integers(
                0, 256, 50_000, dtype=np.uint8))
            await io.write_full("obj", data)
            osdmap = cluster.mon.osdmap
            pool = [p for p in osdmap.pools.values()
                    if p.name == "ec"][0]
            from ceph_tpu.ops.rjenkins import ceph_str_hash_rjenkins
            from ceph_tpu.osd.osdmap import PgId as _PgId

            pg = pool.raw_pg_to_pg(
                _PgId(pool.id, ceph_str_hash_rjenkins(b"obj")))
            _acting, primary = osdmap.pg_to_acting_osds(pg)
            prim = cluster.osds[primary]
            state = prim.pgs[pg]

            # round 1: the service's decode_many dies wholesale once —
            # _batch_reconstruct must retry on host, not give up
            victim = state.acting[1]
            store = cluster.osds[victim].store
            cid = shard_collection(pg, 1)
            raw = store.read(cid, ObjectId("obj"))
            t = Transaction()
            t.write(cid, ObjectId("obj"), 100, 4, b"\xde\xad\xbe\xef")
            store.queue_transaction(t)

            orig = prim.encode_service.decode_many
            calls = {"n": 0}

            async def flaky(sinfo, codec, maps):
                maps = list(maps)
                calls["n"] += 1
                if calls["n"] == 1:
                    return [RuntimeError("RESOURCE_EXHAUSTED (test)")
                            ] * len(maps)
                return await orig(sinfo, codec, maps)

            prim.encode_service.decode_many = flaky
            try:
                res = await prim.scrub_pg(state, pool)
            finally:
                prim.encode_service.decode_many = orig
            assert res["errors"] >= 1 and res["repaired"] >= 1, res
            assert prim.perf["decode_host_retries"] >= 1
            await cluster.wait_for_clean()
            assert store.read(cid, ObjectId("obj")) == raw
            assert await io.read("obj") == data

            # round 2: scripted injection at the dispatch seam while
            # the scrub runs — repair still succeeds via host fallback
            t = Transaction()
            t.write(cid, ObjectId("obj"), 200, 4, b"\xfe\xed\xfa\xce")
            store.queue_transaction(t)
            os.environ["CEPH_TPU_INJECT_DEVICE_FAIL"] = "next=8"
            try:
                res = await prim.scrub_pg(state, pool)
            finally:
                os.environ.pop("CEPH_TPU_INJECT_DEVICE_FAIL", None)
            assert res["errors"] >= 1 and res["repaired"] >= 1, res
            await cluster.wait_for_clean()
            assert store.read(cid, ObjectId("obj")) == raw
            assert await io.read("obj") == data
        finally:
            await cluster.stop()

    _run(main())
