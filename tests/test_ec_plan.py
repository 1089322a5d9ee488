"""ExecPlan cache tier (ceph_tpu/ec/plan.py): bucketed-padding
correctness against the numpy host oracle, plan-key stability across
processes, the fused encode+crc plan, and the acceptance bound —
encoding 256 stripes of a fixed profile compiles at most 3 plans.
"""

from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

import conftest

jax = pytest.importorskip("jax")

from ceph_tpu.ec import plan  # noqa: E402
from ceph_tpu.ec.registry import ErasureCodePluginRegistry  # noqa: E402
from ceph_tpu.models import reed_solomon as rs  # noqa: E402
from ceph_tpu.ops import checksum as cks  # noqa: E402
from ceph_tpu.ops import gf  # noqa: E402

RNG = np.random.default_rng(7)


def _codec(k=4, m=2):
    profile = {"plugin": "ec_jax", "technique": "reed_sol_van",
               "k": str(k), "m": str(m)}
    return ErasureCodePluginRegistry.instance().factory(
        "ec_jax", profile)


def _host_parity(mat, data):
    if data.ndim == 2:
        return gf.gf_matmul_ref(mat, data)
    return np.stack([gf.gf_matmul_ref(mat, data[i])
                     for i in range(data.shape[0])])


# -- bucketing policy -------------------------------------------------------


def test_bucket_bytes_policy():
    assert plan.bucket_bytes(1) == 64
    assert plan.bucket_bytes(64) == 64
    assert plan.bucket_bytes(65) == 80   # quarter-octave: <25% pad
    for s in (1, 7, 65, 777, 4096, 65537):
        b = plan.bucket_bytes(s)
        assert b >= max(s, 64)
        assert b % 16 == 0          # mesh sp-axis and word divisibility
        assert b < 2 * max(s, 64)   # bounded waste
    # monotone: a bigger request never lands in a smaller bucket
    buckets = [plan.bucket_bytes(s) for s in range(1, 5000)]
    assert buckets == sorted(buckets)
    # few buckets per octave: real traffic collapses onto a handful
    assert len({plan.bucket_bytes(s) for s in range(1025, 2049)}) <= 4


def test_bucket_batch_policy():
    assert plan.bucket_batch(1) == 1
    assert plan.bucket_batch(3) == 4
    assert plan.bucket_batch(256) == 256
    for b in (1, 2, 5, 100, 257):
        bb = plan.bucket_batch(b)
        assert bb >= b and bb & (bb - 1) == 0  # power of two
    # above 512 the bucket is capped to the next multiple of 128 — a
    # huge one-shot object must not pad ~2x its stripes to a pow2
    assert plan.bucket_batch(513) == 640
    assert plan.bucket_batch(6144) == 6144
    for b in (513, 1000, 4100, 6145):
        bb = plan.bucket_batch(b)
        assert b <= bb < b * 1.25 and bb % 128 == 0


# -- padded-matmul correctness ---------------------------------------------


@pytest.mark.parametrize("batch,chunk", [
    (1, 777),       # odd chunk size
    (3, 1000),      # ragged batch x odd chunk
    (7, 333),
    (5, 4096),      # exact bucket
])
@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_bucketed_padding_matches_host_reference(batch, chunk):
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (batch, 4, chunk), dtype=np.uint8)
    got = plan.matmul(mat, data)
    assert got is not None
    assert got.shape == (batch, 2, chunk)
    assert np.array_equal(got, _host_parity(mat, data))


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_plan_matmul_matches_host_and_squeezes_2d():
    mat = rs.reed_sol_van_matrix(6, 3)
    data = RNG.integers(0, 256, (3, 6, 1000), dtype=np.uint8)
    got = plan.matmul(mat, data)
    assert got is not None and got.shape == (3, 3, 1000)
    assert np.array_equal(got, _host_parity(mat, data))
    d2 = RNG.integers(0, 256, (6, 512), dtype=np.uint8)
    assert np.array_equal(plan.matmul(mat, d2),
                          gf.gf_matmul_ref(mat, d2))


def test_decode_roundtrip_through_plan_dispatch():
    """decode_batch rides the same plan.matmul entry (decode matrices
    share one shape-keyed plan as runtime operands)."""
    codec = _codec(k=4, m=2)
    data = RNG.integers(0, 256, (5, 4, 512), dtype=np.uint8)
    parity = codec.encode_batch(data)
    have, erased = (2, 3, 4, 5), (0, 1)
    survivors = np.concatenate([data[:, 2:, :], parity], axis=1)
    recovered = codec.decode_batch(have, erased, survivors)
    assert np.array_equal(np.asarray(recovered), data[:, :2, :])


# -- plan-key stability across processes -----------------------------------

_KEY_SNIPPET = """
import json
from ceph_tpu.ec import plan
from ceph_tpu.models import reed_solomon as rs
mat = rs.reed_sol_van_matrix(8, 3)
sig = plan.codec_signature("reed_sol_van", 8, 3, 8, mat)
print(json.dumps(plan.plan_key(sig, "matmul", 3, 8, 37, 5000)))
"""


def test_plan_key_stable_across_processes():
    """The cache key must contain only process-stable parts (sha256
    sigs + ints) — no id()/hash() randomization — so a restarted OSD
    rebuilds the identical plan set."""
    mat = rs.reed_sol_van_matrix(8, 3)
    sig = plan.codec_signature("reed_sol_van", 8, 3, 8, mat)
    local = plan.plan_key(sig, "matmul", 3, 8, 37, 5000)
    r = subprocess.run([sys.executable, "-c", _KEY_SNIPPET],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    import json

    remote = json.loads(r.stdout.strip())
    assert json.loads(json.dumps(local)) == remote
    # and bucketing is baked into the key: same bucket, same key
    assert plan.plan_key(sig, "matmul", 3, 8, 33, 4100) == local
    # the mesh element is part of the key (a plan compiled for a
    # device set must miss for any other set), pure ints — stable
    meshed = plan.plan_key(sig, "matmul", 3, 8, 33, 4100,
                           mesh=(0, 1, 2))
    assert meshed != local and meshed[6] == (0, 1, 2)
    # mesh batch bucket rounds to a multiple of the mesh size (whole
    # stripes per chip): pow2 bucket 64 -> 66 on a 3-chip mesh
    assert meshed[4] == 66


def test_codec_signature_distinguishes_profiles():
    m1 = rs.reed_sol_van_matrix(8, 3)
    m2 = rs.reed_sol_van_matrix(8, 4)
    assert plan.codec_signature("reed_sol_van", 8, 3, 8, m1) != \
        plan.codec_signature("reed_sol_van", 8, 4, 8, m2)
    assert plan.codec_signature("reed_sol_van", 8, 3, 8, m1) != \
        plan.codec_signature("cauchy_good", 8, 3, 8, m1)


# -- fused encode + crc -----------------------------------------------------


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_fused_encode_crc_matches_host():
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (3, 4, 500), dtype=np.uint8)
    out = plan.encode_with_crc(mat, data)
    assert out is not None
    parity, crcs = out
    assert np.array_equal(parity, _host_parity(mat, data))
    chunks = np.concatenate([data, parity], axis=1)
    for b in range(3):
        for c in range(6):
            assert int(crcs[b, c]) == cks.crc32c(
                0, chunks[b, c].tobytes())


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_codec_fused_api_applies_seed():
    codec = _codec(k=4, m=2)
    data = RNG.integers(0, 256, (2, 4, 256), dtype=np.uint8)
    out = codec.encode_batch_with_crc(data, init=0xFFFFFFFF)
    assert out is not None
    parity, crcs = out
    chunks = np.concatenate([data, np.asarray(parity)], axis=1)
    for b in range(2):
        for c in range(6):
            assert int(crcs[b, c]) == cks.crc32c(
                0xFFFFFFFF, chunks[b, c].tobytes())


def test_encode_with_hinfo_fused_device_tier(monkeypatch):
    """The fused device path of ECUtil::encode_with_hinfo is bit-exact
    with the unfused host ledger."""
    from ceph_tpu.osd import ec_util

    monkeypatch.setenv("CEPH_TPU_FUSE_MIN_BYTES", "0")
    codec = _codec(k=4, m=2)
    sinfo = ec_util.StripeInfo(4, 4 * 512)
    data = RNG.integers(0, 256, 6 * 4 * 512, dtype=np.uint8).tobytes()
    shards, hinfo, crc = ec_util.encode_with_hinfo(
        sinfo, codec, data, range(6), logical_len=len(data) - 17)
    ref = ec_util.encode(sinfo, codec, data, range(6))
    ref_hinfo = ec_util.HashInfo(6)
    ref_hinfo.append(0, ref)
    for i in range(6):
        assert bytes(shards[i]) == bytes(ref[i])
    assert hinfo.cumulative_shard_hashes == \
        ref_hinfo.cumulative_shard_hashes
    assert hinfo.total_chunk_size == ref_hinfo.total_chunk_size
    assert crc == cks.crc32c(0xFFFFFFFF,
                             memoryview(data)[:len(data) - 17])


def test_fused_result_folds_ledger_without_per_stripe_calls(monkeypatch):
    """The hinfo ledger of a 128-stripe, 10-shard object makes no scalar
    crc32c_zeros call once the advance tables are cached: the call
    count must not grow with stripes x shards."""
    from ceph_tpu.osd import ec_util

    class Codec:
        def get_chunk_count(self):
            return 10

    stripes, k, chunk = 128, 8, 512
    sinfo = ec_util.StripeInfo(k, k * chunk)
    src = RNG.integers(0, 256, stripes * k * chunk, dtype=np.uint8)
    arr = src.reshape(stripes, k, chunk)
    parity = RNG.integers(0, 256, (stripes, 2, chunk), dtype=np.uint8)
    crc0 = RNG.integers(0, 1 << 32, (stripes, 10), dtype=np.uint32)
    ec_util._fused_result(sinfo, Codec(), src, arr, parity, crc0,
                          range(10), None, None)        # warm the tables
    calls = []
    zeros = cks.crc32c_zeros

    def counted(crc, length):
        calls.append(length)
        return zeros(crc, length)

    monkeypatch.setattr(cks, "crc32c_zeros", counted)
    _, hinfo, _ = ec_util._fused_result(sinfo, Codec(), src, arr, parity,
                                        crc0, range(10), None, None)
    assert not calls, f"{len(calls)} crc32c_zeros calls per object"
    monkeypatch.undo()
    want = []
    for i in range(10):
        c = 0xFFFFFFFF
        for s in range(stripes):
            c = cks.crc32c_zeros(c, chunk) ^ int(crc0[s, i])
        want.append(c)
    assert hinfo.cumulative_shard_hashes == want
    assert hinfo.total_chunk_size == stripes * chunk


# -- observability + the acceptance bound ----------------------------------


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_stats_counters_track_hits_and_misses():
    plan.clear()
    plan.reset_stats()
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (2, 4, 300), dtype=np.uint8)
    plan.matmul(mat, data)
    st = plan.stats()
    assert st["misses"] == 1 and st["hits"] == 0
    plan.matmul(mat, data)
    st = plan.stats()
    assert st["misses"] == 1 and st["hits"] == 1
    assert st["plans"] >= 1
    # the plan's own row (the mesh pipeline's inner jits add rows of
    # their own, with retraces only)
    entry = next(v for lbl, v in st["per_plan"].items()
                 if lbl.startswith("matmul["))
    assert entry["dispatches"] >= 1 and "seconds" not in entry
    # the guarded calls' thread CPU against their wall time
    assert 0 < st["device_call_cpu_s"]
    assert 0 < st["device_call_wall_s"]


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_fused_pallas_plan_records_xor_network_and_codec_compile(
        monkeypatch):
    """The fused plan of a registered matrix (the specialised Pallas
    kernel, interpreted) records the matrix's XOR network as it is
    built, and its `codec_compile` stage: compiled once, at the first
    launch inside the launch's guard, marked on the timeline of the
    batched dispatch it serves, and reused by later launches; the
    network outlives a reset of the counters."""
    from ceph_tpu.common import tracing
    from ceph_tpu.ops import crc_pallas, gf_pallas

    monkeypatch.setattr(gf_pallas, "FORCE_INTERPRET", True)
    monkeypatch.setattr(crc_pallas, "FORCE_INTERPRET", True)
    plan.clear()
    plan.reset_stats()
    codec = _codec(k=8, m=3)
    data = RNG.integers(0, 256, (2, 8, 512), dtype=np.uint8)
    stages = tracing.Stages(tracing.Tracer("test").start("encode_flush"))
    token = tracing.current_dispatch.set(stages)
    try:
        stages.mark("dispatch_pack", annotated=True)
        codec.encode_batch_with_crc(data)
    finally:
        tracing.current_dispatch.reset(token)
        stages.close()
    codec.encode_batch_with_crc(data)
    st = plan.stats()
    [(label, row)] = st["per_plan"].items()
    assert row["executor"] == "pallas_words+crc"
    assert (row["parity_rows"], row["xor_terms"]) == (3, 66)
    assert row["dispatches"] == 2 and row["retraces"] == 1
    assert st["codec_compiles"] == 1
    assert 0 < row["codec_compile_s"] == st["codec_compile_s"]
    names = [sp.name for sp in stages.spans]
    at = names.index("codec_compile")
    assert names[at - 1:at + 3] == ["dispatch_launch", "codec_compile",
                                    "dispatch_launch", "dispatch_fetch"]
    assert names.count("codec_compile") == 1
    plan.reset_stats()
    codec.encode_batch_with_crc(data)
    st = plan.stats()
    assert st["codec_compiles"] == 0
    assert (st["per_plan"][label]["parity_rows"],
            st["per_plan"][label]["xor_terms"]) == (3, 66)


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch counters/plans;\
 subject absent under scripted device-fault injection")
def test_fixed_profile_256_stripes_compiles_at_most_3_plans():
    """The acceptance bound: encoding 256 stripes of one fixed profile
    — arriving as ragged batches inside one power-of-two bucket plus
    one full batch — compiles <= 3 plans (plan.stats() retraces)."""
    plan.clear()
    plan.reset_stats()
    codec = _codec(k=4, m=2)
    chunk = 1024
    total = 0
    # 128 stripes arrive ragged: every batch pads into the B=128 bucket
    for b in (65, 128, 100, 128, 90):
        if total + b > 128:
            b = 128 - total
        if b <= 0:
            break
        data = RNG.integers(0, 256, (b, 4, chunk), dtype=np.uint8)
        parity = codec.encode_batch(data)
        assert np.asarray(parity).shape == (b, 2, chunk)
        total += b
    # ...and 128 more as one full batch
    data = RNG.integers(0, 256, (128, 4, chunk), dtype=np.uint8)
    codec.encode_batch(data)
    total += 128
    assert total == 256
    st = plan.stats()
    assert st["retraces"] <= 3, st
    assert st["hits"] >= 1, st


# -- the satellite LRU fix --------------------------------------------------


def test_gf_mul_table_cache_evicts_lru_not_everything():
    cache = gf._table_cache()
    cache.clear()
    mats = []
    for i in range(70):  # 70 distinct matrices > cap 64
        m = np.full((2, 3), 1 + (i % 255), dtype=np.uint8)
        m[0, 0] = 1 + ((i * 7) % 255)
        m[1, 2] = 1 + ((i * 13) % 255)
        m = np.ascontiguousarray(m)
        mats.append(m)
        gf.gf_mul_tables(m)
    assert len(cache) == 64  # bounded, NOT dumped to zero on overflow
    hot = mats[-1]
    key = (hot.shape, hot.tobytes())
    assert key in cache            # most-recent survived
    cold = mats[0]
    assert (cold.shape, cold.tobytes()) not in cache  # LRU evicted
    # correctness after eviction churn
    tables = gf.gf_mul_tables(hot)
    idx = np.arange(256, dtype=np.uint8)
    assert np.array_equal(tables[0], gf.gf_mul(
        np.full(256, hot[0, 0], np.uint8), idx))
