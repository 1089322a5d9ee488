"""Product-matrix MSR regenerating codec (ceph_tpu/ec/msr.py).

Repair-identity property suite: every single-erasure pattern x ragged
object sizes x d in {k..k+m-1} rebuilds bit-exact against the
full-decode oracle while helpers ship exactly beta = chunk/alpha
bytes each (the arXiv:1412.3022 product-matrix bound); RS
degeneration for d < 2k-2; stream-layout invariance through
ec_util's whole-stream batched path and ranged chunk slices;
host-fallback parity under CEPH_TPU_INJECT_DEVICE_FAIL; the `repair`
ExecPlan kind; and the daemon-level repair-aware recovery over a
live cluster, including the CEPH_TPU_MSR_REPAIR=0 kill switch
(bit-identical classic fallback, zero repair dispatches).
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ec.registry import create_erasure_code
from ceph_tpu.ops import gf
from ceph_tpu.osd import ec_util

from cluster_helpers import Cluster

# d >= 2k-2 (after shortening) admits the product-matrix MSR
# construction; anything smaller degenerates to classic RS
FRACTIONAL = [(2, 2, 3), (2, 3, 3), (3, 3, 4), (3, 3, 5), (4, 3, 6)]
DEGENERATE = [(4, 3, 4), (4, 3, 5), (6, 3, 8)]

SIZES = [1, 517 * 3 + 13, 16 * 1024 + 5]  # ragged: padding exercised


def _msr(k: int, m: int, d: int):
    return create_erasure_code({
        "plugin": "ec_msr", "k": str(k), "m": str(m), "d": str(d)})


def _chunks(codec, data: bytes):
    n = codec.get_chunk_count()
    enc = codec.encode(range(n), data)
    return {i: bytes(enc[i]) for i in range(n)}


def run(coro):
    asyncio.run(asyncio.wait_for(coro, 120))


# -- profile validation -----------------------------------------------------


def test_profile_validation():
    with pytest.raises(ErasureCodeError):
        _msr(4, 3, 3)        # d < k
    with pytest.raises(ErasureCodeError):
        _msr(4, 3, 7)        # d > n-1
    with pytest.raises(ErasureCodeError):
        create_erasure_code({"plugin": "ec_msr", "k": "4", "m": "3",
                             "d": "6", "w": "16"})  # GF(2^8) only


def test_geometry():
    c = _msr(4, 3, 6)
    assert c.supports_fractional_repair()
    assert c.get_sub_chunk_count() == 3       # alpha = d - k + 1
    assert c.repair_degree() == 6
    # chunk sizes are alpha-aligned by construction
    assert c.get_chunk_size(4 * 1024) % 3 == 0


# -- repair identity property suite ----------------------------------------


@pytest.mark.parametrize("k,m,d", FRACTIONAL)
def test_repair_identity(k, m, d):
    """Every single erasure, every ragged size: repair from d
    fractional helpers == the stored chunk == the full-decode oracle,
    and the helpers collectively ship exactly beta*d bytes."""
    codec = _msr(k, m, d)
    n = k + m
    alpha = codec.get_sub_chunk_count()
    assert alpha == d - k + 1
    rng = np.random.default_rng(1000 * k + 10 * m + d)
    for size in SIZES:
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        chunks = _chunks(codec, data)
        beta = len(chunks[0]) // alpha
        for lost in range(n):
            avail = [i for i in range(n) if i != lost]
            spec = codec.minimum_to_repair(lost, avail)
            assert len(spec) == d
            frags = {h: codec.repair_project(lost, chunks[h])
                     for h in spec}
            total = sum(len(f) for f in frags.values())
            assert total <= beta * d
            assert total == beta * d  # exactly the MSR bound
            rep = codec.repair(lost, frags)
            # full-decode oracle over k arbitrary survivors
            oracle = codec.decode(
                {lost}, {i: chunks[i] for i in avail[:k]})
            assert rep == bytes(oracle[lost]) == chunks[lost]


@pytest.mark.parametrize("k,m,d", FRACTIONAL[:2])
def test_repair_prefers_ranked_helpers(k, m, d):
    codec = _msr(k, m, d)
    n = k + m
    avail = list(range(1, n))
    prefer = list(reversed(avail))
    spec = codec.minimum_to_repair(0, avail, prefer=prefer)
    assert sorted(spec) == sorted(prefer[:d])


@pytest.mark.parametrize("k,m,d", DEGENERATE)
def test_rs_degenerate_mode(k, m, d):
    """d < 2k-2 has no product-matrix form: the codec degenerates to
    classic RS (alpha=1, no fractional repair) but stays a correct
    (k, m) code."""
    codec = _msr(k, m, d)
    n = k + m
    assert not codec.supports_fractional_repair()
    assert codec.get_sub_chunk_count() == 1
    with pytest.raises(ErasureCodeError) as ei:
        codec.minimum_to_repair(0, list(range(1, n)))
    assert ei.value.errno == 95  # EOPNOTSUPP
    rng = np.random.default_rng(99)
    data = rng.integers(0, 256, 4099, dtype=np.uint8).tobytes()
    chunks = _chunks(codec, data)
    for lost in range(n):
        have = {i: v for i, v in chunks.items() if i != lost}
        dec = codec.decode({lost}, have)
        assert bytes(dec[lost]) == chunks[lost]


def test_double_erasure_full_decode():
    """Multi-loss stays on the full-decode path and stays correct —
    the repair API is single-loss by design."""
    codec = _msr(4, 3, 6)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 40_000, dtype=np.uint8).tobytes()
    chunks = _chunks(codec, data)
    for lost in [(0, 1), (0, 4), (4, 6)]:
        have = {i: v for i, v in chunks.items() if i not in lost}
        dec = codec.decode(set(lost), have)
        for l in lost:
            assert bytes(dec[l]) == chunks[l]


# -- stream layout invariance ----------------------------------------------


def test_stream_layout_matches_batched_path():
    """The byte-interleaved sub-chunk layout is invariant under
    stripe concatenation: fragments projected from whole multi-stripe
    shard STREAMS (what ec_util's batched encode stores and what the
    OSD helper reads) rebuild the stored stream bit-exact, and any
    chunk-aligned slice of a shard stream decodes standalone (ranged
    degraded reads)."""
    codec = _msr(4, 3, 6)
    k, n = 4, 7
    unit = codec.get_chunk_size(k * 4096)
    sinfo = ec_util.StripeInfo(k, k * unit)
    chunk = sinfo.get_chunk_size()
    nst = 4
    rng = np.random.default_rng(7)
    obj = rng.integers(0, 256, nst * sinfo.get_stripe_width(),
                       dtype=np.uint8).tobytes()
    shards = ec_util.encode(sinfo, codec, obj, range(n))
    alpha = codec.get_sub_chunk_count()
    for lost in range(n):
        helpers = codec.minimum_to_repair(
            lost, [i for i in range(n) if i != lost])
        frags = {h: codec.repair_project(lost, bytes(shards[h]))
                 for h in helpers}
        for f in frags.values():
            assert len(f) == nst * chunk // alpha
        assert codec.repair(lost, frags) == bytes(shards[lost])
    # ranged slice: stripes [1, 3) of each stream decode on their own
    sub = {i: bytes(shards[i][chunk:3 * chunk]) for i in range(n)}
    for lost in range(n):
        have = {i: v for i, v in sub.items() if i != lost}
        dec = codec.decode({lost}, have)
        assert bytes(dec[lost]) == sub[lost]


# -- device-failure parity --------------------------------------------------


def test_repair_host_fallback_parity(monkeypatch):
    """CEPH_TPU_INJECT_DEVICE_FAIL=1.0 forces every device dispatch
    to fail: repair degrades to the numpy host tier bit-exactly."""
    from ceph_tpu.common import circuit

    codec = _msr(4, 3, 6)
    n = 7
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
    chunks = _chunks(codec, data)
    want = {}
    for lost in range(n):
        frags = {h: codec.repair_project(lost, chunks[h])
                 for h in codec.minimum_to_repair(
                     lost, [i for i in range(n) if i != lost])}
        want[lost] = codec.repair(lost, frags)
        assert want[lost] == chunks[lost]
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", "1.0")
    circuit.reset_all()
    try:
        for lost in range(n):
            frags = {h: codec.repair_project(lost, chunks[h])
                     for h in codec.minimum_to_repair(
                         lost, [i for i in range(n) if i != lost])}
            assert codec.repair(lost, frags) == want[lost]
    finally:
        monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
        circuit.reset_all()


def test_repair_plan_kind():
    """The repair matmul rides the ExecPlan cache as its own `repair`
    kind, bit-exact vs the host oracle."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from ceph_tpu.ec import plan

    rng = np.random.default_rng(3)
    mat = rng.integers(1, 256, (3, 6), dtype=np.uint8)
    data = rng.integers(0, 256, (2, 6, 4096), dtype=np.uint8)
    out = plan.repair(mat, data)
    if out is None:
        pytest.skip("no jax backend for plan dispatch")
    ref = np.stack([gf.gf_matmul_ref(mat, data[i]) for i in range(2)])
    assert np.array_equal(out, ref)
    labels = [lbl for lbl in plan.stats()["per_plan"]
              if lbl.startswith("repair[")]
    assert labels


# -- live-cluster repair-aware recovery ------------------------------------

MSR_PROFILE = {"plugin": "ec_msr", "k": "2", "m": "2", "d": "3",
               "crush-failure-domain": "osd"}


async def _thrash_msr_pool(cluster: Cluster):
    """Shared scenario: write through an MSR pool, lose one OSD, mark
    it out so CRUSH remaps, wait for recovery to converge, and verify
    every object bit-exact.  Returns the payload map."""
    await cluster.client.create_ec_pool("msrpool", MSR_PROFILE,
                                        pg_num=4)
    ioctx = cluster.client.open_ioctx("msrpool")
    payloads = {f"o{i}": np.random.default_rng(300 + i).integers(
        0, 256, 30_000 + 17 * i, dtype=np.uint8).tobytes()
        for i in range(6)}
    for name, data in payloads.items():
        await ioctx.write_full(name, data)
    await cluster.kill_osd(0)
    await cluster.wait_for_osd_down(0)
    await cluster.client.mon_command({"prefix": "osd out", "osd": 0})
    await cluster.wait_for_clean(60)
    for name, data in payloads.items():
        assert await ioctx.read(name) == data
    return payloads


def test_cluster_repair_aware_recovery():
    """Losing one OSD of an MSR pool recovers through beta-fragment
    repair: repair_objects counts rebuilt chunks, and the payload
    bytes read per repaired byte stay under the d/alpha bound (1.5x
    here) — strictly below the classic k-read's 2x."""
    async def main():
        cluster = Cluster(num_osds=5)
        await cluster.start()
        try:
            await _thrash_msr_pool(cluster)
            repaired = sum(o.perf["repair_objects"]
                           for o in cluster.osds.values())
            fallbacks = sum(o.perf["repair_fallbacks"]
                            for o in cluster.osds.values())
            frags = sum(o.perf["repair_fragments"]
                        for o in cluster.osds.values())
            assert repaired > 0, "no object took the repair path"
            assert frags >= 3 * repaired  # d fragments per rebuild
            # bandwidth accounting on the primaries that repaired:
            # fragment bytes read <= (d/alpha + slack) * bytes rebuilt
            for osd in cluster.osds.values():
                if osd.perf["repair_objects"] and not fallbacks:
                    read = osd.perf["recovery_bytes_read"]
                    made = osd.perf["recovery_bytes_repaired"]
                    assert read <= 1.6 * made, (read, made)
        finally:
            await cluster.stop()

    run(main())


def test_cluster_repair_kill_switch(monkeypatch):
    """CEPH_TPU_MSR_REPAIR=0 reverts recovery to classic k-read
    reconstruction — zero repair dispatches, bit-identical data."""
    monkeypatch.setenv("CEPH_TPU_MSR_REPAIR", "0")

    async def main():
        cluster = Cluster(num_osds=5)
        await cluster.start()
        try:
            await _thrash_msr_pool(cluster)
            assert sum(o.perf["repair_objects"]
                       for o in cluster.osds.values()) == 0
            assert sum(o.perf["repair_fragments"]
                       for o in cluster.osds.values()) == 0
        finally:
            await cluster.stop()

    run(main())
