"""reed_sol_van as jerasure builds it, through every encode path the
codec has, against the benchmark's plain reference
(benchmark/reference/gf256.py: reed_sol.c step for step, pinned to the
Jerasure 1.2 manual's example; it imports nothing of ceph_tpu)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import crc32c, gf256  # noqa: E402
from ceph_tpu.ec.jax_plugin import ErasureCodeJax  # noqa: E402
from ceph_tpu.models import reed_solomon as rs  # noqa: E402
from ceph_tpu.models.gf_wide import reed_sol_van_matrix_w  # noqa: E402

SHAPES = [(2, 1), (2, 2), (4, 2), (6, 3), (8, 3), (10, 4), (12, 4)]
CHUNK = 512          # the Pallas kernels' smallest chunk


def _codec(k, m, tpu=True):
    codec = ErasureCodeJax("reed_sol_van")
    codec.init({"k": str(k), "m": str(m), "tpu": str(tpu).lower()})
    return codec


def _stripes(k, m, b=2):
    rng = np.random.default_rng([k, m, 0x5EED])
    return rng.integers(0, 256, (b, k, CHUNK), dtype=np.uint8)


def _reference_parity(k, m, data):
    coding = gf256.reed_sol_van(k, m)
    return np.stack([gf256.matmul(coding, d) for d in data])


@pytest.mark.parametrize("k,m", SHAPES)
def test_matrix_is_jerasures(k, m):
    assert np.array_equal(rs.reed_sol_van_matrix(k, m),
                          gf256.reed_sol_van(k, m))


@pytest.mark.parametrize("k,m", SHAPES)
def test_host_encode_is_jerasures(k, m):
    codec = _codec(k, m, tpu=False)
    data = _stripes(k, m, b=1)[0]
    encoded = codec.encode(range(k + m), data.tobytes())
    want = gf256.matmul(gf256.reed_sol_van(k, m), data)
    for j in range(m):
        assert bytes(encoded[k + j]) == want[j].tobytes(), j


@pytest.mark.parametrize("k,m", SHAPES)
def test_encode_batch_is_jerasures(k, m):
    codec = _codec(k, m)
    data = _stripes(k, m)
    assert np.array_equal(np.asarray(codec.encode_batch(data)),
                          _reference_parity(k, m, data))


@pytest.mark.parametrize("k,m", SHAPES)
def test_encode_batch_with_crc_is_jerasures(k, m, monkeypatch):
    """The fused encode+CRC plan on the Pallas kernels (interpreted):
    the served write path, its specialised kernel compiled for this
    matrix's coefficients."""
    from ceph_tpu.ops import crc_pallas, gf_pallas

    monkeypatch.setattr(gf_pallas, "FORCE_INTERPRET", True)
    monkeypatch.setattr(crc_pallas, "FORCE_INTERPRET", True)
    codec = _codec(k, m)
    data = _stripes(k, m)
    parity, crcs = codec.encode_batch_with_crc(data)
    want = _reference_parity(k, m, data)
    assert np.array_equal(np.asarray(parity), want)
    chunks = np.concatenate([data, want], axis=1)
    for b in range(data.shape[0]):
        for i in range(k + m):
            assert int(crcs[b, i]) == crc32c.crc32c(
                0, chunks[b, i].tobytes()), (b, i)


@pytest.mark.parametrize("k,m", SHAPES)
def test_decode_single_and_m_erasures(k, m):
    codec = _codec(k, m, tpu=False)
    n = k + m
    payload = _stripes(k, m, b=1)[0].tobytes()
    encoded = {i: bytes(b) for i, b in
               codec.encode(range(n), payload).items()}
    chunk_len = len(encoded[0])
    # every single erasure, and the first m data chunks at once (which
    # only every parity row together can rebuild)
    patterns = [(i,) for i in range(n)] + [tuple(range(m))]
    for erased in patterns:
        avail = {i: encoded[i] for i in range(n) if i not in erased}
        decoded = codec.decode(range(n), avail, chunk_len)
        for i in range(n):
            assert bytes(decoded[i]) == encoded[i], (erased, i)


@pytest.mark.parametrize("w", [16, 32])
@pytest.mark.parametrize("k,m", [(4, 3), (8, 3)])
def test_wide_word_rows_start_with_one(w, k, m):
    coding = reed_sol_van_matrix_w(k, m, w)
    assert (coding[0] == 1).all() and (coding[:, 0] == 1).all()


# -- data stored before the fix ------------------------------------------


def _pre_fix_reed_sol_van(k, m):
    """The coding matrix this program built before the fix: reed_sol.c
    without its last step, so coding rows after the first do not start
    with one.  Kept here alone, to make the parity such data holds."""
    mul, div = gf256.mul, gf256.div
    rows = k + m
    d = [[0] * k for _ in range(rows)]
    d[0][0] = 1
    d[rows - 1][k - 1] = 1
    for i in range(1, rows - 1):
        x = 1
        for j in range(k):
            d[i][j] = x
            x = mul(x, i)
    for i in range(1, k):
        r = next(r for r in range(i, rows) if d[r][i])
        d[i], d[r] = d[r], d[i]
        inv = div(1, d[i][i])
        for row in d:
            row[i] = mul(inv, row[i])
        for j in range(k):
            e = d[i][j]
            if j != i and e:
                for row in d:
                    row[j] ^= mul(e, row[i])
    for j in range(k):
        inv = div(1, d[k][j])
        for r in range(k, rows):
            d[r][j] = mul(inv, d[r][j])
    return np.array(d[k:], dtype=np.uint8)


def test_pre_fix_matrix_differs_only_after_row_zero():
    old, new = _pre_fix_reed_sol_van(8, 3), gf256.reed_sol_van(8, 3)
    assert np.array_equal(old[0], new[0])
    assert not (old[1:] == new[1:]).all(axis=1).any()


K83, M83 = 8, 3
STALE = range(K83 + 1, K83 + M83)


def _with_pre_fix_object(check, avoid=()):
    """Run ``await check(ctx)`` against a 12-host cluster holding one
    8+3 reed_sol_van object as this program stored it before the fix:
    the old construction's parity in shards k+1 .. k+m-1, and a hinfo
    ledger of those crcs on every shard.  The object's primary holds
    none of the stale shards nor of ``avoid``."""
    import asyncio
    import json

    from benchmark.reference import ec as ref_ec
    from ceph_tpu.os import ObjectId, Transaction
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.osdmap import PgId
    from ceph_tpu.ops.rjenkins import ceph_str_hash_rjenkins
    from ceph_tpu.rados.embedded import shard_collection
    from cluster_helpers import Cluster

    k, m = K83, M83
    profile = {"plugin": "ec_jax", "technique": "reed_sol_van",
               "k": str(k), "m": str(m), "crush-failure-domain": "host"}

    async def main():
        cluster = Cluster(num_osds=12, osds_per_host=1)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool("ec83", profile=profile,
                                                pg_num=4)
            await cluster.wait_for_clean()
            io = cluster.client.open_ioctx("ec83")
            osdmap = cluster.mon.osdmap
            pool = osdmap.pools[osdmap.lookup_pool("ec83")]
            for n in range(64):
                oid = f"pre-fix-{n}"
                pg = pool.raw_pg_to_pg(PgId(
                    pool.id, ceph_str_hash_rjenkins(oid.encode())))
                acting, primary = osdmap.pg_to_acting_osds(pg)
                if acting.index(primary) not in (*STALE, *avoid):
                    break
            prim = cluster.osds[primary]
            chunk = prim._sinfo(pool.id).get_chunk_size()
            data = np.random.default_rng(83).integers(
                0, 256, 2 * k * chunk + 1000, dtype=np.uint8).tobytes()
            await io.write_full(oid, data)
            shards, crcs = ref_ec.encode_objects([data], k, m, chunk)
            old = gf256.matmul(_pre_fix_reed_sol_van(k, m),
                               shards[0, :k])
            ledger = [int(c) for c in crcs[0]]
            for s in STALE:
                ledger[s] = crc32c.crc32c(0xFFFFFFFF, old[s - k].tobytes())
                assert ledger[s] != int(crcs[0, s])
            for s in range(k + m):
                cid = shard_collection(pg, s)
                t = Transaction()
                if s in STALE:
                    t.write(cid, ObjectId(oid), 0, old.shape[1],
                            old[s - k].tobytes())
                hinfo = json.loads(cluster.stores[acting[s]].getattrs(
                    cid, ObjectId(oid))[ec_util.HINFO_KEY])
                hinfo["cumulative_shard_hashes"] = ledger
                t.setattr(cid, ObjectId(oid), ec_util.HINFO_KEY,
                          json.dumps(hinfo).encode())
                cluster.stores[acting[s]].queue_transaction(t)
            state = prim.pgs[pg]
            at = cluster.stores[acting[0]].getattrs(
                shard_collection(pg, 0), ObjectId(oid))

            def stored(s):
                cid = shard_collection(pg, s)
                store = cluster.stores[acting[s]]
                if not store.exists(cid, ObjectId(oid)):
                    return None, None
                return bytes(store.read(cid, ObjectId(oid))), [
                    int(c) for c in json.loads(store.getattrs(
                        cid, ObjectId(oid))[ec_util.HINFO_KEY])[
                            "cumulative_shard_hashes"]]

            def drop(s):
                t = Transaction()
                t.remove(shard_collection(pg, s), ObjectId(oid))
                cluster.stores[acting[s]].queue_transaction(t)

            async def recover(lost):
                version = prim._oi_version(at)
                for s in lost:
                    state.peer_missing.setdefault(s, {})[oid] = version
                async with state.obj_lock(oid):
                    await prim._recover_object(
                        state, pool, oid,
                        prim._acting_peer_shards(state, pool))

            await check(dict(io=io, oid=oid, data=data, prim=prim,
                             state=state, shards=shards[0], crcs=crcs[0],
                             ledger=ledger, acting=acting,
                             me=acting.index(primary), stored=stored,
                             drop=drop, recover=recover))
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(main(), 180))


def test_recovery_rebuilds_parity_stored_before_the_fix():
    """The stale rows marked missing, the OSD's recovery rebuilds them
    from the ledger-checked data shards, and every shard and its own
    hinfo entry is then jerasure's."""
    async def check(c):
        await c["recover"](STALE)
        assert not any(c["oid"] in miss
                       for miss in c["state"].peer_missing.values())
        for s in range(K83 + M83):
            got, ledger = c["stored"](s)
            assert got == c["shards"][s].tobytes(), s
            assert ledger[s] == int(c["crcs"][s]), s
            if s in STALE:
                assert ledger == [int(x) for x in c["crcs"]], s
            else:
                assert ledger == c["ledger"], s
        assert await c["io"].read(c["oid"]) == c["data"]

    _with_pre_fix_object(check)


def test_recovery_refuses_a_decode_through_a_stale_row():
    """A data shard and coding row 0 lost, so the decode has to take a
    parity row the old construction made: the rebuilt data fails the
    source's ledger, and recovery installs nothing and blesses no crc,
    so the shards stay missing instead of taking wrong bytes."""
    async def check(c):
        lost = (next(s for s in range(K83) if s != c["me"]), K83)
        for s in lost:
            c["drop"](s)
        refused = c["prim"].perf["recover_ledger_refusals"]
        await c["recover"](lost)
        assert c["prim"].perf["recover_ledger_refusals"] == refused + 1
        for s in lost:
            assert c["stored"](s) == (None, None), s
            assert c["oid"] in c["state"].peer_missing[s], s
        for s in STALE:
            assert c["stored"](s)[1] == c["ledger"], s

    _with_pre_fix_object(check, avoid=(K83,))
