"""Codec-compiler tier (ec/xsched.py): schedule-vs-naive
bit-exactness across the bitmatrix family (all techniques x legal w
values x every 1- and 2-erasure pattern), GF(2^8) bit-expansion
equivalence on ragged chunk sizes, the CEPH_TPU_XSCHED=0 kill-switch
parity leg through a live cluster, the shared decode-rows cache
(cross-instance hits), and schedule survival across plan rebuilds.
"""

from __future__ import annotations

import asyncio
import itertools

import numpy as np
import pytest

from ceph_tpu.ec import dispatch, plan, xsched
from ceph_tpu.ec.registry import create_erasure_code
from ceph_tpu.models import bitmatrix as bmx
from ceph_tpu.models import reed_solomon as rs
from ceph_tpu.ops import gf

from cluster_helpers import Cluster

RNG = np.random.default_rng(0xEC5)


def _exec(sched: xsched.XorSchedule, pk: np.ndarray) -> np.ndarray:
    """Run a schedule over a (B, C, ps) packet stack, returning the
    (B, R, ps) outputs — the naive_xor_matmul calling convention."""
    b, c, ps = pk.shape
    out = np.zeros((b, sched.n_out, ps), dtype=np.uint8)
    xsched.execute_host(sched, [pk[:, i, :] for i in range(c)],
                        [out[:, r, :] for r in range(sched.n_out)])
    return out


def _codec(technique: str, **extra):
    profile = {"plugin": "ec_jax", "technique": technique, "k": "4",
               "m": "2", "packetsize": "32", "tpu": "false"}
    profile.update({k: str(v) for k, v in extra.items()})
    return create_erasure_code(profile)


# -- compiler properties: every technique x its legal w values ---------

# (technique, k, w) across the legal parameter space: liberation w
# prime >= k, blaum_roth w+1 prime >= k, liber8tion w=8 k<=8
MATRIX_SPACE = [
    ("liberation", 4, 5), ("liberation", 4, 7),
    ("liberation", 4, 11), ("liberation", 4, 13),
    ("blaum_roth", 4, 4), ("blaum_roth", 4, 6),
    ("blaum_roth", 4, 10), ("blaum_roth", 4, 12),
    ("liber8tion", 2, 8), ("liber8tion", 4, 8), ("liber8tion", 8, 8),
]


def _matrix(technique: str, k: int, w: int) -> np.ndarray:
    if technique == "liberation":
        return bmx.liberation_bitmatrix(k, w)
    if technique == "blaum_roth":
        return bmx.blaum_roth_bitmatrix(k, w)
    return bmx.liber8tion_bitmatrix(k)


@pytest.mark.parametrize("technique,k,w", MATRIX_SPACE)
def test_schedule_matches_naive_encode_matrix(technique, k, w):
    bm = _matrix(technique, k, w)
    sched = xsched.compile_matrix(bm)
    pk = RNG.integers(0, 256, (3, bm.shape[1], 24), dtype=np.uint8)
    assert np.array_equal(_exec(sched, pk),
                          xsched.naive_xor_matmul(bm, pk))
    # CSE never costs ops, and the bookkeeping is consistent
    assert sched.xors_scheduled <= sched.xors_naive
    assert sched.n_slots <= max(len(sched.ops), 1)


@pytest.mark.parametrize("technique,k,w", MATRIX_SPACE)
def test_schedule_matches_naive_every_erasure_pattern(technique, k, w):
    """Decode rows for EVERY 1- and 2-erasure pattern execute
    bit-exactly: the dense inverted submatrices are where the CSE
    bites hardest (the deepest temp chains + slot reuse)."""
    bm = _matrix(technique, k, w)
    n = k + 2
    for nerased in (1, 2):
        for erased in itertools.combinations(range(n), nerased):
            have = tuple(i for i in range(n) if i not in erased)[:k]
            rows = bmx.decode_bitmatrix(bm, k, w, have,
                                        tuple(erased))
            sched = xsched.compile_matrix(rows)
            pk = RNG.integers(0, 256, (2, rows.shape[1], 16),
                              dtype=np.uint8)
            assert np.array_equal(
                _exec(sched, pk), xsched.naive_xor_matmul(rows, pk)), \
                (technique, w, erased)


def test_compile_is_deterministic():
    bm = bmx.liberation_bitmatrix(4, 7)
    s1 = xsched.compile_matrix(bm)
    xsched.clear()
    s2 = xsched.compile_matrix(bm)
    assert s1 == s2


def test_decode_reduction_clears_acceptance_bar():
    """The measured-XOR-count acceptance: >= 25% reduction on at
    least one bitmatrix technique (the decode inverses)."""
    best = 0.0
    for technique, k, w in (("liberation", 4, 7),
                            ("liber8tion", 4, 8)):
        bm = _matrix(technique, k, w)
        rows = bmx.decode_bitmatrix(bm, k, w, tuple(range(2, k + 2)),
                                    (0, 1))
        best = max(best, xsched.compile_matrix(rows).reduction_pct)
    assert best >= 25.0


# -- codec-level kill-switch parity ------------------------------------

SWEEP = [("liberation", {"w": 7}), ("blaum_roth", {"w": 6}),
         ("liber8tion", {"w": 8})]


@pytest.mark.parametrize("technique,extra", SWEEP)
def test_kill_switch_parity_every_erasure_pattern(monkeypatch,
                                                  technique, extra):
    """Scheduled and naive paths are bit-identical end to end: same
    parity chunks, and every 1-/2-erasure decode recovers the same
    bytes under both modes (decoding xsched-encoded chunks with the
    kill switch on, and vice versa)."""
    codec = _codec(technique, **extra)
    n = codec.k + codec.m
    payload = bytes(RNG.integers(
        0, 256, 2 * codec.get_alignment() - 11, dtype=np.uint8))
    monkeypatch.setenv("CEPH_TPU_XSCHED", "1")
    enc_on = codec.encode(range(n), payload)
    monkeypatch.setenv("CEPH_TPU_XSCHED", "0")
    enc_off = codec.encode(range(n), payload)
    assert {i: bytes(b) for i, b in enc_on.items()} == \
        {i: bytes(b) for i, b in enc_off.items()}
    chunk_len = len(enc_on[0])
    for nerased in (1, 2):
        for erased in itertools.combinations(range(n), nerased):
            avail = {i: bytes(enc_on[i]) for i in range(n)
                     if i not in erased}
            monkeypatch.setenv("CEPH_TPU_XSCHED", "0")
            dec_off = codec.decode(range(n), avail, chunk_len)
            monkeypatch.setenv("CEPH_TPU_XSCHED", "1")
            dec_on = codec.decode(range(n), avail, chunk_len)
            for i in range(n):
                assert bytes(dec_on[i]) == bytes(enc_on[i]), \
                    (technique, erased, i)
                assert bytes(dec_off[i]) == bytes(enc_on[i]), \
                    (technique, erased, i)


# -- GF(2^8) bit-expansion equivalence on ragged chunk sizes -----------

@pytest.mark.parametrize("builder,k,m", [
    (rs.cauchy_good_matrix, 4, 2),
    (rs.cauchy_orig_matrix, 3, 3),
    (rs.reed_sol_van_matrix, 4, 2),
])
@pytest.mark.parametrize("ps", [1, 3, 17, 33])
def test_gf256_bit_expansion_equivalence_ragged(builder, k, m, ps):
    """jerasure/cauchy-style GF(2^8) matrices expanded to bits via
    gf_matrix_to_bits schedule-execute bit-exactly on ragged packet
    widths (no alignment assumptions in the executor)."""
    bits = gf.gf_matrix_to_bits(builder(k, m))
    sched = xsched.compile_matrix(bits)
    pk = RNG.integers(0, 256, (2, bits.shape[1], ps), dtype=np.uint8)
    assert np.array_equal(_exec(sched, pk),
                          xsched.naive_xor_matmul(bits, pk))


# -- the live-cluster kill-switch leg ----------------------------------

LIBERATION_PROFILE = {"plugin": "ec_jax", "technique": "liberation",
                      "k": "4", "m": "2", "w": "7",
                      "packetsize": "64",
                      "crush-failure-domain": "osd"}


def test_kill_switch_parity_live_cluster(monkeypatch):
    """Writes encoded under one mode read back bit-identically under
    the other, through real daemons: the schedule is a pure lowering
    change, invisible on the wire and on disk."""
    payload = bytes(RNG.integers(0, 256, 7168, dtype=np.uint8))

    async def main():
        cluster = Cluster(num_osds=6, osds_per_host=6)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool(
                "xspool", profile=LIBERATION_PROFILE, pg_num=8)
            io = cluster.client.open_ioctx("xspool")
            monkeypatch.setenv("CEPH_TPU_XSCHED", "1")
            await io.write_full("o-sched", payload)
            monkeypatch.setenv("CEPH_TPU_XSCHED", "0")
            await io.write_full("o-naive", payload)
            # cross-mode reads: naive decode of scheduled encode and
            # the reverse
            assert bytes(await io.read("o-sched")) == payload
            monkeypatch.setenv("CEPH_TPU_XSCHED", "1")
            assert bytes(await io.read("o-naive")) == payload
            assert bytes(await io.read("o-sched")) == payload
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(main(), 120))


# -- shared decode-rows cache ------------------------------------------

def test_decode_rows_shared_across_instances(monkeypatch):
    """Re-instantiated codecs (pool remount / registry re-resolution)
    must NOT re-invert submatrices another instance already paid
    for: the cache lives in ec/dispatch.py keyed by codec signature,
    not on the instance."""
    calls = {"n": 0}
    real = bmx.decode_bitmatrix

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(bmx, "decode_bitmatrix", counting)
    # a geometry no other test uses, so the shared cache starts cold
    c1 = _codec("liberation", k=3, w=5)
    n = c1.k + c1.m
    payload = bytes(RNG.integers(0, 256, c1.get_alignment(),
                                 dtype=np.uint8))
    enc = c1.encode(range(n), payload)
    chunk_len = len(enc[0])
    avail = {i: bytes(enc[i]) for i in range(n) if i not in (0, 1)}
    c1.decode(range(n), avail, chunk_len)
    assert calls["n"] == 1                 # cold: one inversion
    hits_before = dispatch.decode_rows_stats()["hits"]
    c2 = _codec("liberation", k=3, w=5)    # a FRESH instance
    assert c2 is not c1
    out = c2.decode(range(n), avail, chunk_len)
    assert calls["n"] == 1                 # no re-inversion
    assert dispatch.decode_rows_stats()["hits"] > hits_before
    for i in range(n):
        assert bytes(out[i]) == bytes(enc[i])


# -- memoization + plan.stats() observability --------------------------

def test_schedules_survive_plan_rebuilds():
    """The acceptance invariant: compiled schedules are keyed by
    matrix signature, so plan-cache rebuilds (mesh shrink retires
    keys, quarantine evicts them, clear() drops everything) never
    cost a recompilation — visible in plan.stats()['xsched']."""
    codec = _codec("liber8tion", w=8)
    n = codec.k + codec.m
    payload = bytes(RNG.integers(0, 256, codec.get_alignment(),
                                 dtype=np.uint8))
    xsched.clear()
    xsched.reset_stats()
    codec.encode(range(n), payload)
    st1 = plan.stats()["xsched"]
    assert st1["compiled"] >= 1
    assert st1["xors_scheduled"] <= st1["xors_naive"]
    plan.clear()                      # every ExecPlan key retired
    codec2 = _codec("liber8tion", w=8)
    codec2.encode(range(n), payload)
    st2 = plan.stats()["xsched"]
    assert st2["compiled"] == st1["compiled"]     # NO recompilation
    assert st2["cache_hits"] > st1["cache_hits"]
    assert st2["enabled"] is True


def test_kill_switch_compiles_nothing(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_XSCHED", "0")
    codec = _codec("liberation", w=7)
    n = codec.k + codec.m
    payload = bytes(RNG.integers(0, 256, codec.get_alignment(),
                                 dtype=np.uint8))
    xsched.reset_stats()
    codec.encode(range(n), payload)
    st = plan.stats()["xsched"]
    assert st["compiled"] == 0 and st["enabled"] is False
