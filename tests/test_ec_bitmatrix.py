"""Bitmatrix techniques + wide-word reed_sol_van + golden vectors.

Mirrors the reference's typed sweep across all seven jerasure
techniques (/root/reference/src/test/erasure-code/
TestErasureCodeJerasure.cc:34-43: reed_sol_van, reed_sol_r6_op,
cauchy_orig, cauchy_good, liberation, blaum_roth, liber8tion) with
the round-trip/erasure/minimum_to_decode/padding shapes of that file,
plus w in {16, 32} for reed_sol_van and golden chunk vectors that pin
the w=8 reed_sol_van construction BY DATA against an independent
in-test derivation of the published algorithm.
"""

import itertools

import numpy as np
import pytest

from ceph_tpu.ec.registry import create_erasure_code

# (technique, extra profile) — the 7-technique sweep + wide words
SWEEP = [
    ("reed_sol_van", {}),
    ("reed_sol_van", {"w": "16"}),
    ("reed_sol_van", {"w": "32"}),
    ("reed_sol_r6_op", {"m": "2"}),
    ("cauchy_orig", {}),
    ("cauchy_good", {}),
    ("liberation", {"m": "2", "w": "7", "packetsize": "32"}),
    ("blaum_roth", {"m": "2", "w": "6", "packetsize": "32"}),
    ("liber8tion", {"m": "2", "w": "8", "packetsize": "32"}),
]


def make(technique, k="4", m="2", **extra):
    profile = {"plugin": "ec_jax", "technique": technique,
               "k": k, "m": m, "tpu": "false"}
    profile.update(extra)
    return create_erasure_code(profile)


@pytest.mark.parametrize("technique,extra", SWEEP)
def test_encode_decode_roundtrip_all_erasures(technique, extra):
    """TestErasureCodeJerasure encode/decode shape (:57): every 1- and
    2-erasure pattern recovers the original chunks bit-exactly."""
    codec = make(technique, **extra)
    k, m = codec.k, codec.m
    n = k + m
    payload = bytes(np.random.default_rng(42).integers(
        0, 256, 3 * codec.get_alignment() - 17, dtype=np.uint8))
    encoded = codec.encode(range(n), payload)
    assert set(encoded) == set(range(n))
    chunk_len = len(encoded[0])
    for buf in encoded.values():
        assert len(buf) == chunk_len
    for nerased in (1, 2):
        for erased in itertools.combinations(range(n), nerased):
            avail = {i: bytes(encoded[i]) for i in range(n)
                     if i not in erased}
            decoded = codec.decode(range(n), avail, chunk_len)
            for i in range(n):
                assert bytes(decoded[i]) == bytes(encoded[i]), \
                    (technique, erased, i)


@pytest.mark.parametrize("technique,extra", SWEEP)
def test_minimum_to_decode(technique, extra):
    """minimum_to_decode shape (:132): available chunks that already
    cover the want-set come back verbatim; k survivors suffice."""
    codec = make(technique, **extra)
    k, m = codec.k, codec.m
    n = k + m
    want = set(range(k))
    got = codec.minimum_to_decode(want, set(range(n)))
    assert len(got) <= n
    # with exactly k survivors the minimum is those survivors
    # (returned as chunk -> subchunk-range map, get_sub_chunk_count=1)
    survivors = set(range(1, k + 1))
    got = codec.minimum_to_decode(want, survivors)
    assert set(got) == survivors


@pytest.mark.parametrize("technique,extra", SWEEP)
def test_padding_and_alignment(technique, extra):
    """encode pads the tail chunk (:230): short objects round-trip."""
    codec = make(technique, **extra)
    n = codec.k + codec.m
    for size in (1, codec.get_alignment() - 1,
                 codec.get_alignment() + 1):
        payload = bytes(np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8))
        encoded = codec.encode(range(n), payload)
        avail = {i: bytes(encoded[i]) for i in range(codec.k)}
        out = codec.decode_concat(avail)
        assert out[:size] == payload


def test_bitmatrix_parameter_adjudication():
    """The reference reverts invalid geometry with a notice
    (ErasureCodeJerasure.cc:488-494); here invalid geometry is an
    explicit error (silent adjustment would change placement)."""
    from ceph_tpu.ec.interface import ErasureCodeError

    with pytest.raises(ErasureCodeError):
        make("liberation", k="4", m="2", w="6")   # w not prime
    with pytest.raises(ErasureCodeError):
        make("liberation", k="8", m="2", w="7")   # k > w
    with pytest.raises(ErasureCodeError):
        make("blaum_roth", k="4", m="2", w="7")   # w+1 not prime
    with pytest.raises(ErasureCodeError):
        make("liber8tion", k="4", m="2", w="7")   # w != 8
    with pytest.raises(ErasureCodeError):
        make("liberation", k="4", m="3")          # m != 2


def test_wide_words_reject_non_van_techniques():
    from ceph_tpu.ec.interface import ErasureCodeError

    with pytest.raises(ErasureCodeError):
        make("cauchy_good", w="16")
    with pytest.raises(ErasureCodeError):
        make("reed_sol_van", w="24")


# -- golden vectors ---------------------------------------------------------

def _independent_gf256_mul(a: int, b: int) -> int:
    """Schoolbook GF(2^8)/0x11d multiply — no ceph_tpu code involved."""
    p = 0
    for _ in range(8):
        if b & 1:
            p ^= a
        hi = a & 0x80
        a = (a << 1) & 0xFF
        if hi:
            a ^= 0x1D
        b >>= 1
    return p


def _independent_reed_sol_van(k: int, m: int) -> list:
    """The published jerasure construction (Plank's tutorial + 2003
    correction), re-derived here from scratch: extended Vandermonde,
    elementary column ops to systematic form, coding columns scaled so
    row k is all ones, then each later coding row scaled so that it
    starts with one (reed_sol.c's last step).  Pure-python,
    independent of models/."""
    mul = _independent_gf256_mul

    def inv(a):
        for x in range(1, 256):
            if mul(a, x) == 1:
                return x
        raise ZeroDivisionError

    rows, cols = k + m, k
    v = [[0] * cols for _ in range(rows)]
    v[0][0] = 1
    v[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(cols):
            v[i][j] = acc
            acc = mul(acc, i)
    for i in range(k):
        if v[i][i] == 0:
            for j in range(i + 1, k):
                if v[i][j]:
                    for r in range(rows):
                        v[r][i], v[r][j] = v[r][j], v[r][i]
                    break
        if v[i][i] != 1:
            c = inv(v[i][i])
            for r in range(rows):
                v[r][i] = mul(v[r][i], c)
        for j in range(k):
            if j != i and v[i][j]:
                c = v[i][j]
                for r in range(rows):
                    v[r][j] ^= mul(v[r][i], c)
    coding = [row[:] for row in v[k:]]
    for j in range(k):
        if coding[0][j] not in (0, 1):
            c = inv(coding[0][j])
            for r in range(m):
                coding[r][j] = mul(coding[r][j], c)
    for r in range(1, m):
        if coding[r][0] not in (0, 1):
            c = inv(coding[r][0])
            coding[r] = [mul(x, c) for x in coding[r]]
    return coding


def test_reed_sol_van_matrix_matches_independent_derivation():
    from ceph_tpu.models import reed_solomon as rs

    for k, m in [(2, 2), (4, 2), (8, 3), (10, 4)]:
        want = _independent_reed_sol_van(k, m)
        got = rs.reed_sol_van_matrix(k, m)
        assert got.tolist() == want, (k, m)


# Golden chunk vectors: fixed input -> fixed parity bytes.  The parity
# literals below were produced by the benchmark's plain reference
# (benchmark/reference/gf256.py: reed_sol_van + matmul, pinned to the
# Jerasure manual's example) over the fixed input;
# _independent_reed_sol_van + _independent_gf256_mul (pure-python,
# derived from the published construction only) agree with them.  The
# codec must reproduce them byte-for-byte forever — the
# ceph_erasure_code_non_regression corpus role (reference
# src/test/erasure-code/ceph_erasure_code_non_regression.cc:42-147)
# pinned by data.
# fixed pseudorandom input (structured patterns XOR to zero under the
# all-ones parity row and would pin nothing)
GOLDEN_INPUT = bytes(np.random.default_rng(0xCEF).integers(
    0, 256, 512, dtype=np.uint8))
GOLDEN_K, GOLDEN_M = 4, 2


def _golden_parity() -> list:
    coding = _independent_reed_sol_van(GOLDEN_K, GOLDEN_M)
    chunk = len(GOLDEN_INPUT) // GOLDEN_K
    chunks = [GOLDEN_INPUT[i * chunk:(i + 1) * chunk]
              for i in range(GOLDEN_K)]
    out = []
    for j in range(GOLDEN_M):
        row = bytearray(chunk)
        for i in range(GOLDEN_K):
            c = coding[j][i]
            for t in range(chunk):
                row[t] ^= _independent_gf256_mul(c, chunks[i][t])
        out.append(bytes(row))
    return out


# the first 16 parity bytes of each coding chunk, as literals
GOLDEN_P0_HEAD = bytes.fromhex("177234d6377a65eb229b49789bdb7bdd")
GOLDEN_P1_HEAD = bytes.fromhex("eb1b84d3e1d2cae179b31dba95c4b72d")


def test_reed_sol_van_golden_vectors():
    """Bit-exactness pinned by data: codec parity == the independent
    derivation == the checked-in literals."""
    golden = _golden_parity()
    codec = make("reed_sol_van", k=str(GOLDEN_K), m=str(GOLDEN_M))
    # encode with chunk padding disabled by using aligned input
    encoded = codec.encode(range(GOLDEN_K + GOLDEN_M), GOLDEN_INPUT)
    chunk = len(GOLDEN_INPUT) // GOLDEN_K
    for j in range(GOLDEN_M):
        got = bytes(encoded[GOLDEN_K + j])[:chunk]
        assert got == golden[j], f"parity {j} drifted"
    assert golden[0][:16] == GOLDEN_P0_HEAD
    assert golden[1][:16] == GOLDEN_P1_HEAD


def test_bitmatrix_chunk_mapping_roundtrip():
    """A mapping profile repositions chunks on disk; the bitmatrix math
    must follow chunk_index (the review repro: data block read from a
    parity position corrupted the payload)."""
    codec = make("liberation", k="4", m="2", w="7", packetsize="32",
                 mapping="D_DDD_")
    n = codec.k + codec.m
    payload = bytes(np.random.default_rng(9).integers(
        0, 256, codec.get_alignment() * 2 - 5, dtype=np.uint8))
    encoded = codec.encode(range(n), payload)
    assert codec.decode_concat(
        {i: bytes(b) for i, b in encoded.items()})[:len(payload)] \
        == payload
    # erase two, recover, reassemble
    avail = {i: bytes(encoded[i]) for i in list(encoded)[:4]}
    assert codec.decode_concat(avail)[:len(payload)] == payload
