"""GF(2^8) kernel substrate tests.

Mirrors the role of the reference's low-level galois/jerasure checks: field
axioms, table integrity, bit-decomposition equivalence, and TPU-kernel vs
host-oracle agreement.
"""

import numpy as np
import pytest

from ceph_tpu.ops import gf


def py_gf_mul(a: int, b: int) -> int:
    """Bit-serial GF(2^8) multiply — independent of the table build."""
    r = 0
    for _ in range(8):
        if b & 1:
            r ^= a
        b >>= 1
        carry = a & 0x80
        a = (a << 1) & 0xFF
        if carry:
            a ^= gf.GF_POLY & 0xFF
    return r


def test_tables_against_bit_serial_mul():
    rng = np.random.default_rng(0)
    for _ in range(2000):
        a, b = int(rng.integers(0, 256)), int(rng.integers(0, 256))
        assert int(gf.gf_mul(np.uint8(a), np.uint8(b))) == py_gf_mul(a, b)


def test_field_axioms():
    # generator order 255; inverses; distributivity (spot check)
    seen = set()
    x = 1
    for _ in range(255):
        seen.add(x)
        x = py_gf_mul(x, 2)
    assert len(seen) == 255 and x == 1
    for a in range(1, 256):
        assert py_gf_mul(a, gf.gf_inv(a)) == 1
    rng = np.random.default_rng(1)
    for _ in range(200):
        a, b, c = (int(v) for v in rng.integers(0, 256, 3))
        assert py_gf_mul(a, b ^ c) == py_gf_mul(a, b) ^ py_gf_mul(a, c)


def test_const_to_bits_linearity():
    rng = np.random.default_rng(2)
    for _ in range(100):
        c, d = int(rng.integers(0, 256)), int(rng.integers(0, 256))
        m = gf.gf_const_to_bits(c)
        dbits = np.array([(d >> b) & 1 for b in range(8)], dtype=np.uint8)
        ybits = (m @ dbits) & 1
        y = int(sum(int(v) << o for o, v in enumerate(ybits)))
        assert y == py_gf_mul(c, d)


def test_gf_matmul_ref_small():
    m = np.array([[1, 1], [1, 2]], dtype=np.uint8)
    d = np.array([[3, 7], [5, 11]], dtype=np.uint8)
    out = gf.gf_matmul_ref(m, d)
    assert out[0, 0] == 3 ^ 5
    assert out[1, 1] == 7 ^ py_gf_mul(2, 11)


def test_invert_matrix():
    rng = np.random.default_rng(3)
    for n in (2, 4, 8):
        while True:
            a = rng.integers(0, 256, (n, n)).astype(np.uint8)
            try:
                inv = gf.gf_invert_matrix(a)
                break
            except np.linalg.LinAlgError:
                continue
        prod = gf.gf_matmul_ref(a, inv)
        assert np.array_equal(prod, np.eye(n, dtype=np.uint8))


@pytest.mark.parametrize("k,m,s", [(2, 1, 64), (4, 2, 256), (8, 3, 1024)])
def test_tpu_kernel_matches_host_oracle(k, m, s):
    rng = np.random.default_rng(4)
    mat = rng.integers(0, 256, (m, k)).astype(np.uint8)
    data = rng.integers(0, 256, (k, s)).astype(np.uint8)
    want = gf.gf_matmul_ref(mat, data)
    got = np.asarray(gf.gf_matmul_tpu(mat, data))
    assert np.array_equal(want, got)


def test_tpu_kernel_batched():
    rng = np.random.default_rng(5)
    k, m, s, b = 4, 2, 128, 5
    mat = rng.integers(0, 256, (m, k)).astype(np.uint8)
    data = rng.integers(0, 256, (b, k, s)).astype(np.uint8)
    got = np.asarray(gf.gf_matmul_tpu(mat, data))
    assert got.shape == (b, m, s)
    for i in range(b):
        assert np.array_equal(gf.gf_matmul_ref(mat, data[i]), got[i])


@pytest.fixture
def pallas_interpret():
    """Run the Pallas words kernels in interpret mode on CPU."""
    from ceph_tpu.ops import gf_pallas
    if not gf_pallas.HAVE_JAX:
        pytest.skip("jax unavailable")
    gf_pallas.FORCE_INTERPRET = True
    try:
        yield gf_pallas
    finally:
        gf_pallas.FORCE_INTERPRET = False
        gf_pallas._spec_call.cache_clear()
        gf_pallas._gen_call.cache_clear()


def test_pallas_words_roundtrip(pallas_interpret):
    gfp = pallas_interpret
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (3, 2, 1024), dtype=np.uint8)
    w = gfp.words_from_bytes(data)
    assert w.shape == (3, 2, 2, 128) and w.dtype == np.int32
    assert np.array_equal(gfp.bytes_from_words(w), data)


@pytest.mark.parametrize("k,m,s,b", [(2, 1, 512, 1), (4, 2, 1024, 2),
                                     (8, 3, 1536, 1)])
def test_pallas_generic_kernel_matches_oracle(pallas_interpret, k, m, s, b):
    gfp = pallas_interpret
    rng = np.random.default_rng(12)
    mat = rng.integers(0, 256, (m, k)).astype(np.uint8)
    data = rng.integers(0, 256, (b, k, s)).astype(np.uint8)
    got = gfp.gf_matmul_pallas(mat, data)
    for i in range(b):
        assert np.array_equal(got[i], gf.gf_matmul_ref(mat, data[i]))


def test_pallas_specialized_kernel_matches_oracle(pallas_interpret):
    gfp = pallas_interpret
    from ceph_tpu.models import reed_solomon as rs
    mat = rs.reed_sol_van_matrix(8, 3)
    gfp.register_matrix(mat)
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, (8, 2048), dtype=np.uint8)
    got = gfp.gf_matmul_pallas(mat, data)
    assert np.array_equal(got, gf.gf_matmul_ref(mat, data))


def test_pallas_decode_matrix_generic_path(pallas_interpret, monkeypatch):
    """Decode matrices (unregistered) run the generic SMEM kernel and
    reconstruct erased chunks bit-exactly."""
    gfp = pallas_interpret
    from ceph_tpu.models import reed_solomon as rs
    k, m = 4, 2
    mat = rs.reed_sol_van_matrix(k, m)
    rng = np.random.default_rng(14)
    data = rng.integers(0, 256, (k, 512), dtype=np.uint8)
    parity = gf.gf_matmul_ref(mat, data)
    chunks = np.concatenate([data, parity], axis=0)
    have = [1, 2, 3, 4]
    dmat = rs.decode_matrix(mat, k, [0], have)
    # the registry is process-wide: an earlier test's k=4 m=1 codec
    # registers the same all-ones row as its generator
    monkeypatch.delitem(gfp._registered, gfp._coeff_key(dmat),
                        raising=False)
    assert gfp._coeff_key(dmat) not in gfp._registered
    got = gfp.gf_matmul_pallas(dmat, chunks[have])
    assert np.array_equal(got[0], data[0])


def test_gf_mul_jax_matches():
    rng = np.random.default_rng(6)
    a = rng.integers(0, 256, 512).astype(np.uint8)
    b = rng.integers(0, 256, 512).astype(np.uint8)
    assert np.array_equal(np.asarray(gf.gf_mul_jax(a, b)), gf.gf_mul(a, b))


@pytest.mark.parametrize("k,m,s", [(2, 1, 64), (4, 2, 4096),
                                   (8, 3, 100_003), (10, 4, 16 * 1024)])
def test_simd_host_matmul_matches_oracle(k, m, s):
    """Native SIMD GF matmul (gf_simd.cc split-table shuffle) is bit-exact
    vs the numpy oracle, incl. non-vector-aligned tails."""
    rng = np.random.default_rng(7)
    mat = rng.integers(0, 256, (m, k)).astype(np.uint8)
    data = rng.integers(0, 256, (k, s)).astype(np.uint8)
    assert np.array_equal(gf.gf_matmul_host(mat, data),
                          gf.gf_matmul_ref(mat, data))


def test_simd_region_mad_matches():
    from ceph_tpu import native
    lib = native.get_lib()
    if lib is None or not hasattr(lib, "ceph_tpu_gf_region_mad_v"):
        pytest.skip("native SIMD tier unavailable")
    import ctypes
    rng = np.random.default_rng(8)
    for n in (1, 15, 16, 31, 32, 63, 64, 1000, 4097):
        src = rng.integers(0, 256, n).astype(np.uint8)
        dst = rng.integers(0, 256, n).astype(np.uint8)
        c = 0x53
        tbl = gf.gf_mul(np.full(256, c, np.uint8),
                        np.arange(256, dtype=np.uint8))
        want = dst ^ gf.gf_mul(np.full(n, c, np.uint8), src)
        got = dst.copy()
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.ceph_tpu_gf_region_mad_v(
            got.ctypes.data_as(u8p), src.ctypes.data_as(u8p), n,
            np.ascontiguousarray(tbl).ctypes.data_as(u8p))
        assert np.array_equal(want, got), n
