"""Checksum tests.

crc32c vectors are the reference's own
(/root/reference/src/test/common/test_crc32c.cc: Small/PartialWord/Big and
the crc32c_zeros equivalence); xxhash vectors are the published XXH32/XXH64
empty-string digests plus cross-checks of the native C++ against the
independent pure-python mirror.
"""

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.common import checksummer as cs
from ceph_tpu.ops import checksum as cks


class TestCrc32cHost:
    def test_small(self):
        a = b"foo bar baz"
        b = b"whiz bang boom"
        assert cks.crc32c(0, a) == 4119623852
        assert cks.crc32c(1234, a) == 881700046
        assert cks.crc32c(0, b) == 2360230088
        assert cks.crc32c(5678, b) == 3743019208

    def test_partial_word(self):
        assert cks.crc32c(0, b"\x01" * 5) == 2715569182
        assert cks.crc32c(0, b"\x01" * 35) == 440531800

    def test_big(self):
        buf = b"\x01" * 4096000
        assert cks.crc32c(0, buf) == 31583199
        assert cks.crc32c(1234, buf) == 1400919119

    def test_performance_vector(self):
        ln = 1 << 20
        a = np.arange(ln, dtype=np.uint32).astype(np.uint8)
        # independent cross-check native vs python table loop on a prefix
        assert cks.crc32c(0, a[:1000]) == cks._py_crc32c(0, a[:1000].tobytes())

    def test_null_buffer_is_zeros(self):
        for ln in (0, 1, 5, 16, 63, 64, 65, 1024, 123457):
            assert cks.crc32c(77, None, ln) == cks.crc32c(77, b"\x00" * ln)

    def test_zeros_matches_linear(self):
        for seed in (0, 1, 0xFFFFFFFF, 0xDEADBEEF):
            for ln in (0, 1, 3, 15, 16, 17, 255, 4096, 999999):
                assert cks.crc32c_zeros(seed, ln) == \
                    cks.crc32c(seed, b"\x00" * ln)

    def test_combine(self):
        rng = np.random.default_rng(7)
        a = rng.integers(0, 256, 1000, dtype=np.uint8)
        b = rng.integers(0, 256, 333, dtype=np.uint8)
        whole = cks.crc32c(0xFFFFFFFF, np.concatenate([a, b]))
        part = cks.crc32c_combine(cks.crc32c(0xFFFFFFFF, a),
                                  cks.crc32c(0, b), b.size)
        assert whole == part

    def test_python_fallback_agrees(self):
        rng = np.random.default_rng(3)
        buf = rng.integers(0, 256, 4097, dtype=np.uint8)
        assert cks.crc32c(0, buf) == cks._py_crc32c(0, buf.tobytes())

    def test_blocks(self):
        rng = np.random.default_rng(5)
        buf = rng.integers(0, 256, 16 * 512, dtype=np.uint8)
        vals = cks.crc32c_blocks(buf, 512, init=0xFFFFFFFF)
        for i in range(16):
            assert vals[i] == cks.crc32c(0xFFFFFFFF, buf[i * 512:(i + 1) * 512])


def _ledger_loop(crc0, chunk, init):
    """The scalar reference: fold each column's stripes one by one."""
    out = []
    for i in range(crc0.shape[1]):
        c = init
        for s in range(crc0.shape[0]):
            c = cks.crc32c_zeros(c, chunk) ^ int(crc0[s, i])
        out.append(c)
    return out


@pytest.mark.parametrize("init", [0, 0xFFFFFFFF])
@pytest.mark.parametrize("chunk", [512, 1000, 4096])
@pytest.mark.parametrize("n", [3, 10, 14])
@pytest.mark.parametrize("stripes", [1, 2, 3, 7, 128, 129, 600])
def test_fold_ledger_matches_scalar_loop(stripes, n, chunk, init):
    rng = np.random.default_rng(1009 * stripes + 31 * n + chunk)
    crc0 = rng.integers(0, 1 << 32, (stripes, n), dtype=np.uint32)
    got = cks.crc32c_fold_ledger(crc0, chunk, init)
    assert got.dtype == np.uint32 and got.shape == (n,)
    assert got.tolist() == _ledger_loop(crc0, chunk, init)


def test_fold_ledger_without_native_library(monkeypatch):
    """Tables built through the pure-Python crc32c_zeros give the same
    ledger, and the ledger is the crc32c of each column's bytes."""
    rng = np.random.default_rng(29)
    stripes, n, chunk = 129, 10, 1000
    data = rng.integers(0, 256, (stripes, n, chunk), dtype=np.uint8)
    crc0 = np.array([[cks.crc32c(0, data[s, i]) for i in range(n)]
                     for s in range(stripes)], dtype=np.uint32)
    want = _ledger_loop(crc0, chunk, 0xFFFFFFFF)
    assert want == [cks.crc32c(0xFFFFFFFF, data[:, i].tobytes())
                    for i in range(n)]
    cks._zero_advance_tables.cache_clear()
    monkeypatch.setattr(native, "get_lib", lambda: None)
    try:
        got = cks.crc32c_fold_ledger(crc0, chunk, 0xFFFFFFFF)
    finally:
        cks._zero_advance_tables.cache_clear()
    assert got.tolist() == want


def test_fold_ledger_cold_tables_cost_one_step_of_native_calls(
        monkeypatch):
    """A cold table build makes 32 crc32c_zeros calls, whatever the
    radix: further steps are numpy products, not native calls."""
    calls = []
    zeros = cks.crc32c_zeros

    def counted(crc, length):
        calls.append(length)
        return zeros(crc, length)

    cks._zero_advance_tables.cache_clear()
    monkeypatch.setattr(cks, "crc32c_zeros", counted)
    try:
        tables = cks._zero_advance_tables(4096, 256)
    finally:
        cks._zero_advance_tables.cache_clear()
    assert calls == [4096] * 32
    assert tables.shape == (256, 4, 256)
    for p in (0, 1, 100, 255):
        for x in (1, 0x80, 0xFF):
            assert int(tables[p, 2, x]) == zeros(x << 16, (255 - p) * 4096)


class TestXxhash:
    def test_xxh32_empty(self):
        assert cks.xxh32(b"", 0) == 0x02CC5D05

    def test_xxh64_empty(self):
        assert cks.xxh64(b"", 0) == 0xEF46DB3751D8E999

    def test_native_matches_python(self):
        rng = np.random.default_rng(11)
        if native.get_lib() is None:
            pytest.skip("no native lib")
        for ln in (0, 1, 3, 4, 5, 15, 16, 17, 31, 32, 33, 100, 4096):
            buf = rng.integers(0, 256, ln, dtype=np.uint8).tobytes()
            for seed in (0, 1, 0xDEADBEEF):
                assert cks.xxh32(buf, seed) == cks._py_xxh32(buf, seed)
                assert cks.xxh64(buf, seed) == cks._py_xxh64(buf, seed)


@pytest.mark.skipif(not cks.HAVE_JAX, reason="jax required")
class TestCrc32cTpu:
    def test_batch_matches_host(self):
        rng = np.random.default_rng(13)
        for nblk, blen in ((1, 64), (4, 64), (8, 4096), (3, 100), (5, 1)):
            blocks = rng.integers(0, 256, (nblk, blen), dtype=np.uint8)
            out = np.asarray(cks.crc32c_batch_tpu(blocks, init=0xFFFFFFFF))
            for i in range(nblk):
                assert out[i] == cks.crc32c(0xFFFFFFFF, blocks[i]), (nblk, blen, i)

    def test_batch_seed_zero(self):
        rng = np.random.default_rng(17)
        blocks = rng.integers(0, 256, (4, 300), dtype=np.uint8)
        out = np.asarray(cks.crc32c_batch_tpu(blocks, init=0))
        for i in range(4):
            assert out[i] == cks.crc32c(0, blocks[i])


class TestChecksummer:
    @pytest.mark.parametrize("name", ["crc32c", "crc32c_16", "crc32c_8",
                                      "xxhash32", "xxhash64"])
    def test_roundtrip(self, name):
        t = cs.get_csum_string_type(name)
        rng = np.random.default_rng(19)
        data = rng.integers(0, 256, 8 * 4096, dtype=np.uint8).tobytes()
        csum = bytearray()
        cs.Checksummer.calculate(t, 4096, 0, len(data), data, csum)
        assert len(csum) == 8 * cs.get_csum_value_size(t)
        assert cs.Checksummer.verify(t, 4096, 0, len(data), data, csum) == -1

    def test_detects_corruption(self):
        t = cs.CSUM_CRC32C
        rng = np.random.default_rng(23)
        data = bytearray(rng.integers(0, 256, 4 * 4096, dtype=np.uint8).tobytes())
        csum = bytearray()
        cs.Checksummer.calculate(t, 4096, 0, len(data), data, csum)
        data[2 * 4096 + 17] ^= 0xFF
        bad = cs.Checksummer.verify(t, 4096, 0, len(data), data, csum)
        assert bad == 2 * 4096

    def test_partial_range_update(self):
        t = cs.CSUM_CRC32C
        rng = np.random.default_rng(29)
        data = rng.integers(0, 256, 4 * 1024, dtype=np.uint8).tobytes()
        csum = bytearray()
        cs.Checksummer.calculate(t, 1024, 0, len(data), data, csum)
        # re-checksum only block 2 and verify the vector is unchanged
        before = bytes(csum)
        cs.Checksummer.calculate(t, 1024, 2 * 1024, 1024, data, csum)
        assert bytes(csum) == before

    def test_names(self):
        assert cs.get_csum_type_string(cs.CSUM_CRC32C) == "crc32c"
        assert cs.get_csum_string_type("xxhash64") == cs.CSUM_XXHASH64
        with pytest.raises(ValueError):
            cs.get_csum_string_type("nope")


def test_crc32c_partial_bits_words_matches_bytes():
    """The word-layout crc path (device-native int32 rows) produces
    the same crcs as the uint8 path and the host oracle."""
    import jax.numpy as jnp

    from ceph_tpu.ops import checksum as cks

    rng = np.random.default_rng(21)
    block = 4096
    data = rng.integers(0, 256, (6, block), dtype=np.uint8)
    consts = cks.make_crc_consts(block)
    want = [cks.crc32c(0, row.tobytes()) for row in data]
    got_bytes = np.asarray(cks.crc32c_pack_bits(
        cks.crc32c_partial_bits(jnp.asarray(data), consts)))
    words = jnp.asarray(
        np.ascontiguousarray(data).view(np.int32))  # (6, 1024)
    got_words = np.asarray(cks.crc32c_pack_bits(
        cks.crc32c_partial_bits_words(words, consts)))
    assert [int(c) for c in got_bytes] == want
    assert [int(c) for c in got_words] == want


def test_crc_pallas_blocks_bit_exact():
    """ops/crc_pallas.py: the MXU crc kernel (interpret mode on CPU)
    must be bit-exact vs the host crc across block sizes, seeds, and
    non-tile-aligned block counts."""
    import numpy as np

    from ceph_tpu.ops import checksum as cks
    from ceph_tpu.ops import crc_pallas

    if not crc_pallas.HAVE_JAX:
        import pytest

        pytest.skip("no jax")
    import jax.numpy as jnp

    crc_pallas.FORCE_INTERPRET = True
    try:
        rng = np.random.default_rng(11)
        for length, n in [(4096, 5), (4096, 130), (512, 9), (64, 3)]:
            data = rng.integers(0, 256, (n, length), dtype=np.uint8)
            words = jnp.asarray(data.view(np.int32))
            for init in (0, 0xFFFFFFFF, 0xDEADBEEF):
                got = np.asarray(crc_pallas.crc32c_blocks_words(
                    words, length, init=init))
                want = np.array(
                    [cks.crc32c(init, row.tobytes()) for row in data],
                    dtype=np.uint32)
                assert np.array_equal(got, want), (length, n, init)
    finally:
        crc_pallas.FORCE_INTERPRET = False
