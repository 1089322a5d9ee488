"""Checksums: crc32c / xxhash, host-native and TPU-batched.

Reference parity:
  - `ceph_crc32c(seed, data, len)` — Castagnoli CRC, no pre/post inversion,
    NULL data = zero run (/root/reference/src/include/crc32c.h:43-50).
  - `ceph_crc32c_zeros` O(log n) zero-run folding
    (/root/reference/src/common/crc32c.cc:216-239).
  - xxhash32/64 (vendored xxHash submodule in the reference).

TPU design: a CRC over GF(2) is linear in the message bits —
`crc(seed, msg) = Z_len(seed) XOR f(msg)` with `f` linear.  So a batch of
B equal-length blocks becomes:

  1. split each block into 64-byte cells, unpack to 512 bit-planes;
  2. one (512 -> 32) GF(2) matmul per cell computes per-cell partial CRCs
     — a (B*n, 512) x (512, 32) bf16 matmul on the MXU;
  3. a log-depth tree combine folds cells: left' = A_span @ left XOR right,
     where A_span is the 32x32 zero-run advance matrix (the same math the
     reference tabulates in crc_turbo_table);
  4. the seed's zero-run advance Z_len(seed) is a host scalar XORed in.

Blocks are front-padded with zeros to a power-of-two cell count — leading
zeros are a no-op for the zero-seeded linear part `f`, so padding does not
change the result.
"""

from __future__ import annotations

import functools

import numpy as np

from ceph_tpu import native

try:
    import jax
    import jax.numpy as jnp

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

CASTAGNOLI_POLY_REFLECTED = 0x82F63B78

# ---------------------------------------------------------------------------
# Host path: native C++ with pure-python fallback
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _py_table() -> np.ndarray:
    table = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (CASTAGNOLI_POLY_REFLECTED ^ (c >> 1)) if (c & 1) else (c >> 1)
        table[i] = c
    return table


def _py_crc32c(crc: int, data: bytes) -> int:
    table = _py_table()
    for byte in data:
        crc = int(table[(crc ^ byte) & 0xFF]) ^ (crc >> 8)
    return crc


def _np_u8(data) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data, dtype=np.uint8)
    return np.frombuffer(bytes(data), dtype=np.uint8)


def _as_ptr(arr: np.ndarray):
    import ctypes

    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


@functools.lru_cache(maxsize=1)
def _fast_crc():
    """Direct c_char_p prototype bound to the native symbol: bytes
    pass straight through with no per-call cast (the cast dominated the
    messenger's per-frame crcs at ~20us/call)."""
    lib = native.get_lib()
    if lib is None:
        return None
    import ctypes

    proto = ctypes.CFUNCTYPE(ctypes.c_uint32, ctypes.c_uint32,
                             ctypes.c_char_p, ctypes.c_uint64)
    return proto(("ceph_tpu_crc32c", lib))


_fast_crc_fn = None


def crc32c(crc: int, data, length: int | None = None) -> int:
    """ceph_crc32c: data=None means `length` zero bytes."""
    global _fast_crc_fn
    if data is None:
        return crc32c_zeros(crc, length or 0)
    if isinstance(data, bytes):
        # module-global binding: this is the messenger's per-frame hot
        # path, and even an lru_cache lookup per call shows up
        fast = _fast_crc_fn
        if fast is None:
            fast = _fast_crc_fn = _fast_crc()
        if fast is not None:
            return fast(crc & 0xFFFFFFFF, data, len(data))
    lib = native.get_lib()
    if lib is not None:
        if isinstance(data, (bytearray, memoryview)):
            arr = np.frombuffer(data, dtype=np.uint8)  # zero-copy view
        else:
            arr = _np_u8(data)
        return lib.ceph_tpu_crc32c(crc & 0xFFFFFFFF, _as_ptr(arr), arr.size)
    return _py_crc32c(crc & 0xFFFFFFFF, _np_u8(data).tobytes())


@functools.lru_cache(maxsize=None)
def _py_zero_mats() -> list:
    # mats[r] advances a crc through 2^r zero bytes; GF(2) column form.
    table = _py_table()
    one = [int(table[(1 << b) & 0xFF]) ^ ((1 << b) >> 8) for b in range(32)]
    mats = [one]
    for _ in range(1, 64):
        prev = mats[-1]
        mats.append([_py_mat_vec(prev, col) for col in prev])
    return mats


def _py_mat_vec(mat: list, v: int) -> int:
    out = 0
    b = 0
    while v:
        if v & 1:
            out ^= mat[b]
        v >>= 1
        b += 1
    return out


def crc32c_zeros(crc: int, length: int) -> int:
    """Advance crc through `length` zero bytes in O(log length)."""
    lib = native.get_lib()
    if lib is not None:
        return lib.ceph_tpu_crc32c_zeros(crc & 0xFFFFFFFF, length)
    mats = _py_zero_mats()
    r = 0
    crc &= 0xFFFFFFFF
    while length:
        if length & 1:
            crc = _py_mat_vec(mats[r], crc)
        length >>= 1
        r += 1
    return crc


def crc32c_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """crc(A||B) from crc(A)=crc_a and the zero-seeded crc(B)=crc_b."""
    return crc32c_zeros(crc_a, len_b) ^ crc_b


# most rows one level of crc32c_fold_ledger combines (its tables take
# 4 KiB a row): an object of up to 255 stripes folds in one level
_FOLD_RADIX = 256

_BYTE_BITS = (np.arange(256)[:, None] >> np.arange(8)) & 1    # (256, 8)


def _byte_tables(images: np.ndarray) -> np.ndarray:
    """(..., 32) images of the bits 1 << b under a GF(2)-linear map ->
    (..., 4, 256) byte-sliced tables: entry [j, x] is the image of
    x << 8j."""
    basis = images.reshape(*images.shape[:-1], 4, 1, 8)
    return np.bitwise_xor.reduce(
        np.where(_BYTE_BITS == 1, basis, np.uint32(0)), axis=-1)


def _apply_tables(tables: np.ndarray, v: np.ndarray) -> np.ndarray:
    """The map of (4, 256) byte-sliced `tables`, applied to uint32 `v`."""
    return (tables[0][v & 0xFF] ^ tables[1][(v >> 8) & 0xFF]
            ^ tables[2][(v >> 16) & 0xFF] ^ tables[3][v >> 24])


@functools.lru_cache(maxsize=16)
def _zero_advance_tables(length: int, radix: int) -> np.ndarray:
    """(radix, 4, 256) uint32 byte-sliced tables: entry [p, j, x] is
    x << 8j advanced through (radix - 1 - p) * length zero bytes.

    The advance is linear over GF(2).  A cold build makes 32
    crc32c_zeros calls, the basis images of one `length` step, and
    doubles from there in numpy: steps^K applied to the images of
    steps^0..K-1 gives those of steps^K..2K-1.  An entry is one
    (length, radix) pair in use: objects of up to 255 stripes of one
    chunk size use at most eight."""
    step = np.array([crc32c_zeros(1 << b, length) for b in range(32)],
                    dtype=np.uint32)
    powers = (np.uint32(1) << np.arange(32, dtype=np.uint32))[None]
    while len(powers) < radix:               # radix is a power of two
        tables = _byte_tables(step)
        powers = np.concatenate([powers, _apply_tables(tables, powers)])
        step = _apply_tables(tables, step)
    return _byte_tables(powers[::-1])


def crc32c_fold_ledger(crc0, chunk: int, init: int) -> np.ndarray:
    """Cumulative crc32c of each column's chunks, stripe after stripe.

    `crc0` is an (S, n) array of zero-seeded crc32c of `chunk`-byte
    chunks; column i's result is crc32c(init, chunk_0 || ... ||
    chunk_{S-1}) as uint32[n].  Combining is associative (crc(A||B) =
    Z_len(B)(crc A) ^ crc B), so the rows fold as a tree whose levels
    combine up to _FOLD_RADIX rows: each level front-pads with zero
    rows to whole groups (Z(0) = 0) and folds every group of every
    column at once, one table lookup per byte of every row and one XOR
    reduction.  The seed is one more row in front of the stripes, which
    the tree advances past all of them.  Each numpy or native call may
    give up the interpreter lock, which a busy process hands back only
    after a switch interval, so once its tables are cached the fold
    makes a few numpy calls per level, none per row, and no
    crc32c_zeros call."""
    crc0 = np.asarray(crc0, dtype="<u4")
    n = crc0.shape[1]
    rows = np.concatenate(
        [np.full((1, n), init & 0xFFFFFFFF, "<u4"), crc0])
    lane = np.arange(4)
    length = chunk
    while len(rows) > 1:
        radix = min(1 << (len(rows) - 1).bit_length(), _FOLD_RADIX)
        lead = -len(rows) % radix
        if lead:
            rows = np.concatenate([np.zeros((lead, n), "<u4"), rows])
        lanes = rows.view(np.uint8).reshape(-1, radix, n, 4)
        pos = np.arange(radix)[:, None, None]
        rows = np.bitwise_xor.reduce(
            _zero_advance_tables(length, radix)[pos, lane, lanes],
            axis=(1, 3))
        length *= radix
    return rows[0]


def crc32c_blocks(data, block_size: int, init: int = 0xFFFFFFFF) -> np.ndarray:
    """Per-block crc32c over uniform blocks (host loop, native inner)."""
    arr = _np_u8(data)
    assert arr.size % block_size == 0
    n = arr.size // block_size
    lib = native.get_lib()
    if lib is not None:
        import ctypes

        out = np.empty(n, dtype=np.uint32)
        lib.ceph_tpu_crc32c_blocks(
            _as_ptr(arr), n, block_size, init & 0xFFFFFFFF,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
        return out
    return np.array(
        [_py_crc32c(init & 0xFFFFFFFF,
                    arr[i * block_size:(i + 1) * block_size].tobytes())
         for i in range(n)], dtype=np.uint32)


def xxh32(data, seed: int = 0) -> int:
    lib = native.get_lib()
    arr = _np_u8(data)
    if lib is not None:
        return lib.ceph_tpu_xxh32(_as_ptr(arr), arr.size, seed & 0xFFFFFFFF)
    return _py_xxh32(arr.tobytes(), seed & 0xFFFFFFFF)


def xxh64(data, seed: int = 0) -> int:
    lib = native.get_lib()
    arr = _np_u8(data)
    if lib is not None:
        return lib.ceph_tpu_xxh64(_as_ptr(arr), arr.size,
                                  seed & 0xFFFFFFFFFFFFFFFF)
    return _py_xxh64(arr.tobytes(), seed & 0xFFFFFFFFFFFFFFFF)


# Pure-python xxhash mirrors (independent of the C++ for cross-checking).

_M32 = 0xFFFFFFFF
_M64 = 0xFFFFFFFFFFFFFFFF
_P32 = (2654435761, 2246822519, 3266489917, 668265263, 374761393)
_P64 = (11400714785074694791, 14029467366897019727, 1609587929392839161,
        9650029242287828579, 2870177450012600261)


def _rotl32(x, r):
    return ((x << r) | (x >> (32 - r))) & _M32


def _rotl64(x, r):
    return ((x << r) | (x >> (64 - r))) & _M64


def _py_xxh32(data: bytes, seed: int) -> int:
    p1, p2, p3, p4, p5 = _P32
    n = len(data)
    i = 0
    if n >= 16:
        v = [(seed + p1 + p2) & _M32, (seed + p2) & _M32, seed,
             (seed - p1) & _M32]
        while i + 16 <= n:
            for lane in range(4):
                w = int.from_bytes(data[i:i + 4], "little")
                v[lane] = (_rotl32((v[lane] + w * p2) & _M32, 13) * p1) & _M32
                i += 4
        h = (_rotl32(v[0], 1) + _rotl32(v[1], 7) + _rotl32(v[2], 12)
             + _rotl32(v[3], 18)) & _M32
    else:
        h = (seed + p5) & _M32
    h = (h + n) & _M32
    while i + 4 <= n:
        w = int.from_bytes(data[i:i + 4], "little")
        h = (_rotl32((h + w * p3) & _M32, 17) * p4) & _M32
        i += 4
    while i < n:
        h = (_rotl32((h + data[i] * p5) & _M32, 11) * p1) & _M32
        i += 1
    h ^= h >> 15
    h = (h * p2) & _M32
    h ^= h >> 13
    h = (h * p3) & _M32
    h ^= h >> 16
    return h


def _py_xxh64_round(acc, inp):
    return (_rotl64((acc + inp * _P64[1]) & _M64, 31) * _P64[0]) & _M64


def _py_xxh64(data: bytes, seed: int) -> int:
    p1, p2, p3, p4, p5 = _P64
    n = len(data)
    i = 0
    if n >= 32:
        v = [(seed + p1 + p2) & _M64, (seed + p2) & _M64, seed,
             (seed - p1) & _M64]
        while i + 32 <= n:
            for lane in range(4):
                w = int.from_bytes(data[i:i + 8], "little")
                v[lane] = _py_xxh64_round(v[lane], w)
                i += 8
        h = (_rotl64(v[0], 1) + _rotl64(v[1], 7) + _rotl64(v[2], 12)
             + _rotl64(v[3], 18)) & _M64
        for lane in range(4):
            h = ((h ^ _py_xxh64_round(0, v[lane])) * p1 + p4) & _M64
    else:
        h = (seed + p5) & _M64
    h = (h + n) & _M64
    while i + 8 <= n:
        w = int.from_bytes(data[i:i + 8], "little")
        h = (_rotl64(h ^ _py_xxh64_round(0, w), 27) * p1 + p4) & _M64
        i += 8
    if i + 4 <= n:
        w = int.from_bytes(data[i:i + 4], "little")
        h = (_rotl64(h ^ ((w * p1) & _M64), 23) * p2 + p3) & _M64
        i += 4
    while i < n:
        h = (_rotl64(h ^ ((data[i] * p5) & _M64), 11) * p1) & _M64
        i += 1
    h ^= h >> 33
    h = (h * p2) & _M64
    h ^= h >> 29
    h = (h * p3) & _M64
    h ^= h >> 32
    return h


# ---------------------------------------------------------------------------
# TPU batched crc32c
# ---------------------------------------------------------------------------

_CELL = 64  # bytes per matmul cell


@functools.lru_cache(maxsize=None)
def _zero_advance_matrix(length: int) -> np.ndarray:
    """32x32 GF(2) 0/1 matrix advancing a crc through `length` zero bytes."""
    cols = []
    for b in range(32):
        v = crc32c_zeros(1 << b, length)
        cols.append([(v >> o) & 1 for o in range(32)])
    return np.array(cols, dtype=np.uint8).T  # (out_bit, in_bit)


@functools.lru_cache(maxsize=1)
def _cell_matrix() -> np.ndarray:
    """32x512 GF(2) matrix: zero-seeded crc of one 64-byte cell."""
    cols = []
    buf = np.zeros(_CELL, dtype=np.uint8)
    for i in range(_CELL):
        for b in range(8):
            buf[:] = 0
            buf[i] = 1 << b
            v = crc32c(0, buf)
            cols.append([(v >> o) & 1 for o in range(32)])
    return np.array(cols, dtype=np.uint8).T  # (32, 512)


if HAVE_JAX:

    def _mod2_matmul(bits, mat_t):
        """(..., N) 0/1 x (N, 32) -> (..., 32) over GF(2), on the MXU."""
        prod = jnp.einsum(
            "...n,nk->...k",
            bits.astype(jnp.bfloat16),
            mat_t.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return prod.astype(jnp.int32) & 1

    def make_crc_consts(length: int):
        """Device constants for crc32c_partial_bits over `length`-byte rows."""
        ncells = max(1, -(-length // _CELL))
        levels = max(0, (ncells - 1).bit_length())
        return {
            "length": length,
            "levels": levels,
            "cell_mat_t": jnp.asarray(_cell_matrix().T),
            "advances": tuple(
                jnp.asarray(_zero_advance_matrix(_CELL * (1 << lvl)).T)
                for lvl in range(levels)),
        }

    def crc32c_partial_bits(data, consts):
        """Traceable: (..., L) uint8 -> (..., 32) int32 zero-seeded crc bits.

        L = consts["length"]; rows are front-padded with zeros to a
        power-of-two cell count inside the trace (a no-op for the
        zero-seeded linear part of the CRC).
        """
        length = consts["length"]
        levels = consts["levels"]
        ncells = 1 << levels
        lead = ncells * _CELL - length
        if lead:
            pad = [(0, 0)] * (data.ndim - 1) + [(lead, 0)]
            data = jnp.pad(data, pad)
        cells = data.reshape(*data.shape[:-1], ncells, _CELL)
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((cells[..., :, None] >> shifts) & 1).reshape(
            *data.shape[:-1], ncells, _CELL * 8)
        part = _mod2_matmul(bits, consts["cell_mat_t"])  # (..., n, 32)
        for lvl in range(levels):
            pairs = part.reshape(*part.shape[:-2], part.shape[-2] // 2, 2, 32)
            left = _mod2_matmul(pairs[..., 0, :], consts["advances"][lvl])
            part = left ^ pairs[..., 1, :]
        return part[..., 0, :]

    def crc32c_partial_bits_words(words, consts):
        """crc32c_partial_bits over the device-native int32 WORD layout
        (..., L//4): bit k of a little-endian word is bit k%8 of byte
        k//8, so a 0..31 shift unpack yields exactly the byte-then-bit
        order the cell matrix expects — words stay words, no uint8
        relayout (that relayout costs more than the whole crc)."""
        length = consts["length"]
        levels = consts["levels"]
        ncells = 1 << levels
        lead = (ncells * _CELL - length) // 4
        if lead:
            pad = [(0, 0)] * (words.ndim - 1) + [(lead, 0)]
            words = jnp.pad(words, pad)
        cells = words.reshape(*words.shape[:-1], ncells, _CELL // 4)
        shifts = jnp.arange(32, dtype=jnp.int32)
        bits = ((cells[..., :, None] >> shifts) & 1).reshape(
            *words.shape[:-1], ncells, _CELL * 8)
        part = _mod2_matmul(bits, consts["cell_mat_t"])
        for lvl in range(levels):
            pairs = part.reshape(*part.shape[:-2],
                                 part.shape[-2] // 2, 2, 32)
            left = _mod2_matmul(pairs[..., 0, :],
                                consts["advances"][lvl])
            part = left ^ pairs[..., 1, :]
        return part[..., 0, :]

    def crc32c_pack_bits(bits):
        """(..., 32) 0/1 int32 -> (...,) uint32."""
        return jnp.sum(bits.astype(jnp.uint32)
                       << jnp.arange(32, dtype=jnp.uint32),
                       axis=-1, dtype=jnp.uint32)

    def crc32c_combine_bits(left_bits, right_bits, advance_t):
        """GF(2) combine: crc(A||B) bits from zero-seeded partials.

        advance_t is the transposed 32x32 zero-run matrix for len(B)
        (from make_combine_advance).
        """
        return _mod2_matmul(left_bits, advance_t) ^ right_bits

    def make_combine_advance(length: int):
        """Transposed 32x32 advance matrix for combining over `length` bytes."""
        return jnp.asarray(_zero_advance_matrix(length).T)

    @functools.lru_cache(maxsize=None)
    def _crc_batch_kernel(length: int):
        consts = make_crc_consts(length)

        @jax.jit
        def kernel(data):
            return crc32c_pack_bits(crc32c_partial_bits(data, consts))

        return kernel

    def crc32c_batch_tpu(blocks: np.ndarray, init: int = 0xFFFFFFFF):
        """crc32c of each row of a (B, L) uint8 array, on device.

        Returns a (B,) uint32 device array: cell matmul + tree combine for
        the zero-seeded linear part, XOR the host-folded seed advance.
        """
        blocks = np.asarray(blocks, dtype=np.uint8)
        assert blocks.ndim == 2
        _, length = blocks.shape
        f = _crc_batch_kernel(length)(jnp.asarray(blocks))
        seed_adv = crc32c_zeros(init & 0xFFFFFFFF, length)
        return f ^ jnp.uint32(seed_adv)
