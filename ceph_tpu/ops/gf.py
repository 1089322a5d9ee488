"""GF(2^8) algebra for erasure coding, designed TPU-first.

The reference executes Reed-Solomon GF(2^8) products with per-byte table
lookups and SSE/AVX shuffles (jerasure/gf-complete, isa-l; see
/root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.cc:158-175 and
/root/reference/src/erasure-code/isa/ErasureCodeIsa.cc:119-131).  A TPU has no
byte-shuffle unit but has a 128x128 systolic MXU — so we map GF(2^8) linear
algebra onto it by *bit-decomposition*:

  multiplication by a constant c in GF(2^8) is linear over GF(2); it is an
  8x8 0/1 matrix B(c) with column b = bits(c * x^b mod p(x)).  A full
  (m x k) GF(2^8) code matrix therefore becomes an (8m x 8k) GF(2) matrix,
  and `parity = M (*) data` becomes

      parity_bits = (M_bits @ data_bits) mod 2

  — a plain integer matmul followed by a parity reduction, which XLA tiles
  straight onto the MXU.  Sums are bounded by 8k (<= 256 for k <= 32) so the
  accumulation is exact in bf16/int32.

Field: GF(2^8) with primitive polynomial 0x11d and generator x (= 2), the
same field jerasure/gf-complete and isa-l use for w=8, so encoded chunks are
bit-identical with the reference's `reed_sol_van` output (given jerasure's
matrix, each coding row after the first scaled to start with one: see
models/gf_wide.reed_sol_van_matrix_w).

Host-side (numpy) mirrors of each op serve as the independent reference
implementation for tests and for small/latency-sensitive calls.
"""

from __future__ import annotations

import numpy as np

try:  # JAX is the TPU execution path; numpy path works without it.
    import jax
    import jax.numpy as jnp

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

_backend_ok = None


def backend_available() -> bool:
    """True when a jax backend actually initializes.

    `import jax` succeeding does not guarantee a usable backend (e.g.
    JAX_PLATFORMS names a plugin that fails to load); everything that
    device-dispatches gates on this and takes the host path — and the
    failure is logged once, so the host path is never taken unseen."""
    global _backend_ok
    if _backend_ok is None:
        if not HAVE_JAX:
            _backend_ok = False
        else:
            try:
                jax.devices()
                _backend_ok = True
            except Exception as e:
                _backend_ok = False
                import logging

                logging.getLogger(__name__).warning(
                    "no jax backend (%r): every device path runs on the"
                    " host", e)
    return _backend_ok

# ---------------------------------------------------------------------------
# Field tables (host, numpy)
# ---------------------------------------------------------------------------

GF_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1, jerasure/gf-complete w=8 default
GF_ORDER = 256


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)  # doubled to skip the mod-255 on reads
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[0:255]
    return exp, log


GF_EXP, GF_LOG = _build_tables()


def gf_mul(a, b):
    """Elementwise GF(2^8) product of uint8 arrays (numpy)."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    out = GF_EXP[GF_LOG[a] + GF_LOG[b]]
    return np.where((a == 0) | (b == 0), 0, out).astype(np.uint8)


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of 0")
    return int(GF_EXP[255 - GF_LOG[a]])


def gf_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError
    if a == 0:
        return 0
    return int(GF_EXP[(GF_LOG[a] - GF_LOG[b]) % 255])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(GF_EXP[(GF_LOG[a] * n) % 255])


def gf_matmul_ref(m: np.ndarray, d: np.ndarray) -> np.ndarray:
    """Reference GF(2^8) matmul on host: (R,K) x (K,S) -> (R,S), XOR-accumulate.

    Independent oracle for the TPU kernels; also the small-input host path.
    """
    m = np.asarray(m, dtype=np.uint8)
    d = np.asarray(d, dtype=np.uint8)
    r, k = m.shape
    out = np.zeros((r, d.shape[1]), dtype=np.uint8)
    for j in range(r):
        acc = np.zeros(d.shape[1], dtype=np.uint8)
        for i in range(k):
            c = int(m[j, i])
            if c == 0:
                continue
            if c == 1:
                acc ^= d[i]
            else:
                acc ^= gf_mul(np.full((), c, np.uint8), d[i])
        out[j] = acc
    return out


_mul_table_cache = None  # bounded LRU, built lazily (avoids an import
#                          cycle: ec.dispatch imports this module)


def _table_cache():
    global _mul_table_cache
    if _mul_table_cache is None:
        from ceph_tpu.ec.dispatch import LruCache

        _mul_table_cache = LruCache(cap=64)
    return _mul_table_cache


def gf_mul_tables(m: np.ndarray) -> np.ndarray:
    """(R,K) GF matrix -> (R*K, 256) per-coefficient multiply tables
    (the jerasure/isa-l table form consumed by the native region ops).
    LRU-cached: a decode-heavy workload cycling >64 matrices evicts
    the coldest table, never the whole cache."""
    m = np.asarray(m, dtype=np.uint8)
    key = (m.shape, m.tobytes())

    def compute() -> np.ndarray:
        r, k = m.shape
        idx = np.arange(256, dtype=np.uint8)
        tables = np.zeros((r * k, 256), dtype=np.uint8)
        for j in range(r):
            for i in range(k):
                tables[j * k + i] = gf_mul(
                    np.full(256, m[j, i], np.uint8), idx)
        return tables

    return _table_cache().get_or_compute(key, compute)


def gf_matmul_host(m: np.ndarray, d: np.ndarray) -> np.ndarray:
    """Host GF(2^8) matmul through the native SIMD kernel when built
    (AVX2/SSSE3 split-table shuffle — the isa-l/jerasure speed tier,
    ceph_tpu/native/src/gf_simd.cc); numpy reference otherwise."""
    from ceph_tpu import native

    lib = native.get_lib()
    if lib is None or not hasattr(lib, "ceph_tpu_gf_matmul_simd"):
        return gf_matmul_ref(m, d)
    import ctypes

    m = np.asarray(m, dtype=np.uint8)
    d = np.ascontiguousarray(d, dtype=np.uint8)
    r, k = m.shape
    s = d.shape[1]
    tables = gf_mul_tables(m)
    out = np.empty((r, s), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ceph_tpu_gf_matmul_simd(
        tables.ctypes.data_as(u8p), r, k,
        d.ctypes.data_as(u8p), s, out.ctypes.data_as(u8p))
    return out


def gf_invert_matrix(a: np.ndarray) -> np.ndarray:
    """Invert a square GF(2^8) matrix by Gauss-Jordan elimination (host).

    Decode-table construction runs here (k <= 32 — microseconds); the big
    matmul it parameterizes runs on TPU.  Mirrors the role of isa-l's
    gf_invert_matrix (/root/reference/src/erasure-code/isa/ErasureCodeIsa.cc:275).
    """
    a = np.array(a, dtype=np.uint8)
    n = a.shape[0]
    assert a.shape == (n, n)
    aug = np.concatenate([a, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = None
        for row in range(col, n):
            if aug[row, col] != 0:
                pivot = row
                break
        if pivot is None:
            raise np.linalg.LinAlgError("singular GF(2^8) matrix")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        inv = gf_inv(int(aug[col, col]))
        aug[col] = gf_mul(aug[col], np.full((), inv, np.uint8))
        for row in range(n):
            if row != col and aug[row, col] != 0:
                aug[row] ^= gf_mul(aug[col], np.full((), aug[row, col], np.uint8))
    return aug[:, n:]


# ---------------------------------------------------------------------------
# Bit-decomposition: GF(2^8) matrix -> GF(2) matrix
# ---------------------------------------------------------------------------


def gf_const_to_bits(c: int) -> np.ndarray:
    """8x8 GF(2) matrix of 'multiply by c': column b = bits(c * x^b)."""
    cols = []
    for b in range(8):
        v = gf_mul(np.full((), c, np.uint8), np.full((), 1 << b, np.uint8))
        cols.append([(int(v) >> o) & 1 for o in range(8)])
    return np.array(cols, dtype=np.uint8).T  # (out_bit, in_bit)


def gf_matrix_to_bits(m: np.ndarray) -> np.ndarray:
    """(R,K) GF(2^8) matrix -> (8R, 8K) GF(2) 0/1 matrix.

    Row j*8+o, col i*8+b is bit o of (m[j,i] * x^b): output bit (j,o) is the
    XOR over data bits (i,b) selected by this matrix.
    """
    m = np.asarray(m, dtype=np.uint8)
    r, k = m.shape
    out = np.zeros((8 * r, 8 * k), dtype=np.uint8)
    for j in range(r):
        for i in range(k):
            out[j * 8 : j * 8 + 8, i * 8 : i * 8 + 8] = gf_const_to_bits(int(m[j, i]))
    return out


# ---------------------------------------------------------------------------
# TPU kernels (JAX)
# ---------------------------------------------------------------------------

if HAVE_JAX:

    def _unpack_bits(data):
        """(..., K, S) uint8 -> (..., 8K, S) bit planes (LSB-first per byte)."""
        k, s = data.shape[-2], data.shape[-1]
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = (data[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
        return bits.reshape(*data.shape[:-2], k * 8, s)

    def _pack_bits(bits):
        """(..., 8R, S) bits -> (..., R, S) uint8 (LSB-first per byte).

        Bit weighting runs in int32 (TPU-native lane width): the 0/1
        planes times powers-of-two stay exact, and no uint8 `<<`/`*`
        can wrap if a weight or plane is ever wrong upstream.
        """
        r8, s = bits.shape[-2], bits.shape[-1]
        r = r8 // 8
        b = bits.reshape(*bits.shape[:-2], r, 8, s).astype(jnp.int32)
        weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))[None, :, None]
        return jnp.sum(b * weights, axis=-2).astype(jnp.uint8)

    def _gf2_matmul_bytes_impl(mbits, data):
        """GF(2^8) matmul on the MXU: mbits (8R,8K) 0/1, data (..., K, S) uint8.

        Returns (..., R, S) uint8.  The contraction runs as a bf16 matmul
        (exact: sums <= 8K <= 256 < 2^8 representable in bf16's 8-bit
        mantissa... bf16 integers are exact up to 256), then reduced mod 2.

        Untraced body: ec/plan.py traces it inside its plans per
        bucketed shape; the module-level `gf2_matmul_bytes` below is
        the fixed-shape compat wrapper for direct/shard_map callers.
        """
        bits = _unpack_bits(data).astype(jnp.bfloat16)
        mb = mbits.astype(jnp.bfloat16)
        prod = jax.lax.dot_general(
            mb,
            bits,
            (((1,), (bits.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dot_general with no batch dims puts mb's row axis first:
        # (8R, ..., S) -> move to (..., 8R, S)
        if bits.ndim > 2:
            prod = jnp.moveaxis(prod, 0, -2)
        par = prod.astype(jnp.int32) & 1
        return _pack_bits(par)

    # Shape-polymorphic jit kept for direct and shard_map callers (an
    # inner jit is inlined under shard_map); plan-cached dispatch goes
    # through ec/plan.py, which jits _gf2_matmul_bytes_impl itself.
    gf2_matmul_bytes = jax.jit(_gf2_matmul_bytes_impl)

    def gf_matmul_device(m: np.ndarray, data):
        """(R,K) GF(2^8) matrix x (..., K, S) uint8 through the fastest
        device path: the packed-word xtime Pallas kernel on TPU for
        host-side (numpy) inputs (ops/gf_pallas.py — word-layout entry,
        ~360 GiB/s on a v5e), else the XLA bit-decomposition matmul (a
        device-side uint8->int32 relayout would cost more than the
        encode)."""
        from ceph_tpu.ops import gf_pallas

        if isinstance(data, np.ndarray) and gf_pallas.supported(
                np.shape(data)):
            return gf_pallas.gf_matmul_pallas(m, data)
        mbits = jnp.asarray(gf_matrix_to_bits(m))
        return gf2_matmul_bytes(mbits, jnp.asarray(data, dtype=jnp.uint8))

    def gf_matmul_tpu(m: np.ndarray, data):
        """(R,K) GF(2^8) matrix x (..., K, S) uint8 chunks on TPU."""
        return gf_matmul_device(m, data)

    def gf_mul_jax(a, b):
        """Elementwise GF(2^8) product via log/antilog gathers (uint8 arrays)."""
        exp = jnp.asarray(GF_EXP)
        log = jnp.asarray(GF_LOG)
        a = jnp.asarray(a, dtype=jnp.uint8)
        b = jnp.asarray(b, dtype=jnp.uint8)
        out = exp[log[a] + log[b]]
        return jnp.where((a == 0) | (b == 0), jnp.uint8(0), out)
