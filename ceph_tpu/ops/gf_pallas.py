"""Pallas TPU kernels for GF(2^8) matrix x data — the hot EC path.

Formulation: packed-word xtime.  Each int32 lane carries 4 data bytes.
Multiplying a whole word by x (the GF(2^8) doubling step, polynomial
0x11d) is 6 bitwise lane-ops with cross-byte contamination masked off:

    t   = v & 0x80808080        # bit 7 of every byte
    u   = (v << 1) & 0xfefefefe # shift, drop cross-byte carry-in
    out = u ^ ((t >> 7) * 0x1d) # reduce by p(x) per byte

A coefficient c contributes the XOR of the xtime-powers selected by its
set bits, so `parity = M (*) data` is a short XOR network over 8 power
ladders — ~13 VPU lane-ops per data byte, HBM traffic exactly
data-in + parity-out.  Measured on a v5e chip: ~360 GiB/s of data for
RS k=8,m=3 (vs ~19 GiB/s for the XLA bit-decomposition path, whose bf16
bit-plane materialization is HBM-bound).

Two kernels share the ladder:

* specialized: the coefficient matrix is baked in at trace time and the
  XOR network unrolls to straight-line VPU code.  Fastest, but Mosaic
  pays a large one-time compile per matrix — so it is reserved for
  *registered* encode matrices (the codec registers its generator at
  init; see `register_matrix`).
* generic: the coefficient matrix is a runtime SMEM operand; one compile
  per (r, k, geometry) covers every erasure pattern.  This is the decode
  path — Reed-Solomon decode matrices vary per erasure signature and
  per-pattern recompiles (~1 min each through the AOT helper) would
  stall recovery.

Layout contract (the part that makes or breaks performance): the device
representation of EC buffers is int32 *words*, shape (B, K, S//512, 128)
— full (sublane, lane) tiles.  uint8 device arrays are NOT accepted:
a device-side uint8<->int32 bitcast is a lane-regrouping relayout that
costs more than the entire encode (measured: ~30 ms per 64 MiB, which
is what previously capped this kernel at 2 GiB/s).  Host bytes view as
words for free (`words_from_bytes`).

The xtime identity is textbook GF(2^8) arithmetic; the reference's SIMD
equivalents live in /root/reference/src/erasure-code/ (jerasure/
gf-complete PSHUFB tables, isa-l; e.g. ErasureCodeIsa.cc:119-131).
"""

from __future__ import annotations

import functools
import os

import numpy as np
from ceph_tpu.common import flags

try:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

# Rows of 128 int32 lanes per tile.  TS=64 measured fastest on v5e
# (364 GiB/s vs 339 at TS=128 for RS 8+3).
_TS = 64

_M80 = int(0x80808080) - (1 << 32)  # as signed int32 literals
_MFE = int(0xFEFEFEFE) - (1 << 32)

# Encode matrices registered by codecs: these (and only these) get the
# unrolled specialized kernel; everything else uses the generic one.
# Each maps to the size of its unrolled XOR network (`register_matrix`).
_registered: dict = {}

# Test hook: force interpret-mode pallas (runs on CPU) regardless of
# platform, so the kernel logic is exercised in the CPU test tier.
FORCE_INTERPRET = False


def _coeff_key(matrix: np.ndarray) -> tuple:
    m = np.asarray(matrix, dtype=np.uint8)
    return tuple(tuple(int(c) for c in row) for row in m)


def register_matrix(matrix: np.ndarray) -> None:
    """Mark a generator matrix as hot: it will be compiled into the
    specialized unrolled kernel on first use (compile cost amortized
    across the lifetime of the codec).  Records the kernel's XOR
    network: ``parity_rows`` output rows and ``xor_terms``, the set
    bits of the coefficients (one XOR of a ladder power each)."""
    key = _coeff_key(matrix)
    if key not in _registered and len(_registered) < 64:
        _registered[key] = {
            "parity_rows": len(key),
            "xor_terms": sum(bin(c).count("1") for row in key
                             for c in row)}


def registered(matrix: np.ndarray):
    """The XOR network ``register_matrix`` recorded for this matrix
    (a dict of ``parity_rows`` and ``xor_terms``), or None where the
    matrix is not registered and takes the generic kernel."""
    return _registered.get(_coeff_key(matrix))


def words_from_bytes(data: np.ndarray) -> np.ndarray:
    """(..., S) uint8 host array -> (..., S//512, 128) int32 view (free)."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    s = data.shape[-1]
    assert s % 512 == 0, s
    return data.view(np.int32).reshape(*data.shape[:-1], s // 512, 128)


def bytes_from_words(words: np.ndarray) -> np.ndarray:
    """(..., R4, 128) int32 host array -> (..., R4*512) uint8 view (free)."""
    words = np.ascontiguousarray(words, dtype=np.int32)
    r4 = words.shape[-2]
    return words.view(np.uint8).reshape(*words.shape[:-2], r4 * 512)


def supported(data_shape, platform: str | None = None) -> bool:
    """True when the words kernel can run: a TPU backend (or forced
    interpret mode) and S a multiple of 512 bytes (one (1,128) int32
    row).  CEPH_TPU_PALLAS=0 is the kill switch."""
    if not flags.enabled("CEPH_TPU_PALLAS"):
        return False
    if not HAVE_JAX:
        return False
    if not FORCE_INTERPRET:
        try:
            plat = platform or jax.devices()[0].platform
        except Exception:
            return False
        if plat != "tpu":
            return False
    s = data_shape[-1]
    return s % 512 == 0 and s > 0


if HAVE_JAX:

    def _xtime(v):
        """Multiply every packed byte by x in GF(2^8)/0x11d (6 lane-ops).

        The >>7 must be a LOGICAL shift: int32 arithmetic shift would
        smear the sign across the top byte's reduction mask."""
        t = v & jnp.int32(_M80)
        u = (v << 1) & jnp.int32(_MFE)
        hi = jax.lax.shift_right_logical(t, jnp.int32(7))
        return u ^ (hi * jnp.int32(0x1D))

    def _spec_kernel(d_ref, o_ref, *, coeffs, k: int, r: int):
        """Coefficients static: the double loop unrolls at trace time
        into straight-line vector code (XOR network over the ladder)."""
        v = d_ref[0]                       # (K, TS, 128) int32
        acc = [None] * r
        u = [v[i] for i in range(k)]
        for s in range(8):
            for j in range(r):
                for i in range(k):
                    if (coeffs[j][i] >> s) & 1:
                        acc[j] = u[i] if acc[j] is None else acc[j] ^ u[i]
            if s != 7:
                u = [_xtime(x) for x in u]
        zero = None
        for j in range(r):
            if acc[j] is None:
                if zero is None:
                    zero = jnp.zeros_like(v[0])
                acc[j] = zero
            o_ref[0, j] = acc[j]

    def _gen_kernel(m_ref, d_ref, o_ref, *, k: int, r: int):
        """Coefficients from SMEM: mask = -bit broadcasts a scalar into
        an AND, so one compile covers every matrix of this shape."""
        v = d_ref[0]
        u = [v[i] for i in range(k)]
        pows = [u]
        for _ in range(7):
            u = [_xtime(x) for x in u]
            pows.append(u)
        for j in range(r):
            acc = None
            for i in range(k):
                c = m_ref[j, i]
                for s in range(8):
                    term = pows[s][i] & (-((c >> s) & 1))
                    acc = term if acc is None else acc ^ term
            o_ref[0, j] = acc

    def x32(call):
        """Trace a pallas_call with x64 off, whatever the process or
        caller state: under x64 the BlockSpec index maps trace to i64,
        which Mosaic refuses to lower ("failed to legalize operation
        'func.return'")."""

        @functools.wraps(call)
        def run(*args):
            with jax.enable_x64(False):
                return call(*args)

        return run

    @functools.lru_cache(maxsize=128)
    def _spec_call(coeffs, b: int, r4: int, ts: int,
                   interpret: bool = False):
        r, k = len(coeffs), len(coeffs[0])
        kern = functools.partial(_spec_kernel, coeffs=coeffs, k=k, r=r)
        return x32(pl.pallas_call(
            kern,
            grid=(b, r4 // ts),
            in_specs=[pl.BlockSpec((1, k, ts, 128),
                                   lambda bi, ti: (bi, 0, ti, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, r, ts, 128),
                                   lambda bi, ti: (bi, 0, ti, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b, r, r4, 128), jnp.int32),
            interpret=interpret,
            name="gf_words",
        ))

    @functools.lru_cache(maxsize=64)
    def _gen_call(r: int, k: int, b: int, r4: int, ts: int,
                  interpret: bool = False):
        kern = functools.partial(_gen_kernel, k=k, r=r)
        return x32(pl.pallas_call(
            kern,
            grid=(b, r4 // ts),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, k, ts, 128),
                                   lambda bi, ti: (bi, 0, ti, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, r, ts, 128),
                                   lambda bi, ti: (bi, 0, ti, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b, r, r4, 128), jnp.int32),
            interpret=interpret,
            name="gf_words_smem",
        ))

    def _pick_ts(r4: int) -> int:
        ts = min(_TS, r4)
        while r4 % ts:
            ts //= 2
        return ts

    def gf_matmul_words(matrix: np.ndarray, words):
        """(R,K) GF(2^8) matrix x (B,K,R4,128) int32 device words ->
        (B,R,R4,128) int32 device words.  Dispatches the specialized
        kernel for registered matrices, the generic one otherwise."""
        key = _coeff_key(matrix)
        r, k = len(key), len(key[0])
        b, kk, r4, lanes = words.shape
        assert kk == k and lanes == 128, (words.shape, matrix.shape)
        ts = _pick_ts(r4)
        if key in _registered:
            return _spec_call(key, b, r4, ts, FORCE_INTERPRET)(words)
        mwords = jnp.asarray(np.asarray(matrix, np.uint8).astype(np.int32))
        return _gen_call(r, k, b, r4, ts, FORCE_INTERPRET)(mwords, words)

    def gf_matmul_words_runtime(mwords, words):
        """Traceable words-kernel entry: the (R,K) coefficient matrix is
        a RUNTIME int32 operand (the generic SMEM kernel), so one
        compile per shape covers every matrix — the decode path's
        contract (per-erasure-signature matrices must not retrace)."""
        b, k, r4, lanes = words.shape
        r = mwords.shape[0]
        assert lanes == 128
        return _gen_call(r, k, b, r4, _pick_ts(r4),
                         FORCE_INTERPRET)(mwords, words)

    def gf_matmul_pallas(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        """Host entry: (..., K, S) uint8 numpy -> (..., R, S) uint8 numpy
        (leading dims flattened into the kernel batch axis).

        Host<->word conversions are numpy views (free); the transfer and
        the kernel are the only real costs."""
        data = np.asarray(data)
        lead = data.shape[:-2]
        k, s = data.shape[-2:]
        data = data.reshape((-1, k, s) if lead else (1, k, s))
        w = jnp.asarray(words_from_bytes(data))
        out = np.asarray(gf_matmul_words(matrix, w))
        res = bytes_from_words(out)
        return res.reshape(*lead, res.shape[-2], s) if lead else res[0]
