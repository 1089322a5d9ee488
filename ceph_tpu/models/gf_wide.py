"""GF(2^16) and GF(2^32) field arithmetic for wide-word Reed-Solomon.

Reference parity: jerasure/gf-complete support w in {8, 16, 32} for
technique=reed_sol_van (ErasureCodeJerasure.cc:62-78 parses w; the
gf-complete submodule is empty in the reference tree, so the field
parameters here are gf-complete's PUBLISHED defaults: primitive
polynomials 0x1100B for w=16 and 0x400007 for w=32).

w=16 uses log/antilog tables (128 KiB — trivial).  w=32 cannot table a
4-billion-element field; multiplication is vectorized carry-less
multiply + polynomial reduction (the same math gf-complete's SPLIT/
CARRY_FREE implementations compute), and inversion is
exponentiation by 2^32 - 2 (Fermat), cached per matrix coefficient.
"""

from __future__ import annotations

import functools

import numpy as np

POLY16 = 0x1100B
POLY32 = 0x400007  # x^32 + x^22 + x^2 + x + 1 (gf-complete default)


# ---------------------------------------------------------------------------
# GF(2^16): log/antilog tables
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _tables16():
    exp = np.zeros(131070, dtype=np.uint16)
    log = np.zeros(65536, dtype=np.int32)
    x = 1
    for i in range(65535):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x10000:
            x ^= POLY16
    exp[65535:] = exp[:65535]
    return exp, log


def mul16(a, b):
    """Elementwise GF(2^16) product of uint16 arrays/scalars."""
    exp, log = _tables16()
    a = np.asarray(a, dtype=np.uint16)
    b = np.asarray(b, dtype=np.uint16)
    out = exp[log[a] + log[b]]
    return np.where((a == 0) | (b == 0), np.uint16(0), out)


def inv16(a: int) -> int:
    exp, log = _tables16()
    if a == 0:
        raise ZeroDivisionError("GF(2^16) inverse of 0")
    return int(exp[(65535 - log[a]) % 65535])


# ---------------------------------------------------------------------------
# GF(2^32): carry-less multiply + reduction (vectorized)
# ---------------------------------------------------------------------------

def mul32(coeff: int, data):
    """GF(2^32) product of one coefficient with a uint32 array.

    clmul via shift-accumulate over the coefficient's set bits into a
    64-bit intermediate, then reduction by POLY32 from the top bit
    down — the schoolbook carry-free multiply gf-complete's
    CARRY_FREE path computes with PCLMULQDQ.
    """
    d = np.asarray(data, dtype=np.uint64)
    acc = np.zeros_like(d)
    c = int(coeff)
    b = 0
    while c:
        if c & 1:
            acc ^= d << np.uint64(b)
        c >>= 1
        b += 1
    # reduce the 64-bit intermediates mod x^32 + (POLY32 & 0xffffffff)
    red = np.uint64(POLY32 & 0xFFFFFFFF)
    for bit in range(62, 31, -1):
        mask = (acc >> np.uint64(bit)) & np.uint64(1)
        acc ^= (mask * red) << np.uint64(bit - 32)
        acc &= ~(mask << np.uint64(bit))
    return acc.astype(np.uint32)


def _mul32_scalar(a: int, b: int) -> int:
    return int(mul32(a, np.array([b], dtype=np.uint32))[0])


@functools.lru_cache(maxsize=4096)
def inv32(a: int) -> int:
    """a^(2^32 - 2) by square-and-multiply (Fermat inverse)."""
    if a == 0:
        raise ZeroDivisionError("GF(2^32) inverse of 0")
    result, base = 1, a
    e = (1 << 32) - 2
    while e:
        if e & 1:
            result = _mul32_scalar(result, base)
        base = _mul32_scalar(base, base)
        e >>= 1
    return result


# ---------------------------------------------------------------------------
# field façade used by the wide Vandermonde construction
# ---------------------------------------------------------------------------

class Field:
    """Scalar ops for one word size (8 delegates to ops.gf)."""

    def __init__(self, w: int):
        assert w in (8, 16, 32)
        self.w = w
        self.dtype = {8: np.uint8, 16: np.uint16, 32: np.uint32}[w]

    def mul(self, a: int, b: int) -> int:
        if self.w == 8:
            from ceph_tpu.ops import gf

            return int(gf.gf_mul(np.uint8(a), np.uint8(b)))
        if self.w == 16:
            return int(mul16(np.uint16(a), np.uint16(b)))
        return _mul32_scalar(a, b)

    def inv(self, a: int) -> int:
        if self.w == 8:
            from ceph_tpu.ops import gf

            return gf.gf_inv(a)
        if self.w == 16:
            return inv16(a)
        return inv32(a)

    def mul_vec(self, coeff: int, data):
        """coeff x uint<w> array, vectorized."""
        if self.w == 8:
            from ceph_tpu.ops import gf

            return gf.gf_mul(np.asarray(data, np.uint8), np.uint8(coeff))
        if self.w == 16:
            return mul16(data, np.uint16(coeff))
        return mul32(coeff, data)


def invert_matrix_w(mat: np.ndarray, w: int) -> np.ndarray:
    """Gauss-Jordan inverse over GF(2^w)."""
    f = Field(w)
    n = mat.shape[0]
    a = mat.astype(np.uint64).copy()
    inv = np.eye(n, dtype=np.uint64)
    for col in range(n):
        pivot = None
        for r in range(col, n):
            if a[r, col]:
                pivot = r
                break
        if pivot is None:
            raise ValueError("singular matrix")
        if pivot != col:
            a[[col, pivot]] = a[[pivot, col]]
            inv[[col, pivot]] = inv[[pivot, col]]
        if a[col, col] != 1:
            c = f.inv(int(a[col, col]))
            for j in range(n):
                a[col, j] = f.mul(int(a[col, j]), c)
                inv[col, j] = f.mul(int(inv[col, j]), c)
        for r in range(n):
            if r != col and a[r, col]:
                c = int(a[r, col])
                for j in range(n):
                    a[r, j] ^= f.mul(int(a[col, j]), c)
                    inv[r, j] ^= f.mul(int(inv[col, j]), c)
    return inv.astype(f.dtype)


def decode_matrix_w(coding: np.ndarray, k: int, erasures: list,
                    have: list, w: int) -> np.ndarray:
    """models/reed_solomon.decode_matrix generalized over GF(2^w)."""
    f = Field(w)
    assert len(have) == k
    gen = np.zeros((k, k), dtype=np.uint64)
    for row, c in enumerate(have):
        if c < k:
            gen[row, c] = 1
        else:
            gen[row] = coding[c - k]
    inv = invert_matrix_w(gen, w).astype(np.uint64)
    out = np.zeros((len(erasures), k), dtype=np.uint64)
    for row, e in enumerate(erasures):
        if e < k:
            out[row] = inv[e]
        else:
            for j in range(k):
                acc = 0
                for t in range(k):
                    acc ^= f.mul(int(coding[e - k, t]), int(inv[t, j]))
                out[row, j] = acc
    return out.astype(f.dtype)


def reed_sol_van_matrix_w(k: int, m: int, w: int) -> np.ndarray:
    """The jerasure reed_sol_van construction over GF(2^w) (the w=8
    path in models/reed_solomon.py generalized to wide words), as
    reed_sol.c's reed_sol_big_vandermonde_distribution_matrix builds it:
    extended Vandermonde -> systematize by column ops -> scale coding
    columns so the first coding row is all ones -> scale each later
    coding row so that it starts with one."""
    f = Field(w)
    rows, cols = k + m, k
    v = np.zeros((rows, cols), dtype=np.uint64)
    v[0, 0] = 1
    if rows > 1:
        v[rows - 1, cols - 1] = 1
        for i in range(1, rows - 1):
            acc = 1
            for j in range(cols):
                v[i, j] = acc
                acc = f.mul(acc, i)
    # systematize (column ops)
    for i in range(k):
        if v[i, i] == 0:
            for j in range(i + 1, k):
                if v[i, j] != 0:
                    v[:, [i, j]] = v[:, [j, i]]
                    break
            else:
                raise ValueError("vandermonde not reducible")
        if v[i, i] != 1:
            c = f.inv(int(v[i, i]))
            for r in range(rows):
                v[r, i] = f.mul(int(v[r, i]), c)
        for j in range(k):
            if j != i and v[i, j] != 0:
                c = int(v[i, j])
                for r in range(rows):
                    v[r, j] ^= f.mul(int(v[r, i]), c)
    # scale coding columns so coding row 0 is all ones
    coding = v[k:]
    for j in range(k):
        if coding[0, j] not in (0, 1):
            c = f.inv(int(coding[0, j]))
            for r in range(m):
                coding[r, j] = f.mul(int(coding[r, j]), c)
    # scale each later coding row so its first element is one
    for r in range(1, m):
        if coding[r, 0] not in (0, 1):
            c = f.inv(int(coding[r, 0]))
            for j in range(k):
                coding[r, j] = f.mul(int(coding[r, j]), c)
    return coding.astype(f.dtype)
