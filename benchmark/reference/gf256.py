"""GF(2^8) arithmetic and jerasure's coding matrices, in numpy.

Field arithmetic as galois.c has it at w=8 (primitive polynomial 0x11D);
the coding matrices as jerasure's reed_sol.c builds them, which Ceph's
jerasure plugin takes for technique=reed_sol_van and reed_sol_r6_op.
Independent of ceph_tpu: the benchmark's plain reference for the parity
a pool of these techniques must store.
"""

from __future__ import annotations

import functools

import numpy as np

PRIM_POLY = 0x11D


@functools.lru_cache(maxsize=None)
def _exp_log():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= PRIM_POLY
    exp[255:510] = exp[:255]
    return exp, log


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    exp, log = _exp_log()
    return int(exp[log[a] + log[b]])


def div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by zero")
    if a == 0:
        return 0
    exp, log = _exp_log()
    return int(exp[(log[a] - log[b]) % 255])


@functools.lru_cache(maxsize=None)
def mul_table() -> np.ndarray:
    """(256, 256) uint8: mul_table()[a, b] = a * b."""
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(256):
        for b in range(256):
            t[a, b] = mul(a, b)
    return t


def _big_vandermonde_distribution(rows: int, cols: int) -> list:
    """reed_sol.c's reed_sol_big_vandermonde_distribution_matrix at w=8,
    step for step: the extended Vandermonde matrix (rows 1, i, i^2, ...
    with the unit rows at top and bottom) made systematic by column
    operations (swapping rows where a pivot is zero); then each column's
    coding part scaled so that coding row 0 is all ones; then each later
    coding row scaled so that it starts with one."""
    d = [[0] * cols for _ in range(rows)]
    d[0][0] = 1
    d[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        x = 1
        for j in range(cols):
            d[i][j] = x
            x = mul(x, i)
    for i in range(1, cols):
        r = next((r for r in range(i, rows) if d[r][i]), None)
        if r is None:
            raise ValueError("reed_sol_van: no pivot")
        d[i], d[r] = d[r], d[i]
        if d[i][i] != 1:
            inv = div(1, d[i][i])
            for row in d:
                row[i] = mul(inv, row[i])
        for j in range(cols):
            e = d[i][j]
            if j != i and e:
                for row in d:
                    row[j] ^= mul(e, row[i])
    for j in range(cols):
        if d[cols][j] != 1:
            inv = div(1, d[cols][j])
            for r in range(cols, rows):
                d[r][j] = mul(inv, d[r][j])
    for r in range(cols + 1, rows):
        if d[r][0] != 1:
            inv = div(1, d[r][0])
            d[r] = [mul(x, inv) for x in d[r]]
    return d


def reed_sol_van(k: int, m: int) -> np.ndarray:
    """(m, k) coding matrix of reed_sol_vandermonde_coding_matrix(k, m,
    8): the last m rows of the distribution matrix.  Its coding row 0 and
    column 0 are all ones."""
    return np.array(_big_vandermonde_distribution(k + m, k)[k:],
                    dtype=np.uint8)


def reed_sol_r6(k: int) -> np.ndarray:
    """(2, k) coding matrix of reed_sol_r6_coding_matrix(k, 8): P is the
    XOR of the data chunks, Q the sum of 2^j times chunk j, as
    reed_sol_r6_encode computes it by Horner's rule."""
    out = np.ones((2, k), dtype=np.uint8)
    x = 1
    for j in range(k):
        out[1, j] = x
        x = mul(x, 2)
    return out


def coding_matrix(technique: str, k: int, m: int) -> np.ndarray:
    """The (m, k) coding matrix of a jerasure technique at w=8."""
    if technique == "reed_sol_van":
        return reed_sol_van(k, m)
    if technique == "reed_sol_r6_op":
        if m != 2:
            raise ValueError("reed_sol_r6_op has m=2")
        return reed_sol_r6(k)
    raise ValueError(f"no reference for technique {technique!r}")


def matmul(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(m, k) x (k, L) uint8 over GF(2^8) -> (m, L), one table lookup per
    coefficient and byte."""
    t = mul_table()
    out = np.zeros((matrix.shape[0], data.shape[1]), dtype=np.uint8)
    for i in range(matrix.shape[0]):
        for j in range(matrix.shape[1]):
            c = int(matrix[i, j])
            if c == 1:
                out[i] ^= data[j]
            elif c:
                out[i] ^= t[c][data[j]]
    return out
