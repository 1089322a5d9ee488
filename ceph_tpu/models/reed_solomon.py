"""Generator-matrix constructions for Reed-Solomon and Cauchy codes.

These reproduce the *published* constructions used by the reference's default
plugin (jerasure's reed_sol.c / cauchy.c, per Plank's tutorial and its 2003
correction) so that encoded chunks are bit-identical with the reference for
technique=reed_sol_van / reed_sol_r6_op / cauchy_orig at w=8
(/root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.cc:200-204,
:252-255, :327).  Implementation is original, written from the algorithm (extended
Vandermonde -> systematic by column ops -> coding columns scaled so the
first coding row is all ones -> each later coding row scaled so that it
starts with one, reed_sol.c's last step); the single Field-parameterized
copy lives in models/gf_wide.py and serves w in {8, 16, 32}.
"""

from __future__ import annotations

import numpy as np

from ceph_tpu.ops.gf import gf_div, gf_inv, gf_mul, gf_pow


def reed_sol_van_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) coding matrix, jerasure reed_sol_vandermonde_coding_matrix(w=8).

    ONE implementation serves every word size: the Field-parameterized
    construction in models/gf_wide.py (this w=8 entry is what the
    golden-vector and independent-derivation tests pin, so wide words
    inherit the pinned algorithm rather than a drifting copy)."""
    if k + m > 256:
        raise ValueError("k+m must be <= 256 for w=8")
    from ceph_tpu.models.gf_wide import reed_sol_van_matrix_w

    return reed_sol_van_matrix_w(k, m, 8)


def reed_sol_r6_matrix(k: int) -> np.ndarray:
    """(2, k) RAID-6 matrix: row0 = ones (P), row1 = powers of 2 (Q).

    jerasure reed_sol_r6_coding_matrix; technique=reed_sol_r6_op.
    """
    m = np.zeros((2, k), dtype=np.uint8)
    m[0, :] = 1
    for j in range(k):
        m[1, j] = gf_pow(2, j)
    return m


def cauchy_orig_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) Cauchy matrix: element (i, j) = 1 / (i XOR (m + j)) in GF(2^8).

    jerasure cauchy_original_coding_matrix; technique=cauchy_orig.
    """
    if k + m > 256:
        raise ValueError("k+m must be <= 256 for w=8")
    out = np.zeros((m, k), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            out[i, j] = gf_div(1, i ^ (m + j))
    return out


def cauchy_good_matrix(k: int, m: int) -> np.ndarray:
    """Improved Cauchy matrix (jerasure cauchy_good technique).

    jerasure's "good" variant rescales the original Cauchy matrix to minimize
    the bit-matrix one-count: divide column j by element (0, j) so row 0 is
    all ones, then for each subsequent row pick the row divisor yielding the
    fewest bits.  We implement the row-0 normalization and per-row best-divisor
    search over the row's own elements, the documented improvement strategy.
    """
    mat = cauchy_orig_matrix(k, m)
    for j in range(k):
        a = int(mat[0, j])
        if a != 1:
            mat[:, j] = gf_mul(mat[:, j], np.uint8(gf_inv(a)))
    from ceph_tpu.ops.gf import gf_const_to_bits

    def row_ones(row: np.ndarray) -> int:
        return int(sum(gf_const_to_bits(int(c)).sum() for c in row))

    for i in range(1, m):
        best = mat[i].copy()
        best_ones = row_ones(best)
        for div in set(int(c) for c in mat[i] if c > 1):
            cand = gf_mul(mat[i], np.uint8(gf_inv(div)))
            ones = row_ones(cand)
            if ones < best_ones:
                best, best_ones = cand, ones
        mat[i] = best
    return mat


def decode_matrix(coding: np.ndarray, k: int, erasures: list[int],
                  have: list[int]) -> np.ndarray:
    """Rows mapping the k chosen surviving chunks -> the erased chunks.

    Mirrors the role of jerasure_matrix_decode / isa_decode
    (/root/reference/src/erasure-code/isa/ErasureCodeIsa.cc:151-311): build the
    generator rows of the k surviving chunks, invert, then express every
    erased chunk (data via the inverse, coding via re-encoding) as a GF(2^8)
    combination of the survivors.

    coding: (m, k) coding matrix.  have: exactly k surviving chunk ids in the
    order their buffers will be stacked.  Returns (len(erasures), k).
    """
    from ceph_tpu.ops.gf import gf_invert_matrix, gf_matmul_ref

    assert len(have) == k
    gen = np.zeros((k, k), dtype=np.uint8)
    for row, c in enumerate(have):
        if c < k:
            gen[row, c] = 1
        else:
            gen[row] = coding[c - k]
    inv = gf_invert_matrix(gen)  # survivors -> original data
    out = np.zeros((len(erasures), k), dtype=np.uint8)
    for row, e in enumerate(erasures):
        if e < k:
            out[row] = inv[e]
        else:
            # erased coding chunk: coding_row @ inv
            out[row] = gf_matmul_ref(coding[e - k : e - k + 1], inv)[0]
    return out
