"""`ec_jax` — the TPU erasure codec (the framework's flagship compute path).

Reference parity: techniques reed_sol_van / reed_sol_r6_op / cauchy_orig /
cauchy_good of the jerasure plugin
(/root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.cc), plus the
isa plugin's decode strategy — invert the surviving k x k generator submatrix
and LRU-cache decode tables keyed by the erasure signature
(/root/reference/src/erasure-code/isa/ErasureCodeIsa.cc:151-311,
ErasureCodeIsaTableCache.cc).

TPU-first design: encode/decode are GF(2) bit-matrix matmuls on the MXU
(ceph_tpu.ops.gf), batched over stripes.  The single-object API matches the
reference interface; the batched API (encode_batch/decode_batch) is what the
object store and benchmarks drive, amortizing host->device transfers over
many stripes per dispatch.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Set, Tuple

import numpy as np

from ceph_tpu.ec import dispatch
from ceph_tpu.ec.interface import ErasureCode, ErasureCodeError, to_bool, to_int
from ceph_tpu.models import reed_solomon as rs
from ceph_tpu.ops import checksum as cks
from ceph_tpu.ops import gf

LARGEST_VECTOR_WORDSIZE = 16  # layout-parity constant from the reference


class ErasureCodeJax(ErasureCode):
    """GF(2^8) matrix codec executed on TPU (or host numpy fallback)."""

    TECHNIQUES = ("reed_sol_van", "reed_sol_r6_op", "cauchy_orig", "cauchy_good")

    def __init__(self, technique: str = "reed_sol_van") -> None:
        super().__init__()
        if technique not in self.TECHNIQUES:
            raise ErasureCodeError(2, f"unknown technique {technique}")
        self.technique = technique
        self.w = 8
        self.per_chunk_alignment = False
        self.packetsize = 2048
        self.matrix: np.ndarray | None = None
        self._decode_cache = dispatch.LruCache(256)
        self.use_tpu = True
        self.tpu_min_bytes = 1  # kernel engages for everything unless configured
        self._plan_sig: str | None = None

    # -- init -------------------------------------------------------------

    def init(self, profile: Dict[str, str]) -> None:
        profile["technique"] = self.technique
        defaults = {"reed_sol_van": ("2", "1"), "reed_sol_r6_op": ("7", "2"),
                    "cauchy_orig": ("7", "3"), "cauchy_good": ("7", "3")}
        dk, dm = defaults[self.technique]
        self.k = to_int("k", profile, dk)
        self.m = to_int("m", profile, dm)
        self.w = to_int("w", profile, "8")
        if self.w not in (8, 16, 32):
            raise ErasureCodeError(22, f"w={self.w} not in {{8, 16, 32}}")
        if self.w != 8 and self.technique != "reed_sol_van":
            # matches the reference: wide words are a reed_sol_van
            # feature; the cauchy/r6 constructions here are w=8
            # (ErasureCodeJerasure.cc:62-78 parses w per technique)
            raise ErasureCodeError(
                22, f"technique {self.technique} supports w=8 only")
        if self.technique == "reed_sol_r6_op" and self.m != 2:
            raise ErasureCodeError(22, "reed_sol_r6_op requires m=2")
        self.per_chunk_alignment = to_bool(
            "jerasure-per-chunk-alignment", profile, "false")
        if self.technique.startswith("cauchy"):
            self.packetsize = to_int("packetsize", profile, "2048")
        want_tpu = to_bool("tpu", profile, "true")
        self.use_tpu = want_tpu and gf.backend_available()
        if want_tpu and not self.use_tpu:
            import logging

            logging.getLogger(__name__).warning(
                "ec_jax %s k=%s m=%s: tpu requested, no jax backend;"
                " the codec runs on the host", self.technique,
                profile.get("k"), profile.get("m"))
        self.tpu_min_bytes = to_int("tpu-min-bytes", profile, "1")
        self.sanity_check_k_m(self.k, self.m)
        mapping = profile.get("mapping")
        if mapping and len(mapping) != self.k + self.m:
            raise ErasureCodeError(
                22, f"mapping {mapping} maps {len(mapping)} chunks, expected"
                f" {self.k + self.m}")
        super().init(profile)
        self._prepare()

    def _prepare(self) -> None:
        if self.technique == "reed_sol_van" and self.w != 8:
            from ceph_tpu.models import gf_wide

            # wide-word Vandermonde (GF(2^16)/GF(2^32)); the device
            # layout is w=8-specific, so wide codecs run the host tier
            self.matrix = gf_wide.reed_sol_van_matrix_w(
                self.k, self.m, self.w)
            self.use_tpu = False
            return
        if self.technique == "reed_sol_van":
            self.matrix = rs.reed_sol_van_matrix(self.k, self.m)
        elif self.technique == "reed_sol_r6_op":
            self.matrix = rs.reed_sol_r6_matrix(self.k)
        elif self.technique == "cauchy_orig":
            self.matrix = rs.cauchy_orig_matrix(self.k, self.m)
        else:
            self.matrix = rs.cauchy_good_matrix(self.k, self.m)
        if self.use_tpu:
            from ceph_tpu.ops import gf_pallas

            # Hot generator matrix: compiles into the specialized
            # unrolled Pallas kernel on first device dispatch.
            gf_pallas.register_matrix(self.matrix)

    # -- geometry (layout-parity with ErasureCodeJerasure) ----------------

    def get_alignment(self) -> int:
        if self.technique.startswith("cauchy"):
            unit = self.w * self.packetsize * 4
            if unit % LARGEST_VECTOR_WORDSIZE:
                return self.k * self.w * self.packetsize * LARGEST_VECTOR_WORDSIZE
            return self.k * unit
        alignment = self.k * self.w * 4
        if (self.w * 4) % LARGEST_VECTOR_WORDSIZE:
            alignment = self.k * self.w * LARGEST_VECTOR_WORDSIZE
        return alignment

    def get_chunk_size(self, object_size: int) -> int:
        if self.per_chunk_alignment:
            alignment = (self.w * LARGEST_VECTOR_WORDSIZE
                         if not self.technique.startswith("cauchy")
                         else self._cauchy_per_chunk_alignment())
            chunk_size = -(-object_size // self.k)
            modulo = chunk_size % alignment
            if modulo:
                chunk_size += alignment - modulo
            return chunk_size
        return super().get_chunk_size(object_size)

    def _cauchy_per_chunk_alignment(self) -> int:
        alignment = self.w * self.packetsize
        modulo = alignment % LARGEST_VECTOR_WORDSIZE
        if modulo:
            alignment += LARGEST_VECTOR_WORDSIZE - modulo
        return alignment

    def supports_result_decode(self) -> bool:
        """True when GF-linear compute kernels commute with this
        codec (the coded-compute pushdown gate, ceph_tpu/compute):
        every plain GF(2^8) matrix technique acts POSITION-WISE on
        bytes, so a kernel result vector satisfies the same code
        relation as the shards and decodes through the normal decode
        path at lane width.  Wide-word (w>8) and cauchy variants mix
        across byte/word boundaries or carry per-chunk alignment the
        lane-width synthetic stripe cannot honor; remapped layouts
        (chunk_mapping) are excluded with them — those codecs take
        the full-decode fallback."""
        return (self.matrix is not None and self.w == 8
                and not self.technique.startswith("cauchy")
                and not self.get_chunk_mapping())

    # -- kernels ----------------------------------------------------------

    def plan_signature(self) -> str:
        """Stable-across-processes identity of this codec's generator
        (the ExecPlan cache key prefix; see ec/plan.py)."""
        if self._plan_sig is None:
            from ceph_tpu.ec import plan

            self._plan_sig = plan.codec_signature(
                self.technique, self.k, self.m, self.w, self.matrix)
        return self._plan_sig

    def _matmul(self, mat: np.ndarray, data: np.ndarray) -> np.ndarray:
        """(R,K) GF matrix x (K,S) or (B,K,S) uint8 -> parity, device-dispatched."""
        if self.w != 8:
            return self._matmul_wide(mat, data)
        encode = mat is self.matrix
        sig = self.plan_signature() if encode else None
        return dispatch.gf_matmul(
            mat, data, self.use_tpu, self.tpu_min_bytes, sig=sig,
            # the generator matmul is the encode family; everything
            # else (inverted decode rows) is ec-decode — each trips
            # and recovers its own breaker
            family="ec-encode" if encode else "ec-decode")

    def _matmul_wide(self, mat: np.ndarray, data: np.ndarray) -> np.ndarray:
        """Host GF(2^w) matmul for w in {16, 32}: chunks viewed as
        little-endian w-bit words (jerasure's word semantics)."""
        from ceph_tpu.models import gf_wide

        f = gf_wide.Field(self.w)
        batched = data.ndim == 3
        if not batched:
            data = data[None]
        b, kk, s = data.shape
        assert s % (self.w // 8) == 0, (s, self.w)
        words = data.view(f.dtype)
        out = np.zeros((b, mat.shape[0], words.shape[-1]), dtype=f.dtype)
        for j in range(mat.shape[0]):
            for i in range(kk):
                c = int(mat[j, i])
                if c == 0:
                    continue
                if c == 1:
                    out[:, j] ^= words[:, i]
                else:
                    out[:, j] ^= f.mul_vec(c, words[:, i])
        res = out.view(np.uint8).reshape(b, mat.shape[0], s)
        return res if batched else res[0]

    def encode_chunks(self, want_to_encode: Set[int],
                      encoded: Dict[int, bytearray]) -> None:
        k, m = self.k, self.m
        # frombuffer reads the bytearrays in place (np.stack owns the
        # copy it needs); parity rows land back via their buffer view
        # — the old bytes()/tobytes() round trip re-copied every
        # chunk twice per encode
        data = np.stack([
            np.frombuffer(encoded[self.chunk_index(i)], dtype=np.uint8)
            for i in range(k)])
        parity = np.ascontiguousarray(self._matmul(self.matrix, data))
        for j in range(m):
            encoded[self.chunk_index(k + j)][:] = parity[j].data

    def decode_chunks(self, want_to_read: Set[int],
                      chunks: Mapping[int, bytes],
                      decoded: Dict[int, bytearray]) -> None:
        k, m = self.k, self.m
        # Positions on disk map to logical chunk ids through chunk_mapping;
        # the generator-matrix math lives in logical space.
        erasures = [i for i in range(k + m) if self.chunk_index(i) not in chunks]
        if not erasures:
            return
        have = [i for i in range(k + m) if self.chunk_index(i) in chunks][:k]
        if len(have) < k:
            raise ErasureCodeError(5, "not enough chunks to decode")
        dmat = self._decode_matrix(tuple(have), tuple(erasures))
        src = np.stack([
            np.frombuffer(decoded[self.chunk_index(i)], dtype=np.uint8)
            for i in have])
        out = np.ascontiguousarray(self._matmul(dmat, src))
        for row, e in enumerate(erasures):
            decoded[self.chunk_index(e)][:] = out[row].data

    def _decode_matrix(self, have: tuple, erasures: tuple) -> np.ndarray:
        """LRU-cached decode rows keyed by (have, erasures) — the signature
        cache of ErasureCodeIsaTableCache."""
        if self.w != 8:
            from ceph_tpu.models import gf_wide

            return self._decode_cache.get_or_compute(
                (have, erasures),
                lambda: gf_wide.decode_matrix_w(
                    self.matrix, self.k, list(erasures), list(have),
                    self.w))
        return self._decode_cache.get_or_compute(
            (have, erasures),
            lambda: rs.decode_matrix(self.matrix, self.k,
                                     list(erasures), list(have)))

    # -- batched API (the TPU-native entry points) ------------------------

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """(B, k, S) uint8 stripes -> (B, m, S) parity in one device dispatch."""
        assert data.ndim == 3 and data.shape[1] == self.k
        return self._matmul(self.matrix, data)

    def decode_batch(self, have: tuple, erasures: tuple,
                     survivors: np.ndarray) -> np.ndarray:
        """(B, k, S) surviving chunks (rows in `have` order) -> erased chunks."""
        dmat = self._decode_matrix(tuple(have), tuple(erasures))
        return self._matmul(dmat, survivors)

    def encode_many_with_crc(self, arrs: Sequence[np.ndarray],
                             init: int = 0
                             ) -> Optional[List[Tuple[np.ndarray,
                                                      np.ndarray]]]:
        """N pending (B_i, k, S) stripe batches -> [(parity_i, crc_i)]
        in order, folded into ONE fused encode+crc dispatch: same-S
        batches concatenate along the stripe axis (the encode
        service's flush path — many concurrent objects, one plan
        call).  None when the fused plan is unavailable (callers fall
        back per item)."""
        if self.w != 8 or not self.use_tpu:
            return None
        from ceph_tpu.ec import plan

        arrs = [np.asarray(a, dtype=np.uint8) for a in arrs]
        if not arrs:
            return []
        s = arrs[0].shape[-1]
        if any(a.ndim != 3 or a.shape[1] != self.k or a.shape[2] != s
               for a in arrs):
            return None
        big = arrs[0] if len(arrs) == 1 else np.concatenate(arrs, axis=0)
        out = plan.encode_with_crc(self.matrix, big,
                                   sig=self.plan_signature())
        if out is None:
            return None
        parity, crcs = out
        if init:
            adv = cks.crc32c_zeros(init & 0xFFFFFFFF, s)
            crcs = crcs ^ np.uint32(adv)
        res: List[Tuple[np.ndarray, np.ndarray]] = []
        off = 0
        for a in arrs:
            b = a.shape[0]
            res.append((parity[off:off + b], crcs[off:off + b]))
            off += b
        return res

    def encode_batch_with_crc(self, data: np.ndarray, init: int = 0
                              ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Fused encode + per-chunk crc32c in one device dispatch:
        (B, k, S) -> (parity (B, m, S), crcs (B, k+m) uint32 seeded
        `init`).  None when the fused plan is unavailable (callers
        fall back to encode + host CRC)."""
        if self.w != 8 or not self.use_tpu:
            return None
        from ceph_tpu.ec import plan

        out = plan.encode_with_crc(self.matrix, data,
                                   sig=self.plan_signature())
        if out is None:
            return None
        parity, crcs = out
        if init:
            # crc32c(init, chunk) = crc32c_zeros(init, S) ^ crc32c(0, chunk)
            adv = cks.crc32c_zeros(init & 0xFFFFFFFF, data.shape[-1])
            crcs = crcs ^ np.uint32(adv)
        return parity, crcs
