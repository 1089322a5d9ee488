"""Sharded storage pipeline: EC encode/decode + hinfo CRC over a chip mesh.

This is the multi-chip version of the EC-on-OSD hot path (SURVEY.md §3.2):
stripe batches are data-parallel over the mesh "dp" axis, and each chunk's
byte axis is sequence-parallel over "sp" — the striping idea of
libradosstriper/ECUtil (reference src/osd/ECUtil.h:27-80) mapped onto ICI.

Per step, entirely on-device under one shard_map:
  1. parity = GF(2^8) generator matmul (bit-decomposed on the MXU); purely
     local — the byte axis is elementwise for the code, so "sp" needs no
     collective here;
  2. per-chunk hinfo crc32c (ECUtil::HashInfo, reference ECUtil.h:101-160):
     each device folds its byte segment to 32 partial-CRC bits, then an
     all_gather over "sp" + log-free linear fold with zero-run advance
     matrices combines segments — the cross-chip traffic is 32 bits per
     chunk, not the data;
  3. optional CRUSH placement of each stripe's PG via the vmapped straw2
     kernel (replicated over "sp").

Decode runs the same matmul with host-inverted decode rows
(ErasureCodeIsa-style table cache lives in the codec).
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ec import plan
from ceph_tpu.ops import checksum as cks
from ceph_tpu.ops import gf


def _shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Logical axis rules (the T5X partitioner pattern, SNIPPETS [1]/[2])
# ---------------------------------------------------------------------------

# The EC data plane's logical axes and where each lands on the chip
# mesh.  `stripe` is data-parallel over the DCN-across-hosts x
# ICI-within-host data axes — ("dcn", "dp"), the T5X hybrid-mesh
# pattern: stripes are plentiful and independent, so the slow
# cross-host interconnect carries nothing per-byte; `shard` (the k+m
# chunk axis) stays WITHIN a chip — a stripe's shards share the
# generator matmul, and splitting them would turn a local MXU product
# into cross-chip traffic; `byte` may be sequence-parallel over "sp"
# (elementwise for the code, so only the 32-bit CRC fold ever crosses
# ICI — and never DCN).  The product-path mesh plans (ec/plan.py) use
# stripe-parallel meshes (hybrid ("dcn", "dp") across hosts, flat
# ("dp",) within one); the dryrun exercises the sp>1 byte split.
LOGICAL_AXIS_RULES = (("stripe", ("dcn", "dp")), ("shard", None),
                      ("byte", "sp"))


def logical_spec(*logical_axes, rules=LOGICAL_AXIS_RULES,
                 mesh: Optional[Mesh] = None):
    """PartitionSpec for an array whose dims carry the given logical
    axis names (None = unnamed/replicated dim).  A rule may map to
    ONE mesh axis or a TUPLE of them (`stripe` -> ("dcn", "dp"));
    axes ABSENT from `mesh` are dropped — a single-host ("dp",)
    stripe mesh resolves `stripe` to plain "dp", a hybrid mesh to the
    ("dcn", "dp") pair, and a mesh with neither to replicated — so
    the same array spec works on any mesh shape, which is what lets a
    shrunken (or single-host) mesh reuse the same kernel builders."""
    table = dict(rules)
    names = []
    axes = set(mesh.axis_names) if mesh is not None else None
    for ax in logical_axes:
        m = table.get(ax) if ax is not None else None
        if isinstance(m, tuple):
            present = tuple(a for a in m
                            if axes is None or a in axes)
            m = (None if not present
                 else present[0] if len(present) == 1 else present)
        elif m is not None and axes is not None and m not in axes:
            m = None
        names.append(m)
    return P(*names)


def data_parallel_size(mesh: Mesh) -> int:
    """The number of stripe-parallel ways a mesh provides: the
    product of its data axes (dcn x dp) — what batch divisibility and
    per-chip whole-stripe rounding key on."""
    shape = dict(mesh.shape)
    return shape.get("dcn", 1) * shape.get("dp", 1)


def stripe_mesh(devices) -> Mesh:
    """A stripe-parallel mesh over the given devices: one stripe
    sub-batch per chip, shards and bytes within-chip — the product
    path's mesh shape (ec/plan.py mesh plans).  Devices spanning more
    than one host (parallel/multihost.py topology) lay out as a
    hybrid ("dcn", "dp") mesh — DCN across hosts, dp within — and a
    single host's set stays the flat ("dp",) mesh, bit-identical to
    the PR-9 shape."""
    from ceph_tpu.parallel import multihost

    return multihost.hybrid_stripe_mesh(devices)


def build_mesh_encode_crc(mesh: Mesh, chunk_bytes: int, label: str):
    """Compiled mesh fused encode + per-chunk zero-seeded crc32c:
    (mbits, (B, k, S)) -> (parity (B, m, S), crcs (B, k+m) packed
    bits).  Traces plan.fused_encode_crc_step — the SAME kernel the
    single-device plan jits, so single-vs-mesh bit-exactness is by
    construction — sharded stripe-parallel; with whole chunks
    on-chip the CRC needs no cross-chip fold, and parity + CRC stay
    device-resident between the stages inside ONE dispatch.  Returns
    (jitted_fn, input_sharding)."""
    from ceph_tpu.ec import plan
    from ceph_tpu.ops import checksum as cks

    consts = cks.make_crc_consts(chunk_bytes)
    data_spec = logical_spec("stripe", "shard", "byte", mesh=mesh)
    crc_spec = logical_spec("stripe", "shard", mesh=mesh)
    local_step = functools.partial(plan.fused_encode_crc_step,
                                   consts=consts)
    fn = _shard_map(local_step, mesh=mesh,
                    in_specs=(P(), data_spec),
                    out_specs=(data_spec, crc_spec))
    return (plan.tracked_jit(label, fn),
            NamedSharding(mesh, data_spec))


class ShardedPipeline:
    """A compiled multi-chip encode(+hinfo crc)(+placement) step."""

    def __init__(self, mesh: Mesh, k: int, m: int, chunk_bytes: int,
                 matrix: np.ndarray, csum_init: int = 0xFFFFFFFF,
                 placement_rule=None, result_max: int = 0):
        self.mesh = mesh
        self.k, self.m = k, m
        self.chunk_bytes = chunk_bytes
        # partial meshes (a shrunken healthy set, or a pure ("dp",)
        # stripe mesh) may lack any axis: an absent axis is size 1,
        # not an error — the same pipeline code serves every shape.
        # dp is the TOTAL stripe-parallel width (dcn x dp on a hybrid
        # multi-host mesh)
        shape = dict(mesh.shape)
        self.sp = shape.get("sp", 1)
        self.dp = data_parallel_size(mesh)
        if chunk_bytes % self.sp:
            raise ValueError(
                f"chunk_bytes {chunk_bytes} not divisible by sp={self.sp}")
        self.seg = chunk_bytes // self.sp
        self.csum_init = csum_init
        self._mbits = jnp.asarray(gf.gf_matrix_to_bits(matrix))
        self._crc_consts = cks.make_crc_consts(self.seg)
        self._advance_t = cks.make_combine_advance(self.seg)
        self._seed_adv = cks.crc32c_zeros(csum_init & 0xFFFFFFFF, chunk_bytes)
        self._placement_one = (placement_rule.trace_one
                               if placement_rule is not None else None)
        if placement_rule is not None and result_max:
            if placement_rule.result_max != result_max:
                raise ValueError(
                    f"placement_rule yields {placement_rule.result_max} osds"
                    f" per input, caller expected {result_max}")
        self._encode = self._build_encode()
        self._decode_cache = {}
        self._words_cache = {}

    # -- encode + hinfo + placement ---------------------------------------

    def _fold_segments(self, gathered):
        """(P, ..., 32) per-segment partial CRC bits -> (..., 32) total."""
        total = gathered[0]
        for p in range(1, gathered.shape[0]):
            total = cks.crc32c_combine_bits(total, gathered[p],
                                            self._advance_t)
        return total

    def _build_encode(self):
        mesh = self.mesh
        has_sp = "sp" in dict(mesh.shape)

        def local_step(mbits, data, pgs):
            # data (B_l, k, S_l); pgs (B_l,)
            parity = gf.gf2_matmul_bytes(mbits, data)
            chunks = jnp.concatenate([data, parity], axis=1)
            part = cks.crc32c_partial_bits(chunks, self._crc_consts)
            if has_sp:
                # (P, B_l, k+m, 32): combine per-segment partials
                gathered = jax.lax.all_gather(part, "sp")
            else:
                # pure stripe mesh: whole chunks on-chip, no fold
                gathered = part[None]
            crc = cks.crc32c_pack_bits(self._fold_segments(gathered))
            crc = crc ^ jnp.uint32(self._seed_adv)
            if self._placement_one is not None:
                placement = jax.vmap(self._placement_one)(pgs)
            else:
                placement = jnp.zeros((pgs.shape[0], 1), dtype=jnp.int32)
            return parity, crc, placement

        data_spec = logical_spec("stripe", "shard", "byte", mesh=mesh)
        row_spec = logical_spec("stripe", mesh=mesh)
        shard = _shard_map(
            functools.partial(local_step, self._mbits),
            mesh=mesh,
            in_specs=(data_spec, row_spec),
            out_specs=(data_spec, row_spec, row_spec),
        )
        jfn = plan.tracked_jit(
            f"striped.encode k{self.k}m{self.m} S{self.chunk_bytes}",
            shard)
        if self._placement_one is None:
            return jfn

        def run(*args):
            # the CRUSH program's int64 draws need x64 for this trace
            # and call only (crush/kernel.py keeps it scoped)
            with jax.enable_x64(True):
                return jfn(*args)

        return run

    def data_sharding(self) -> NamedSharding:
        return NamedSharding(
            self.mesh, logical_spec("stripe", "shard", "byte",
                                    mesh=self.mesh))

    def pg_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh,
                             logical_spec("stripe", mesh=self.mesh))

    def put_stripes(self, data) -> jax.Array:
        """Place a (B, k, S) host batch onto the mesh with dp/sp sharding."""
        return jax.device_put(jnp.asarray(data, dtype=jnp.uint8),
                              self.data_sharding())

    def encode(self, data, pgs=None):
        """(B, k, S) stripes [+ (B,) pg ids] -> (parity, hinfo crcs, placement).

        parity (B, m, S) stays mesh-sharded; crcs (B, k+m) uint32 and
        placement (B, R) are dp-sharded, sp-replicated.

        The dispatch rides the ec-encode breaker guard (watchdog +
        injection seam); there is no host twin at this mesh layer, so
        an unrecovered failure raises — single-chip callers reach the
        mesh through ec/dispatch.gf_matmul, which owns the bit-exact
        host degradation.
        """
        from ceph_tpu.common import circuit

        b = data.shape[0]
        if b % self.dp:
            raise ValueError(f"batch {b} not divisible by dp={self.dp}")
        if pgs is None:
            pgs = jnp.zeros((b,), dtype=jnp.int32)
        status, out = circuit.device_call(
            "ec-encode", self._encode, data,
            jnp.asarray(pgs, dtype=jnp.int32), batch=int(b),
            label="striped.encode", oom_to_fail=True,
            devices=tuple(d.id for d in self.mesh.devices.flat))
        if status != "ok":
            if isinstance(out, BaseException):
                raise out
            raise RuntimeError(
                f"striped encode unavailable ({status}: ec-encode"
                " breaker)")
        return out

    # -- decode -----------------------------------------------------------

    def _decode_fn(self, rows: int):
        fn = self._decode_cache.get(rows)
        if fn is None:
            mesh = self.mesh

            def local(dmat_bits, survivors):
                return gf.gf2_matmul_bytes(dmat_bits, survivors)

            spec = logical_spec("stripe", "shard", "byte", mesh=mesh)
            shard = _shard_map(
                local, mesh=mesh,
                in_specs=(P(), spec),
                out_specs=spec,
            )
            fn = plan.tracked_jit(
                f"striped.matmul r{rows}k{self.k} S{self.chunk_bytes}",
                shard)
            self._decode_cache[rows] = fn
        return fn

    def decode(self, dmat: np.ndarray, survivors):
        """(B, k, S) surviving chunks x (R, k) decode rows -> (B, R, S)."""
        dmat_bits = jnp.asarray(gf.gf_matrix_to_bits(dmat))
        return self._decode_fn(dmat.shape[0])(dmat_bits, survivors)

    # -- generalized mesh matmul (the codec device dispatch) ---------------

    def matmul(self, mat: np.ndarray, data: np.ndarray):
        """(R, K) x (B, K, S) host batch -> (B, R, S) over the mesh.

        Encode and decode are the same product (decode rows come from
        the codec's signature cache), so this one entry serves both —
        it is what ec/dispatch routes the daemons' device path
        through.  At sp == 1 each device runs the packed-word Pallas
        kernel (host bytes view as words for free); at sp > 1 the byte
        axis is sequence-parallel and the XLA bit-decomposition runs
        under shard_map.
        """
        from ceph_tpu.ops import gf_pallas

        b, k, s = data.shape
        if self.sp == 1 and gf_pallas.supported((b, k, s)):
            return self._matmul_words(mat, data)
        dev = jax.device_put(jnp.asarray(data, dtype=jnp.uint8),
                             self.data_sharding())
        return self.decode(np.asarray(mat, dtype=np.uint8), dev)

    def _matmul_words(self, mat: np.ndarray, data: np.ndarray):
        from ceph_tpu.ops import gf_pallas

        key = gf_pallas._coeff_key(mat)
        if key in gf_pallas._registered:
            # hot encode generators: the unrolled specialized kernel,
            # one compile per registered matrix (bounded set)
            fn = self._words_cache.get(key)
            if fn is None:
                matarr = np.array(key, dtype=np.uint8)

                def local(w):
                    return gf_pallas.gf_matmul_words(matarr, w)

                fn = self._jit_words(local)
                self._words_cache[key] = fn
            args = (fn,)
        else:
            # decode matrices vary per erasure signature: ONE compile
            # per (r, k) shape, matrix as a runtime SMEM operand
            r, k = len(key), len(key[0])
            fn = self._words_cache.get((r, k))
            if fn is None:
                fn = self._jit_words(gf_pallas.gf_matmul_words_runtime,
                                     runtime_mat=True)
                self._words_cache[(r, k)] = fn
            args = (fn, jnp.asarray(
                np.asarray(mat, np.uint8).astype(np.int32)))
        words = jnp.asarray(gf_pallas.words_from_bytes(data))
        sharding = NamedSharding(
            self.mesh, logical_spec("stripe", "shard", None, None,
                                    mesh=self.mesh))
        dw = jax.device_put(words, sharding)
        out = np.asarray(args[0](*args[1:], dw))
        return gf_pallas.bytes_from_words(out)

    def _jit_words(self, local, runtime_mat: bool = False):
        spec = logical_spec("stripe", "shard", None, None,
                            mesh=self.mesh)
        in_specs = (P(), spec) if runtime_mat else (spec,)
        kind = "runtime" if runtime_mat else "spec"
        return plan.tracked_jit(
            f"striped.words.{kind} k{self.k} S{self.chunk_bytes}",
            _shard_map(local, mesh=self.mesh, in_specs=in_specs,
                       out_specs=spec))
