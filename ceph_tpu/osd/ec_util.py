"""EC stripe arithmetic and shard hashing.

Reference parity: ECUtil (/root/reference/src/osd/ECUtil.{h,cc}):

- stripe_info_t — pure logical<->chunk offset maps over
  stripe_width = k * chunk_size rows (ECUtil.h:27-80);
- ECUtil::encode/decode — adapt whole-object buffers to the per-stripe
  codec (ECUtil.cc);
- HashInfo — cumulative per-shard crc32c kept in an object xattr
  (hinfo_key), the bit-exactness ledger updated on append
  (ECUtil.h:101-160).

TPU-first deviation: where the reference loops stripes calling the codec
once per stripe, `encode`/`decode` here stack all stripes into one
(B, k, chunk) batch and make a single device dispatch through the codec's
batched entry points when available — host<->TPU latency is amortized over
the whole object (SURVEY.md §7 hard part #4).
"""

from __future__ import annotations

import os

from ceph_tpu.common import flags
from typing import Dict, Iterable, List, Mapping, Optional, Set, Tuple

import numpy as np

from ceph_tpu.ops import checksum as cks

HINFO_KEY = "hinfo_key"


def is_hinfo_key_string(key: str) -> bool:
    return key == HINFO_KEY


class StripeInfo:
    """stripe_info_t: stripe_width = stripe_size (k) x chunk_size."""

    def __init__(self, stripe_size: int, stripe_width: int):
        assert stripe_width % stripe_size == 0
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // stripe_size

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def get_stripe_width(self) -> int:
        return self.stripe_width

    def get_chunk_size(self) -> int:
        return self.chunk_size

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return (-(-offset // self.stripe_width)) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - offset % self.stripe_width

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset + (self.stripe_width - rem) if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def aligned_offset_len_to_chunk(self, off_len: Tuple[int, int]
                                    ) -> Tuple[int, int]:
        off, length = off_len
        return (self.aligned_logical_offset_to_chunk_offset(off),
                self.aligned_logical_offset_to_chunk_offset(length))

    def offset_len_to_stripe_bounds(self, off_len: Tuple[int, int]
                                    ) -> Tuple[int, int]:
        off, length = off_len
        start = self.logical_to_prev_stripe_offset(off)
        end_len = self.logical_to_next_stripe_offset((off - start) + length)
        return start, end_len


def encode(sinfo: StripeInfo, ec_impl, data: bytes,
           want: Iterable[int]) -> Dict[int, bytes]:
    """Whole-object encode: (stripes x width) -> per-shard chunk streams.

    Input must be stripe-aligned (callers zero-pad, as the reference tool
    does).  All stripes go through the codec in one batched dispatch when
    the codec exposes encode_batch (the ec_jax path).
    """
    logical_size = len(data)
    assert logical_size % sinfo.get_stripe_width() == 0
    want = set(want)
    out: Dict[int, bytes] = {}
    if logical_size == 0:
        return out

    width = sinfo.get_stripe_width()
    chunk = sinfo.get_chunk_size()
    n_stripes = logical_size // width
    k = width // chunk
    n = ec_impl.get_chunk_count()

    if ec_impl.get_chunk_size(width) != chunk:
        from ceph_tpu.ec.interface import ErasureCodeError

        raise ErasureCodeError(
            22, f"stripe unit {chunk} is incompatible with the codec's"
            f" alignment: a {width}-byte stripe encodes to"
            f" {ec_impl.get_chunk_size(width)}-byte chunks")

    if hasattr(ec_impl, "encode_batch") and not ec_impl.get_chunk_mapping():
        arr = np.frombuffer(data, dtype=np.uint8).reshape(n_stripes, k, chunk)
        # shard-STREAM layout: one contiguous transpose up front, then
        # every downstream step (the matmul, the per-shard bytes) works
        # on contiguous rows — per-stripe dispatch and strided copies
        # both cost more than the whole encode
        streams = np.ascontiguousarray(np.moveaxis(arr, 1, 0))
        # shards leave as FROZEN zero-copy row views (the fused-path
        # discipline): nothing mutates them after the encode, frozen
        # OWNERS are store-adoptable (buffer.is_immutable walks the
        # base chain), and the per-shard tobytes copy was the whole
        # object's size over again.  Freeze before reshaping so the
        # row views' base is the frozen owner.
        streams.setflags(write=False)
        streams = streams.reshape(k, n_stripes * chunk)
        parity = ec_impl.encode_batch(streams[None])[0]  # (m, B*chunk)
        parity = np.ascontiguousarray(parity)
        if parity.base is not None:
            # e.g. a wrapper over a device buffer: own the memory so
            # the frozen-owner contract holds (cost parity with the
            # tobytes this path used to pay)
            parity = parity.copy()
        parity.setflags(write=False)
        for i in range(n):
            if i not in want:
                continue
            out[i] = streams[i].data if i < k else parity[i - k].data
        return out

    # generic path: per-stripe through the interface (array codes, mappings)
    parts: Dict[int, List[bytes]] = {i: [] for i in want}
    mv = memoryview(data) if not isinstance(data, memoryview) else data
    for s in range(n_stripes):
        encoded = ec_impl.encode(want, mv[s * width:(s + 1) * width])
        for i, buf in encoded.items():
            assert len(buf) == chunk
            parts[i].append(buf)
    return {i: b"".join(bufs) for i, bufs in parts.items()}


def encode_with_hinfo(sinfo: StripeInfo, ec_impl, data,
                      want: Iterable[int],
                      logical_len: Optional[int] = None
                      ) -> Tuple[Dict[int, object], "HashInfo",
                                 Optional[int]]:
    """Whole-object encode + per-shard cumulative crc32c in one step.

    Matches ECTransaction::generate_transactions followed by
    HashInfo::append (ECBackend.cc:2000, ECUtil.h:132-147) but fused:
    on the host tier the parity accumulate and every crc run inside
    ONE cache-resident native pass (native/src/datapath.cc), data
    shards come back as zero-copy StridedBuf views of the caller's
    buffer, and the logical content crc32c over data[:logical_len]
    (when asked for) rides along for the write reply's data-digest.
    """
    from ceph_tpu import native

    n = ec_impl.get_chunk_count()
    matrix = getattr(ec_impl, "matrix", None)
    lib = native.get_lib()
    use_device = bool(getattr(ec_impl, "use_tpu", False)) and \
        len(data) >= getattr(ec_impl, "tpu_min_bytes", 1)
    if use_device and matrix is not None \
            and not ec_impl.get_chunk_mapping():
        fused = _encode_with_hinfo_device(sinfo, ec_impl, data, want,
                                          logical_len)
        if fused is not None:
            return fused
    if (matrix is None or ec_impl.get_chunk_mapping() or lib is None
            or use_device
            or not hasattr(lib, "ceph_tpu_ec_encode_noT")):
        from ceph_tpu.common.buffer import as_buffer

        data = as_buffer(data)
        shards = encode(sinfo, ec_impl, data, want)
        hinfo = HashInfo(n)
        hinfo.append(0, shards)
        crc = None
        if logical_len is not None:
            crc = cks.crc32c(0xFFFFFFFF, memoryview(data)[:logical_len])
        return shards, hinfo, crc

    import ctypes

    from ceph_tpu.common.buffer import StridedBuf

    width = sinfo.get_stripe_width()
    chunk = sinfo.get_chunk_size()
    assert len(data) % width == 0
    n_stripes = len(data) // width
    k = width // chunk
    m = n - k
    stream = n_stripes * chunk
    tables = getattr(ec_impl, "_mul_tables", None)
    if tables is None:
        from ceph_tpu.ops import gf

        tables = np.ascontiguousarray(gf.gf_mul_tables(matrix))
        ec_impl._mul_tables = tables
    src = np.frombuffer(data, dtype=np.uint8)
    parity_out = np.empty((max(m, 1), stream), dtype=np.uint8)
    crcs = np.full(n, 0xFFFFFFFF, dtype=np.uint32)
    lcrc = np.full(1, 0xFFFFFFFF, dtype=np.uint32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ceph_tpu_ec_encode_noT(
        tables.ctypes.data_as(u8p), m, k,
        src.ctypes.data_as(u8p), n_stripes, chunk,
        parity_out.ctypes.data_as(u8p), crcs.ctypes.data_as(u32p),
        0 if logical_len is None else logical_len,
        lcrc.ctypes.data_as(u32p) if logical_len is not None else None)
    # data shards stay strided views of the adopted source buffer —
    # no transpose copy is ever made (StridedBuf docstring).  Both
    # shard kinds are frozen read-only: nothing mutates them after the
    # kernel, and only immutable buffers are store-adoptable.
    if src.flags.writeable:
        src.setflags(write=False)
    parity_out.setflags(write=False)
    stripes = src.reshape(n_stripes, k, chunk)
    want = set(want)
    out: Dict[int, object] = {}
    for i in range(n):
        if i not in want:
            continue
        out[i] = StridedBuf(stripes[:, i, :]) if i < k \
            else parity_out[i - k].data
    hinfo = HashInfo(n)
    hinfo.cumulative_shard_hashes = [int(c) for c in crcs]
    hinfo.total_chunk_size = stream
    return out, hinfo, (int(lcrc[0]) if logical_len is not None else None)


def _fuse_min_bytes() -> Optional[int]:
    """Object-size floor for the fused device encode+crc path; None
    disables it.  CEPH_TPU_FUSE_MIN_BYTES overrides (tests set 0).
    Default: 1 MiB on a real TPU backend — that is where fusing the
    parity and hinfo-CRC round-trips into one dispatch pays; on the
    CPU tier the fused path is the native noT kernel below."""
    env = flags.get("CEPH_TPU_FUSE_MIN_BYTES")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            import sys

            # a typo'd knob must not silently disable the fused tier:
            # warn and fall through to the default policy
            print(f"# CEPH_TPU_FUSE_MIN_BYTES={env!r} is not an "
                  "integer; using the default policy",
                  file=sys.stderr)
    from ceph_tpu.ec import plan

    return (1 << 20) if plan.device_platform() == "tpu" else None


def _fused_result(sinfo: StripeInfo, ec_impl, src: np.ndarray,
                  arr: np.ndarray, parity, crc0,
                  want: Iterable[int], logical_len: Optional[int],
                  data) -> Tuple[Dict[int, object], "HashInfo",
                                 Optional[int]]:
    """Assemble one object's (shards, hinfo, data_crc) from the fused
    device outputs: the (stripes, shards) matrix of zero-seeded chunk
    crcs folds into the cumulative per-shard ledger on host in one
    vectorised pass (checksum.crc32c_fold_ledger): a few numpy calls
    per object, none per stripe or shard.
    Zero-copy contract (same as the native tier): data shards are
    strided views of the caller's buffer, parity rows read-only
    memoryviews — the stores adopt immutable buffers, no transpose or
    defensive copies on the hot path."""
    from ceph_tpu.common.buffer import StridedBuf

    n = ec_impl.get_chunk_count()
    chunk = sinfo.get_chunk_size()
    n_stripes, k, _ = arr.shape
    hinfo = HashInfo(n)
    hinfo.cumulative_shard_hashes = [
        int(c) for c in cks.crc32c_fold_ledger(
            np.asarray(crc0)[:, :n], chunk, 0xFFFFFFFF)]
    hinfo.total_chunk_size = n_stripes * chunk
    if src.flags.writeable:
        src.setflags(write=False)
    want = set(want)
    shards: Dict[int, object] = {}
    for i in range(n):
        if i not in want:
            continue
        if i < k:
            shards[i] = StridedBuf(arr[:, i, :])
        else:
            row = np.ascontiguousarray(parity[:, i - k, :]).reshape(-1)
            row.setflags(write=False)
            shards[i] = row.data
    crc = None
    if logical_len is not None:
        crc = cks.crc32c(0xFFFFFFFF, memoryview(data)[:logical_len])
    return shards, hinfo, crc


def _encode_with_hinfo_device(sinfo: StripeInfo, ec_impl, data,
                              want: Iterable[int],
                              logical_len: Optional[int]):
    """Fused DEVICE tier of encode_with_hinfo: stripes batch into one
    (B, k, chunk) plan-cached dispatch that returns parity AND every
    chunk's zero-seeded crc32c (ec/plan.encode_with_crc); the crcs
    fold into the cumulative ledger in _fused_result.  Returns None
    when the fused plan does not apply (callers fall through to the
    host tiers)."""
    fmin = _fuse_min_bytes()
    if fmin is None or len(data) < max(fmin, 1) \
            or not hasattr(ec_impl, "encode_batch_with_crc"):
        return None
    from ceph_tpu.common.buffer import as_buffer

    data = as_buffer(data)
    width = sinfo.get_stripe_width()
    chunk = sinfo.get_chunk_size()
    if len(data) % width or ec_impl.get_chunk_size(width) != chunk:
        return None  # the generic path owns the incompatibility error
    n_stripes = len(data) // width
    k = width // chunk
    src = np.frombuffer(data, dtype=np.uint8)
    arr = src.reshape(n_stripes, k, chunk)
    out = ec_impl.encode_batch_with_crc(arr, init=0)
    if out is None:
        return None
    parity, crc0 = out          # (B, m, chunk), (B, k+m) zero-seeded
    return _fused_result(sinfo, ec_impl, src, arr, parity, crc0,
                         want, logical_len, data)


def device_fused_available(ec_impl) -> bool:
    """True when the fused device encode tier can engage for this
    codec — the encode service's batching gate.  Requires a real
    policy floor (``_fuse_min_bytes()`` is None on the CPU-only
    default, which keeps the service fully inline there), a
    device-enabled codec, and the fused batched entry points."""
    return (_fuse_min_bytes() is not None
            and bool(getattr(ec_impl, "use_tpu", False))
            and not ec_impl.get_chunk_mapping()
            and hasattr(ec_impl, "encode_many_with_crc"))


def encode_many_with_hinfo(sinfo: StripeInfo, ec_impl,
                           items) -> List[Tuple[Dict[int, object],
                                                "HashInfo",
                                                Optional[int]]]:
    """N whole-object encodes of one codec profile in ONE dispatch.

    ``items`` is a sequence of ``(data, want, logical_len)`` tuples;
    returns per-item ``(shards, hinfo, data_crc)`` exactly as
    encode_with_hinfo would produce.  The device tier folds every
    item's stripes into a single fused encode+crc plan call (the
    encode service's flush path); when the fused plan does not apply
    the items run the inline tiers one by one — results are
    bit-identical either way."""
    items = list(items)
    if not items:
        return []
    fused = _encode_many_device(sinfo, ec_impl, items)
    if fused is not None:
        return fused
    packed = _encode_many_bitmatrix(sinfo, ec_impl, items)
    if packed is not None:
        return packed
    return [encode_with_hinfo(sinfo, ec_impl, d, w, logical_len=l)
            for d, w, l in items]


def bitmatrix_native_available(ec_impl) -> bool:
    """True when the packed multi-object NATIVE tape tier can engage
    for this codec — the encode service's batching gate for the
    bitmatrix family (the device gate is device_fused_available).
    Requires the fused native executor (built + CEPH_TPU_NATIVE_XSCHED
    up), the schedule compiler (CEPH_TPU_XSCHED up, matrix within the
    serving-path compile bound), and an identity chunk mapping."""
    from ceph_tpu.ec import xsched

    bm = getattr(ec_impl, "bitmatrix", None)
    return (bm is not None
            and getattr(ec_impl, "_sig", None) is not None
            and not ec_impl.get_chunk_mapping()
            and xsched.enabled()
            and xsched.native_available()
            and xsched.host_compile_allowed(bm))


def _encode_many_bitmatrix(sinfo: StripeInfo, ec_impl, items):
    """Packed multi-object tier for the bitmatrix family: EVERY stripe
    of every item becomes one object of a single native region arena,
    so a flushed bucket of thousands of tiny writes runs as ONE
    compiled XOR tape call, and the per-shard HashInfo crc32c ledger
    folds natively over arena spans in a second call.  Requires
    single-block chunks (chunk == w * packetsize — a chunk's bytes ARE
    its w input regions back to back, so packing is one flat copy per
    item); anything else returns None and the caller runs the items
    inline, bit-identically."""
    if not bitmatrix_native_available(ec_impl):
        return None
    from ceph_tpu.common.buffer import StridedBuf, as_buffer
    from ceph_tpu.ec import xsched

    width = sinfo.get_stripe_width()
    chunk = sinfo.get_chunk_size()
    w, ps = ec_impl.w, ec_impl.packetsize
    n = ec_impl.get_chunk_count()
    k = width // chunk
    if chunk != w * ps or ec_impl.get_chunk_size(width) != chunk \
            or k != ec_impl.k:
        return None
    datas = []
    stripes_of = []
    for d, _want, _l in items:
        d = as_buffer(d)
        if len(d) == 0 or len(d) % width:
            return None
        datas.append(d)
        stripes_of.append(len(d) // width)
    sched = xsched.compile_matrix(ec_impl.bitmatrix, sig=ec_impl._sig)
    prog = xsched.lower_program(sched)
    n_regions, out_base = prog.n_regions, prog.out_base
    total = sum(stripes_of)
    arena = np.empty((total, n_regions, ps), dtype=np.uint8)
    s0 = 0
    for d, ns in zip(datas, stripes_of):
        arena[s0:s0 + ns, :k * w, :] = \
            np.frombuffer(d, dtype=np.uint8).reshape(ns, k * w, ps)
        s0 += ns
    xsched.execute_native(prog, arena)
    # per-shard cumulative crc ledger: one span per (stripe, shard),
    # stripe-ordered so multi-stripe shards fold like HashInfo.append
    m = n - k
    offs = np.concatenate([np.arange(k, dtype=np.int64) * w,
                           out_base + np.arange(m, dtype=np.int64) * w])
    rows = np.arange(total, dtype=np.int64)[:, None] * n_regions
    item_of = np.repeat(np.arange(len(items), dtype=np.int64),
                        stripes_of)
    spans = np.empty((total * n, 3), dtype=np.int32)
    spans[:, 0] = (rows + offs[None, :]).reshape(-1)
    spans[:, 1] = w
    spans[:, 2] = (item_of[:, None] * n
                   + np.arange(n, dtype=np.int64)[None, :]).reshape(-1)
    crcs = np.full(len(items) * n, 0xFFFFFFFF, dtype=np.uint32)
    xsched.crc_regions_native(arena, spans, crcs)
    results = []
    s0 = 0
    for (item, d, ns) in zip(items, datas, stripes_of):
        _data, want, logical_len = item
        src = np.frombuffer(d, dtype=np.uint8)
        if src.flags.writeable:
            src.setflags(write=False)
        grid = src.reshape(ns, k, chunk)
        it = len(results)
        want = set(want)
        shards: Dict[int, object] = {}
        for i in range(n):
            if i not in want:
                continue
            if i < k:
                shards[i] = StridedBuf(grid[:, i, :])
            else:
                row = np.ascontiguousarray(
                    arena[s0:s0 + ns,
                          out_base + (i - k) * w:out_base + (i - k + 1) * w,
                          :]).reshape(-1)
                row.setflags(write=False)
                shards[i] = row.data
        hinfo = HashInfo(n)
        hinfo.cumulative_shard_hashes = [
            int(c) for c in crcs[it * n:(it + 1) * n]]
        hinfo.total_chunk_size = ns * chunk
        crc = None
        if logical_len is not None:
            crc = cks.crc32c(0xFFFFFFFF, memoryview(d)[:logical_len])
        results.append((shards, hinfo, crc))
        s0 += ns
    return results


def _encode_many_device(sinfo: StripeInfo, ec_impl, items):
    """Batched twin of _encode_with_hinfo_device: the fuse-bytes floor
    applies to the TOTAL batch (aggregating small concurrent writes
    past the floor is the service's whole point).  Returns None when
    any item cannot ride the fused plan — the caller then runs all of
    them inline."""
    fmin = _fuse_min_bytes()
    if fmin is None or not getattr(ec_impl, "use_tpu", False) \
            or not hasattr(ec_impl, "encode_many_with_crc") \
            or ec_impl.get_chunk_mapping():
        return None
    width = sinfo.get_stripe_width()
    chunk = sinfo.get_chunk_size()
    if ec_impl.get_chunk_size(width) != chunk:
        return None
    from ceph_tpu.common.buffer import as_buffer

    datas = []
    total = 0
    for d, _w, _l in items:
        d = as_buffer(d)
        if len(d) == 0 or len(d) % width:
            return None
        datas.append(d)
        total += len(d)
    if total < max(fmin, 1) or \
            total < getattr(ec_impl, "tpu_min_bytes", 1):
        return None
    k = width // chunk
    srcs = [np.frombuffer(d, dtype=np.uint8) for d in datas]
    arrs = [s.reshape(-1, k, chunk) for s in srcs]
    out = ec_impl.encode_many_with_crc(arrs, init=0)
    if out is None:
        return None
    results = []
    for (item, d, src, arr, (parity, crc0)) in zip(
            items, datas, srcs, arrs, out):
        _data, want, logical_len = item
        results.append(_fused_result(sinfo, ec_impl, src, arr,
                                     parity, crc0, want, logical_len,
                                     d))
    return results


def encode_many(sinfo: StripeInfo, ec_impl, datas,
                wants) -> List[Dict[int, bytes]]:
    """N plain whole-object encodes (same profile) in one dispatch.

    Shard streams are chunk-aligned, so cross-object batching is
    concatenation along the stripe axis (the recovery-path fold,
    generalized): ONE ``encode`` of the joined bytes, then each
    object's shard slices come back out.  Per-object fallback keeps
    one malformed object from failing the rest."""
    datas = list(datas)
    wants = [set(w) for w in wants]
    assert len(datas) == len(wants)
    width = sinfo.get_stripe_width()
    chunk = sinfo.get_chunk_size()

    def one(d, w) -> Dict[int, bytes]:
        from ceph_tpu.common.buffer import as_buffer

        return encode(sinfo, ec_impl, as_buffer(d), w)

    if len(datas) <= 1 or any(len(d) % width for d in datas):
        return [one(d, w) for d, w in zip(datas, wants)]
    union = set().union(*wants)
    try:
        # join straight off the buffer protocol: b"".join accepts
        # memoryview/bytearray parts, so wrapping each in bytes()
        # first would copy every payload TWICE per batched encode
        # (hot-path-copy worklist fix: ~10.3ms -> ~0.16ms for a
        # 32x256KiB batch join, measured JAX_PLATFORMS=cpu)
        joined = b"".join(datas)
        full = encode(sinfo, ec_impl, joined, union)
    except Exception:
        return [one(d, w) for d, w in zip(datas, wants)]
    out: List[Dict[int, bytes]] = []
    offsets = {s: 0 for s in union}
    for d, w in zip(datas, wants):
        shard_len = (len(d) // width) * chunk
        shards = {}
        # offsets advance for EVERY union shard — each item owns a
        # shard_len slice of every joined stream whether or not it
        # asked for that shard
        for s in union:
            if s in w:
                shards[s] = full.get(s, b"")[
                    offsets[s]:offsets[s] + shard_len]
            offsets[s] += shard_len
        out.append(shards)
    return out


def fastest_survivors(ec_impl, have: Mapping[int, bytes], k: int,
                      prefer=None) -> Dict[int, bytes]:
    """Choose a decodable subset of survivor shard streams.

    The payloads are already fetched, so decode COST dominates the
    choice: available data shards always rank first (all-data decode
    is a free interleave — no GF dispatch), and only the erasure
    fill-ins among parity shards follow the caller's rank order
    (fastest peers first — the hedge tracker's EWMA ranking feeds
    `prefer`; the fetch-side fan-out is where EWMAs buy latency).

    Grows the candidate set in that order until the codec's
    minimum_to_decode accepts it, then returns exactly the minimum
    streams.  Deterministic for a fixed rank, so objects decoded in
    the same wave keep sharing survivor sets (the decode_many
    batching key).  Raises the codec's error when even the full
    survivor set cannot decode — the caller's below-k handling owns
    that, same as a direct minimum_to_decode call."""
    if not have:
        raise ValueError("no survivors")
    want = {ec_impl.chunk_index(i) for i in range(k)}
    rank = prefer if prefer is not None else (lambda s: (s,))
    order = sorted(have, key=lambda s: (s not in want, rank(s)))
    for j in range(min(k, len(order)), len(order) + 1):
        try:
            minimum = ec_impl.minimum_to_decode(want, set(order[:j]))
        except Exception:
            if j >= len(order):
                raise
            continue
        return {i: have[i] for i in minimum}
    raise AssertionError("unreachable")  # loop returns or re-raises


def choose_decode_set(ec_impl, have: Mapping[int, bytes], k: int,
                      prefer=None, first_k: bool = False,
                      ) -> Optional[Dict[int, bytes]]:
    """fastest_survivors plus the daemon's standard failure policy —
    one idiom instead of a try/rank/fallback copy at every call site.

    Returns the minimal decodable survivor map.  When no subset
    decodes: the first k shards by index if `first_k` (recovery paths
    that defer below-k adjudication to the decode attempt itself),
    else None (read paths that answer EIO)."""
    try:
        return fastest_survivors(ec_impl, have, k, prefer=prefer)
    except Exception:
        if first_k:
            return {s: have[s] for s in sorted(have)[:k]}
        return None


def decode_many(sinfo: StripeInfo, ec_impl,
                maps) -> List[bytes]:
    """N decode requests (same profile) -> logical byte streams.

    Requests sharing a survivor-shard set concatenate their per-shard
    streams and decode in ONE dispatch (the recovery-wave fold, shared
    with the read path); a failed group retries per request so one
    malformed object cannot poison its group."""
    maps = list(maps)
    out: List[Optional[bytes]] = [None] * len(maps)
    groups: Dict[tuple, List[int]] = {}
    for i, m in enumerate(maps):
        groups.setdefault(tuple(sorted(m)), []).append(i)
    chunk = sinfo.get_chunk_size()
    width = sinfo.get_stripe_width()
    for key, idxs in groups.items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = decode(sinfo, ec_impl, maps[i])
            continue
        try:
            # same zero-copy join as encode_many: the sub-read reply
            # payloads are bytes-like already
            streams = {s: b"".join(maps[i][s] for i in idxs)
                       for s in key}
            folded = memoryview(decode(sinfo, ec_impl, streams))
            off = 0
            for i in idxs:
                stream_len = len(next(iter(maps[i].values())))
                span = (stream_len // chunk) * width
                # view per request: the fold's output is sliced, not
                # re-copied, on its way back to each caller
                out[i] = folded[off:off + span]
                off += span
        except Exception:
            for i in idxs:
                out[i] = decode(sinfo, ec_impl, maps[i])
    return out  # type: ignore[return-value]


def decode(sinfo: StripeInfo, ec_impl,
           to_decode: Mapping[int, bytes]) -> bytes:
    """Per-shard chunk streams -> the original logical byte stream."""
    assert to_decode
    chunk = sinfo.get_chunk_size()
    width = sinfo.get_stripe_width()
    k = width // chunk
    total = len(next(iter(to_decode.values())))
    assert total % chunk == 0
    for buf in to_decode.values():
        assert len(buf) == total
    if total == 0:
        return b""
    n_stripes = total // chunk

    have = tuple(sorted(to_decode))
    want = tuple(range(k))
    erased = tuple(i for i in want if i not in to_decode)
    if not erased and not ec_impl.get_chunk_mapping():
        cols = [np.frombuffer(to_decode[i], dtype=np.uint8).reshape(
            n_stripes, chunk) for i in range(k)]
        # the stack IS the interleave; hand out a frozen view of it
        # instead of paying tobytes (a second whole-object pass)
        full = np.stack(cols, axis=1)
        full.setflags(write=False)
        return full.reshape(-1).data
    if hasattr(ec_impl, "decode_batch") and not ec_impl.get_chunk_mapping() \
            and len(have) >= k:
        survivors = np.stack([
            np.frombuffer(to_decode[i], dtype=np.uint8).reshape(
                n_stripes, chunk)
            for i in have[:k]], axis=1)             # (B, k, chunk)
        recovered = ec_impl.decode_batch(have[:k], erased, survivors)
        cols = []
        for i in range(k):
            if i in to_decode:
                cols.append(np.frombuffer(
                    to_decode[i], dtype=np.uint8).reshape(n_stripes, chunk))
            else:
                cols.append(np.asarray(recovered[:, erased.index(i), :]))
        full = np.stack(cols, axis=1)
        full.setflags(write=False)
        return full.reshape(-1).data

    from ceph_tpu.common.buffer import as_buffer

    out = []
    # slice views, not byte ranges: one memoryview per stream, every
    # per-stripe chunk a zero-copy window of it (as_buffer adapts
    # StridedBuf shards with their one cached materialization)
    views = {i: memoryview(as_buffer(buf))
             for i, buf in to_decode.items()}
    for s in range(n_stripes):
        chunks = {i: mv[s * chunk:(s + 1) * chunk]
                  for i, mv in views.items()}
        row = ec_impl.decode_concat(chunks)
        assert len(row) == width
        out.append(row)
    return b"".join(out)


class HashInfo:
    """Cumulative per-shard crc32c ledger (ECUtil.h:101-160)."""

    def __init__(self, num_chunks: int = 0):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes: List[int] = [0xFFFFFFFF] * num_chunks
        self.projected_total_chunk_size = 0

    def append(self, old_size: int, to_append: Mapping[int, bytes]) -> None:
        assert old_size == self.total_chunk_size
        appended = 0
        for shard, buf in to_append.items():
            appended = len(buf)
            if self.has_chunk_hash():
                assert shard < len(self.cumulative_shard_hashes)
                self.cumulative_shard_hashes[shard] = cks.crc32c(
                    self.cumulative_shard_hashes[shard], buf)
        self.total_chunk_size += appended

    def clear(self) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [
            0xFFFFFFFF] * len(self.cumulative_shard_hashes)

    def get_chunk_hash(self, shard: int) -> int:
        assert shard < len(self.cumulative_shard_hashes)
        return self.cumulative_shard_hashes[shard]

    def get_total_chunk_size(self) -> int:
        return self.total_chunk_size

    def get_total_logical_size(self, sinfo: StripeInfo) -> int:
        return self.total_chunk_size * (
            sinfo.get_stripe_width() // sinfo.get_chunk_size())

    def has_chunk_hash(self) -> bool:
        return bool(self.cumulative_shard_hashes)

    def set_total_chunk_size_clear_hash(self, new_chunk_size: int) -> None:
        self.cumulative_shard_hashes = []
        self.total_chunk_size = new_chunk_size

    # -- wire/xattr form --------------------------------------------------

    def to_dict(self) -> dict:
        return {"total_chunk_size": self.total_chunk_size,
                "cumulative_shard_hashes": list(self.cumulative_shard_hashes)}

    @classmethod
    def from_dict(cls, d: dict) -> "HashInfo":
        hi = cls(0)
        hi.total_chunk_size = int(d["total_chunk_size"])
        hi.cumulative_shard_hashes = [
            int(x) for x in d["cumulative_shard_hashes"]]
        return hi
