"""Pallas TPU kernel for batched crc32c on the packed-word layout.

crc32c is GF(2)-linear: the zero-seeded crc of an L-byte block is a
(32 x 8L) 0/1 matrix applied to the block's bits.  Fold the per-cell
matrices and the tree of zero-advance combines (ops/checksum.py's
formulation) into ONE precomputed (8L, 32) matrix M, and the crc of a
whole block is a single GF(2) matmul:

    crc_bits = block_bits @ M   (mod 2)

~256 MACs per data byte — MXU work, not VPU work.  The XLA path
(checksum.crc32c_partial_bits_words) materializes the 8x bit expansion
in HBM between the unpack and the matmul, which caps it at ~8 GiB/s;
here the unpack happens per-tile in VMEM and never touches HBM, so
traffic is data-in + 32 bits out.

Bit-index bookkeeping: the kernel never reshapes bits.  For each bit
position k in 0..31 it extracts the (B, W) 0/1 plane of bit k of every
int32 word and multiplies by M_k = M[k::32] — mathematically identical
to the flat (B, 8L) @ (8L, 32) product, but expressible as 32 clean
(B, W) x (W, 128) MXU dots with no in-kernel relayout.  Accumulation
is exact in int32 (int8 x int8 -> int32 MXU dots, sums bounded by 8L);
mod-2 happens once at the end.

Input layout matches ops/gf_pallas.py: int32 words, bit k of word w =
bit k%8 of byte 4w + k//8 (little-endian view of the byte stream) —
device EC buffers are already in this form, so hinfo/BlueStore-style
per-block checksums of encoded chunks run straight off the encode
kernel's output with no relayout.

Role parity: batched data-path crc32c — src/common/crc32c* (the
reference's asm tier) and the per-4KiB-block checksumming of
BlueStore writes (Checksummer, BlueStore.cc:13642).
"""

from __future__ import annotations

import functools

import numpy as np
from ceph_tpu.common import flags

from ceph_tpu.ops import checksum as cks

try:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

# block-tile rows per grid step
_BT = 128

# VMEM budget for the (32, W, 128) int8 matrix stack (~4 MiB at
# W=1024, i.e. 4 KiB csum blocks); beyond this the XLA path is used
_MAX_W = 2048

# Test hook, mirroring gf_pallas.FORCE_INTERPRET
FORCE_INTERPRET = False


@functools.lru_cache(maxsize=8)
def _mk_stack(length: int) -> np.ndarray:
    """(32, W, 128) 0/1 stack of per-bit-position matrices.

    M (8L, 32) maps zero-seeded block bits to crc bits: bit (32w + k)
    of the block (bit k of word w) contributes column vector
    M[32w + k].  Built from the cell matrix and zero-advance matrices
    exactly as the XLA tree-fold would compose them.
    """
    assert length % cks._CELL == 0
    ncells = length // cks._CELL
    cell = cks._cell_matrix()                      # (32, 512)
    rows = []
    for j in range(ncells):
        adv = cks._zero_advance_matrix(cks._CELL * (ncells - 1 - j))
        mj = (adv.astype(np.uint32) @ cell.astype(np.uint32)) & 1
        rows.append(mj.T.astype(np.uint8))         # (512, 32)
    big = np.concatenate(rows, axis=0)             # (8L, 32)
    w = length // 4
    mk = np.zeros((32, w, 128), dtype=np.uint8)
    for k in range(32):
        mk[k, :, :32] = big[k::32]
    return mk


def supported(length: int, n_blocks: int,
              platform: str | None = None) -> bool:
    if not flags.enabled("CEPH_TPU_PALLAS"):
        return False  # same kill switch as gf_pallas
    if not HAVE_JAX:
        return False
    if length % cks._CELL or length // 4 > _MAX_W:
        return False
    if not FORCE_INTERPRET:
        try:
            plat = platform or jax.devices()[0].platform
        except Exception:
            return False
        if plat != "tpu":
            return False
    return n_blocks > 0


if HAVE_JAX:

    def _crc_kernel(w_ref, m_ref, o_ref):
        # int8 x int8 -> int32 MXU dots: exact (operands are 0/1, sums
        # bounded by 8L), and measured ~4x the bf16 rate on v5e
        acc = None
        w = w_ref[...]                             # (BT, W) int32
        for k in range(32):
            bits = ((jax.lax.shift_right_logical(w, jnp.int32(k))
                     & jnp.int32(1))).astype(jnp.int8)
            d = jax.lax.dot_general(
                bits, m_ref[k],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = d if acc is None else acc + d
        o_ref[...] = acc & 1

    from ceph_tpu.ops.gf_pallas import x32

    @functools.lru_cache(maxsize=8)
    def _crc_call(n_tiles: int, w: int, interpret: bool = False):
        return x32(pl.pallas_call(
            _crc_kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((_BT, w), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((32, w, 128), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((_BT, 128), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n_tiles * _BT, 128),
                                           jnp.int32),
            interpret=interpret,
            name="crc32c_words",
        ))

    def crc32c_blocks_words(words, length: int, init: int = 0xFFFFFFFF):
        """crc32c of every `length`-byte block, blocks given as int32
        words (n_blocks, length//4) in the device layout.  Returns an
        (n_blocks,) uint32 device array.

        The seed enters via linearity: crc(seed, B) =
        crc(0, B) ^ advance(seed, len) — the advance is one host
        constant XORed into every lane.
        """
        n_blocks, w = words.shape
        assert w == length // 4, (words.shape, length)
        mk = jnp.asarray(_mk_stack(length), dtype=jnp.int8)
        pad = -n_blocks % _BT
        if pad:
            words = jnp.pad(words, ((0, pad), (0, 0)))
        bits = _crc_call((n_blocks + pad) // _BT, w,
                         FORCE_INTERPRET)(words, mk)
        crcs = jnp.sum(
            bits[:n_blocks, :32].astype(jnp.uint32)
            << jnp.arange(32, dtype=jnp.uint32),
            axis=-1, dtype=jnp.uint32)
        seed_adv = cks.crc32c_zeros(init & 0xFFFFFFFF, length)
        return crcs ^ jnp.uint32(seed_adv)
