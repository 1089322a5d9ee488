"""Table CRC-32C (Castagnoli, reflected polynomial 0x82F63B78) in numpy.

Ceph's convention (ceph_crc32c): the register starts at the caller's
seed and is returned as is, with no inversion before or after.  An EC
shard's hinfo entry is crc32c(0xFFFFFFFF, shard).

Many streams of equal length are hashed at once: each stream is cut into
blocks, every block's crc from seed 0 is taken in one vectorized byte
loop, and the blocks are folded left to right with the linear map that
advances a register over one block of zero bytes:
crc(s, A || B) = zeros(crc(s, A), len(B)) ^ crc(0, B).
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x82F63B78


@functools.lru_cache(maxsize=None)
def table() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for b in range(256):
        c = b
        for _ in range(8):
            c = (c >> 1) ^ POLY if c & 1 else c >> 1
        t[b] = c
    return t


def _update(crc: np.ndarray, data: np.ndarray) -> np.ndarray:
    """crc (n,) uint32 through data (n, L) uint8, one byte per step."""
    t = table()
    crc = crc.astype(np.uint32).copy()
    cols = np.ascontiguousarray(data.T)
    for j in range(cols.shape[0]):
        crc = t[(crc ^ cols[j]) & 0xFF] ^ (crc >> 8)
    return crc


@functools.lru_cache(maxsize=None)
def _zeros_tables(nbytes: int) -> np.ndarray:
    """(4, 256) uint32: row r, entry b is the register (b << 8r) advanced
    over nbytes zero bytes, so an advance is four lookups."""
    regs = (np.arange(256, dtype=np.uint32)[None, :]
            << (8 * np.arange(4, dtype=np.uint32))[:, None]).reshape(-1)
    out = _update(regs, np.zeros((regs.size, nbytes), dtype=np.uint8))
    return out.reshape(4, 256)


def _advance(crc: np.ndarray, nbytes: int) -> np.ndarray:
    z = _zeros_tables(nbytes)
    return (z[0][crc & 0xFF] ^ z[1][(crc >> 8) & 0xFF]
            ^ z[2][(crc >> 16) & 0xFF] ^ z[3][crc >> 24])


def crc32c_streams(seed: int, streams: np.ndarray,
                   block: int = 4096) -> np.ndarray:
    """crc32c(seed, row) for every row of a (n, L) uint8 array."""
    n, length = streams.shape
    if length % block:
        head = length % block
        crc = _update(np.full(n, seed, dtype=np.uint32), streams[:, :head])
        streams = streams[:, head:]
        length -= head
    else:
        crc = np.full(n, seed, dtype=np.uint32)
    nblocks = length // block
    if nblocks == 0:
        return crc
    blocks = _update(np.zeros(n * nblocks, dtype=np.uint32),
                     streams.reshape(n * nblocks, block)
                     ).reshape(n, nblocks)
    for b in range(nblocks):
        crc = _advance(crc, block) ^ blocks[:, b]
    return crc


def crc32c(seed: int, data: bytes) -> int:
    arr = np.frombuffer(bytes(data), dtype=np.uint8)[None, :]
    return int(crc32c_streams(seed, arr)[0])
