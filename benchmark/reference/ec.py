"""What an EC pool must store for one object: the plain reference.

An object is zero-padded to whole stripes of k chunks; shard j (j < k)
is chunk j of every stripe, end to end; the m parity shards are the
technique's coding matrix applied to the data shards byte by byte; each shard's
hinfo entry is crc32c(0xFFFFFFFF, shard).  Imports nothing of ceph_tpu.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from benchmark.reference import crc32c, gf256


def encode_objects(payloads: List[bytes], k: int, m: int, chunk: int,
                   technique: str = "reed_sol_van"
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Equal-sized objects -> (shards (n, k+m, L) uint8, hinfo crcs
    (n, k+m) uint32)."""
    width = k * chunk
    size = len(payloads[0])
    if any(len(p) != size for p in payloads):
        raise ValueError("encode_objects takes objects of one size")
    padded = -(-size // width) * width
    stripes = padded // width
    arr = np.zeros((len(payloads), padded), dtype=np.uint8)
    for i, p in enumerate(payloads):
        arr[i, :size] = np.frombuffer(bytes(p), dtype=np.uint8)
    data = arr.reshape(len(payloads), stripes, k, chunk).transpose(
        0, 2, 1, 3).reshape(len(payloads), k, stripes * chunk)
    matrix = gf256.coding_matrix(technique, k, m)
    shards = np.empty((len(payloads), k + m, stripes * chunk),
                      dtype=np.uint8)
    shards[:, :k] = data
    for i in range(len(payloads)):
        shards[i, k:] = gf256.matmul(matrix, data[i])
    crcs = crc32c.crc32c_streams(
        0xFFFFFFFF, shards.reshape(-1, stripes * chunk), block=chunk)
    return shards, crcs.reshape(len(payloads), k + m)
