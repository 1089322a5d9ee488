"""Fault: one bit of the first parity shard altered where the encode
produces it."""

from benchmark.faults import _encode


def install():
    def alter(buf, j):
        if j == 0 and buf:
            buf[len(buf) // 2] ^= 0x01
    return _encode.patch(alter)
