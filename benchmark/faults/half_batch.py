"""Fault: half of each encode left out -- the parity of the second half
of an object's stripes is never computed (left zero)."""

from benchmark.faults import _encode


def install():
    def alter(buf, _j):
        half = len(buf) // 2
        buf[half:] = bytes(len(buf) - half)
    return _encode.patch(alter)
