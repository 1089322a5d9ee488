"""Kernels: the fused encode+CRC plan's GF and crc kernels against the
chip's HBM bandwidth.  The bytes are the benchmark's own count for the
objects the encode service took in the traced span: per stripe, read
k*S, write m*S parity and (k+m) 4-byte crcs.  The time is the kernels'
device time in the trace.  The Pallas calls carry no name= of their own
yet, so they are matched by their custom-call target: on the chip
(PR 22's trace) the fused plan's GF words kernel shows as
``_lambda_.2 custom-call tpu_custom_call`` (s32[B,m,8,128] out) and its
crc kernel as ``_lambda_.3 custom-call tpu_custom_call``
(s32[B*(k+m),128] out); no other Pallas kernel runs in these cells'
windows.  A configuration with no EC pool has nothing to read."""

import json
import os

KERNELS = ("tpu_custom_call",)


def peaks(kind):
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}")
    return table[kind]


def read(w):
    t, c, g = w["trace"], w["traced"], w["geometry"]
    if not t or not c or not g:
        return None
    secs = sum(s for name, s in t["ops"].items()
               if name.endswith(KERNELS))
    reqs = c["encode"]["requests"]
    if secs <= 0 or reqs <= 0:
        return None
    stripes = reqs * g["object_stripes"]
    nbytes = stripes * ((g["k"] + g["m"]) * g["chunk"]
                        + (g["k"] + g["m"]) * 4)
    bound_s = nbytes / peaks(w["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * bound_s / secs
