"""Kernels: the fused plan's GF kernel alone against the chip's HBM
bandwidth, apart from the crc kernel that ``ec_encode_roofline`` adds
in.  The bytes are the benchmark's own count for the objects the encode
service took in the traced span: per stripe, k*S data read and m*S
parity written, k and m from the configuration's profile.  The time is
the device time of the specialised kernel, the ops named ``gf_words.*``
(``gf_words_smem`` is the generic one).  A trace without that kernel
has nothing to read."""

from benchmark.metrics.ec_encode_roofline import peaks

PREFIX = "gf_words."


def kernel_seconds(ops):
    return sum(s for name, s in ops.items() if name.startswith(PREFIX))


def stripe_bytes(k, parity_rows, chunk):
    """HBM bytes of one stripe through the GF kernel: k chunks in,
    parity_rows chunks out."""
    return (k + parity_rows) * chunk


def share(w, parity_rows):
    """The share in percent, given the matrix's parity rows."""
    t, c, g = w["trace"], w["traced"], w["geometry"]
    if not t or not c or not g or not parity_rows:
        return None
    secs = kernel_seconds(t["ops"])
    reqs = c["encode"]["requests"]
    if secs <= 0 or reqs <= 0:
        return None
    nbytes = reqs * g["object_stripes"] * stripe_bytes(
        g["k"], parity_rows, g["chunk"])
    bound_s = nbytes / peaks(w["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * bound_s / secs


def read(w):
    g = w["geometry"]
    if not g:
        return None
    return share(w, g["m"])
