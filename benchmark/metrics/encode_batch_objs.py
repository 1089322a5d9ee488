"""Encode service: objects per batched encode+CRC dispatch over the
window, summed over the OSDs (EncodeService per-profile counters)."""


def read(w):
    enc = w["window"]["encode"]
    if enc["batches"] <= 0:
        return None
    return enc["requests"] / enc["batches"]
