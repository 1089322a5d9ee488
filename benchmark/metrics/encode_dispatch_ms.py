"""Codec and dispatch: host seconds per batched encode+CRC dispatch, from
hand-off to the results back on the host (EncodeService
``dispatch_seconds`` over ``batches``)."""


def read(w):
    enc = w["window"]["encode"]
    if enc["batches"] <= 0:
        return None
    return enc["dispatch_s"] / enc["batches"] * 1e3
