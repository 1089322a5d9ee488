"""Codec and dispatch: host work on the worker thread, per batched
dispatch -- packing the stripes up to the device call
(``dispatch_pack``) and folding the results into shards, hinfo and
the data crc32c after it (``dispatch_fold``)."""

from benchmark.metrics._dispatch import per_batch_ms


def read(w):
    return per_batch_ms(w, "dispatch_pack", "dispatch_fold")
