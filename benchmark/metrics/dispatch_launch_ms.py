"""Codec and dispatch: the jitted plan call on the watchdog thread,
host-to-device copy included (``dispatch_launch``), per batched
dispatch."""

from benchmark.metrics._dispatch import per_batch_ms


def read(w):
    return per_batch_ms(w, "dispatch_launch")
