"""Codec and dispatch: from the plan call's return until its results
are host arrays (``dispatch_fetch``), per batched dispatch."""

from benchmark.metrics._dispatch import per_batch_ms


def read(w):
    return per_batch_ms(w, "dispatch_fetch")
