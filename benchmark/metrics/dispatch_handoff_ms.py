"""Codec and dispatch: the batch's hops between threads, per batched
dispatch -- loop to worker (``dispatch_handoff``), worker to the
watchdog thread and back (``dispatch_guard``), worker to loop
(``dispatch_resume``)."""

from benchmark.metrics._dispatch import per_batch_ms


def read(w):
    return per_batch_ms(w, "dispatch_handoff", "dispatch_guard",
                        "dispatch_resume")
