"""Encode service: each request's wait from its enqueue to the start
of its batch's dispatch, per encode request (the ``encode_queue``
stage: one sample a request, the same waits the service sums as
``queue_seconds``)."""


def read(w):
    win = w["window"]
    waits = win["osd"]["stage_s"].get("encode_queue")
    reqs = win["encode"]["requests"]
    if waits is None or reqs <= 0:
        return None
    return waits / reqs * 1e3
