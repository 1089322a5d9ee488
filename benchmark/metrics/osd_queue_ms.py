"""OSD op engine: time an op waited in the mClock queue (``queue.*``
stages), per OSD client op, over the window."""

from benchmark.metrics._stages import per_op_ms


def read(w):
    return per_op_ms(w, lambda s: s.startswith("queue."))
