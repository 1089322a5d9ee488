"""Encode service: time an op waited for its encode (batching wait and
shared dispatch, or the inline host encode), per OSD client op."""

from benchmark.metrics._stages import per_op_ms


def read(w):
    return per_op_ms(w, lambda s: s in ("encode_wait", "encode_flush",
                                        "encode_inline"))
