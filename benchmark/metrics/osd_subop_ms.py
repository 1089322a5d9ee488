"""OSD op engine: time on the critical path spent in sub-writes and
sub-reads to the other shards' OSDs, per OSD client op."""

from benchmark.metrics._stages import per_op_ms


def read(w):
    return per_op_ms(w, lambda s: s in ("subwrite", "subread"))
