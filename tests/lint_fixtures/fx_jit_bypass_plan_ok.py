"""Clean twin: EC entry points compile through the ExecPlan cache
(ceph_tpu.ec.plan) — bucketed and counted."""

from ceph_tpu.ec import plan


def encode_stripes(mbits, data):
    return mbits @ data


encode_fn = plan.tracked_jit("fx.encode", encode_stripes)


def batched_parity(matrix, stripes):
    return plan.matmul(matrix, stripes)
