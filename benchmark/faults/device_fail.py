"""Fault: every device dispatch fails, through the program's own
injection seam; the breaker falls back to the host, so the stored bytes
stay right and only the device path's checks can see it."""


def install():
    from ceph_tpu.common import flags

    prev = flags.peek("CEPH_TPU_INJECT_DEVICE_FAIL")
    flags.set_flag("CEPH_TPU_INJECT_DEVICE_FAIL", "p=1")

    def undo():
        if prev is None:
            flags.clear("CEPH_TPU_INJECT_DEVICE_FAIL")
        else:
            flags.set_flag("CEPH_TPU_INJECT_DEVICE_FAIL", prev)
    return undo
