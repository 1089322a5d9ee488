"""Fault: a write to an EC pool that returns success and leaves every
shard's store as it was (the step that returns its state unchanged).
Writes to replicated pools, such as the gateway's metadata, still land,
so the cell runs to its check."""


def _ec_shard(cid: str) -> bool:
    # shard_collection(): "<pool>.<ps hex>s<shard>_head" for an EC
    # shard, "<pool>.<ps hex>_head" for a replicated PG
    return "s" in cid.split("_", 1)[0]


def install():
    from ceph_tpu.osd.daemon import OSDDaemon

    orig = OSDDaemon._apply_shard_ops

    def apply(self, t, cid, oid, ops, save_rollback=False):
        if _ec_shard(cid):
            return orig(self, t, cid, oid, [], save_rollback=False)
        return orig(self, t, cid, oid, ops, save_rollback=save_rollback)

    OSDDaemon._apply_shard_ops = apply

    def undo():
        OSDDaemon._apply_shard_ops = orig
    return undo
