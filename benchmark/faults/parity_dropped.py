"""The control: the primary acknowledges a write whose parity shards it
never stored -- their sub-writes carry the object's metadata and no
bytes -- which breaks the stated guarantee that an acknowledged write is
on all k+m shards."""


def install():
    from ceph_tpu.osd.daemon import OSDDaemon

    orig = OSDDaemon._submit_shard_writes

    async def submit(self, state, pool, oid, shard_ops, entry,
                     admit_epoch=None):
        if pool.is_erasure():
            k = self._codec(pool.id).get_data_chunk_count()
            shard_ops = {s: ops if s < k else
                         [op for op in ops if op.op != "write"]
                         for s, ops in shard_ops.items()}
        return await orig(self, state, pool, oid, shard_ops, entry,
                          admit_epoch)

    OSDDaemon._submit_shard_writes = submit

    def undo():
        OSDDaemon._submit_shard_writes = orig
    return undo
