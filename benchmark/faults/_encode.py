"""Shared by the encode faults: rewrite the parity of every encode the
OSDs' encode service returns, where it is produced."""


def patch(alter):
    from ceph_tpu.osd.encode_service import EncodeService

    orig = EncodeService.encode_with_hinfo

    async def encode(self, sinfo, codec, data, want, logical_len=None):
        shards, hinfo, crc = await orig(self, sinfo, codec, data, want,
                                        logical_len=logical_len)
        k = codec.get_data_chunk_count()
        shards = dict(shards)
        for i in range(k, codec.get_chunk_count()):
            if i in shards:
                buf = bytearray(bytes(shards[i]))
                alter(buf, i - k)
                shards[i] = bytes(buf)
        return shards, hinfo, crc

    EncodeService.encode_with_hinfo = encode

    def undo():
        EncodeService.encode_with_hinfo = orig
    return undo
