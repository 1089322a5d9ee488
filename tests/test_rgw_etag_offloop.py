"""The MD5 ETag of a PUT body runs on the gateway's ETag pool, beside the
body's stripe writes, and gives the ETag the loop would have given.

Bodies from ETAG_OFFLOOP_MIN bytes up hash on the pool; smaller ones,
and the crc32c mode's manifest-stitched ETags, stay on the loop."""

import asyncio
import gc
import hashlib
import threading

import numpy as np
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.ops import checksum as cks
from ceph_tpu.rgw import RGWLite, gateway
from ceph_tpu.rgw.s3_frontend import S3Frontend

from cluster_helpers import Cluster

PROFILE = {"plugin": "ec_jax", "technique": "reed_sol_van",
           "k": "2", "m": "1", "crush-failure-domain": "osd",
           "tpu": "false"}
MIN = gateway.ETAG_OFFLOOP_MIN


def run(coro):
    asyncio.run(asyncio.wait_for(coro, 120))


async def _cluster_gateway(etag_hash: str = "md5"):
    cluster = Cluster(num_osds=4, osds_per_host=2)
    await cluster.start()
    await cluster.client.create_replicated_pool("meta", size=2, pg_num=4)
    await cluster.client.create_ec_pool("data", profile=PROFILE, pg_num=4)
    # 64 KiB stripes: a body just over MIN spans three stripe objects
    rgw = RGWLite(cluster.client, "data", "meta", stripe_size=64 << 10,
                  etag_hash=etag_hash)
    await rgw.create_bucket("b")
    return cluster, rgw


def _body(n: int, salt: int) -> bytes:
    return np.random.default_rng([n, salt]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _multipart_form(part_etags) -> str:
    digests = b"".join(bytes.fromhex(e) for e in part_etags)
    return f"{hashlib.md5(digests).hexdigest()}-{len(part_etags)}"


@pytest.mark.parametrize("size,etag_hash", [
    (MIN - 1, "md5"), (MIN + 1, "md5"), (0, "md5"), (MIN + 1, "crc32c")],
    ids=["under", "over", "empty", "crc32c"])
def test_etag_is_the_hash_of_the_body(size, etag_hash):
    """A single PUT's and each part's ETag is the hash of the bytes
    written, the Complete's is the S3 multipart form, and the counters
    say where each body was hashed."""
    def want(data: bytes) -> str:
        if etag_hash == "crc32c":
            return "%08x" % cks.crc32c(0xFFFFFFFF, data)
        return hashlib.md5(data).hexdigest()

    async def main():
        cluster, rgw = await _cluster_gateway(etag_hash)
        try:
            single = _body(size, 0)
            assert await rgw.put_object("b", "single", single) \
                == want(single)
            parts = [_body(size, 1), _body(size, 2)]
            upload = await rgw.init_multipart("b", "multi")
            etags = [await rgw.upload_part("b", "multi", upload, n + 1, p)
                     for n, p in enumerate(parts)]
            assert etags == [want(p) for p in parts]
            combined = await rgw.complete_multipart(
                "b", "multi", upload, list(zip((1, 2), etags)))
            assert combined == _multipart_form(etags)
            assert await rgw.get_object("b", "single") == single
            assert await rgw.get_object("b", "multi") == b"".join(parts)
            offloop = etag_hash == "md5" and size >= MIN
            # crc32c ETags are stitched from the stripes' digests:
            # nothing hashes the body, a zero-length one included
            inline = etag_hash == "md5" and size < MIN
            assert rgw.stats() == {
                "etag_offloop": 3 * offloop,
                "etag_offloop_bytes": 3 * size * offloop,
                "etag_inline": 3 * inline}
        finally:
            rgw.stop_etag_pool()
            await cluster.stop()

    run(main())


def test_large_part_hashes_on_the_etag_pool(monkeypatch):
    """The MD5 of a part at MIN bytes runs on a gateway pool thread, not
    the loop's; where it outlasts the stripe writes, the request's
    critical path names the wait `s3.etag`."""
    calls = []
    released = threading.Event()
    md5 = gateway._etag

    def recording_etag(data):
        calls.append((memoryview(data).nbytes, threading.get_ident(),
                      threading.current_thread().name))
        # held until the stripe writes are done, so the ETag is still
        # pending when the gateway reaches it
        assert released.wait(30)
        return md5(data)

    complete = gateway.PutObjProcessor.complete

    async def complete_then_release(self):
        manifest = await complete(self)
        released.set()
        return manifest

    monkeypatch.setattr(gateway, "_etag", recording_etag)
    monkeypatch.setattr(gateway.PutObjProcessor, "complete",
                        complete_then_release)

    async def main():
        cluster, rgw = await _cluster_gateway()
        try:
            loop_thread = threading.get_ident()
            data = _body(MIN, 3)
            upload = await rgw.init_multipart("b", "k")
            tracer = tracing.Tracer("rgw")
            async with tracer.span("s3.PUT /b/k") as root:
                etag = await rgw.upload_part("b", "k", upload, 1, data)
            assert etag == hashlib.md5(data).hexdigest()
            assert [c[0] for c in calls] == [MIN]
            _n, ident, name = calls[0]
            assert ident != loop_thread
            assert name.startswith("rgw-etag")
            assert rgw.stats()["etag_offloop"] == 1
            assert "s3.etag" in tracing.critical_path_spans(root)["stages"]
        finally:
            rgw.stop_etag_pool()
            await cluster.stop()

    run(main())


@pytest.mark.parametrize("hash_outcome", ["raised", "running"])
def test_failed_stripe_write_settles_the_hash(monkeypatch, hash_outcome):
    """A part whose stripe write fails raises the write's error, leaves
    its hash future done, and no "exception was never retrieved" is
    reported: whether the hash had already failed or was still
    running."""
    release = threading.Event()
    futures = []

    def etag(data):
        if hash_outcome == "raised":
            raise MemoryError("hash failed")
        assert release.wait(30)
        return hashlib.md5(data).hexdigest()

    monkeypatch.setattr(gateway, "_etag", etag)

    async def main():
        cluster, rgw = await _cluster_gateway()
        loop = asyncio.get_running_loop()
        reported = []
        loop.set_exception_handler(lambda _loop, ctx: reported.append(ctx))
        start = rgw._md5_start

        def recording_start(data):
            fut = start(data)
            futures.append(fut)
            return fut

        async def failing_write(oid, data):
            if hash_outcome == "raised":
                await asyncio.wait({futures[0]})
            raise ConnectionError("osd gone")

        monkeypatch.setattr(rgw, "_md5_start", recording_start)
        monkeypatch.setattr(rgw.data, "write_full", failing_write)
        try:
            upload = await rgw.init_multipart("b", "k")
            with pytest.raises(ConnectionError, match="osd gone"):
                await rgw.upload_part("b", "k", upload, 1, _body(MIN, 4))
            assert len(futures) == 1 and futures[0].done()
            release.set()
            futures.clear()
            gc.collect()
            await asyncio.sleep(0.05)
            assert reported == []
            doc = await rgw._upload("b", "k", upload)
            assert doc["parts"] == {}
        finally:
            release.set()
            rgw.stop_etag_pool()
            await cluster.stop()

    run(main())


def test_etag_pool_stops_with_the_frontend():
    """S3Frontend.stop shuts the gateway's ETag pool down and ends its
    threads; a PUT after that starts a new pool."""
    def pool_threads():
        return [t for t in threading.enumerate()
                if t.name.startswith("rgw-etag")]

    async def main():
        cluster, rgw = await _cluster_gateway()
        fe = S3Frontend(rgw, {"ak": "sk"})
        try:
            await fe.start(gc_interval=0)
            data = _body(MIN, 5)
            await rgw.put_object("b", "k", data)
            assert pool_threads()
            await fe.stop()
            assert pool_threads() == []
            assert await rgw.put_object("b", "k2", data) \
                == hashlib.md5(data).hexdigest()
            assert rgw.stats()["etag_offloop"] == 2
        finally:
            await fe.stop()
            await cluster.stop()
        assert pool_threads() == []

    run(main())
