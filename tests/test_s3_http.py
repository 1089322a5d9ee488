"""S3 HTTP frontend tier: a spec-level sigv4 client (raw HTTP over a
socket, signature math from the AWS SigV4 spec) drives the gateway the
way a stock S3 client would — bucket CRUD, object round-trips with MD5
ETag verification, multipart, auth rejection.

Reference parity: the rgw_asio_frontend + rgw_auth_s3 + rgw_rest_s3
surface (/root/reference/src/rgw/)."""

import asyncio
import hashlib
import urllib.parse
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from cluster_helpers import Cluster

from ceph_tpu.rgw import RGWLite
from ceph_tpu.rgw.s3_frontend import S3Frontend, sign_request

ACCESS, SECRET = "AKIDEXAMPLE", "s3cr3t-key-for-tests"


class MiniS3:
    """Raw-socket S3 client: HTTP/1.1 + sigv4 from the spec."""

    def __init__(self, addr: str, access: str = ACCESS,
                 secret: str = SECRET):
        self.host, port = addr.rsplit(":", 1)
        self.port = int(port)
        self.access, self.secret = access, secret
        self._r = self._w = None

    async def _connect(self):
        if self._w is None or self._w.is_closing():
            self._r, self._w = await asyncio.open_connection(
                self.host, self.port, limit=8 << 20)

    async def request(self, method, path, query=None, body=b"",
                      sign=True):
        await self._connect()
        query = query or {}
        headers = {"Host": f"{self.host}:{self.port}"}
        if sign:
            headers = sign_request(method, path, query, headers, body,
                                   self.access, self.secret)
        qs = urllib.parse.urlencode(query)
        target = path + ("?" + qs if qs else "")
        req = [f"{method} {target} HTTP/1.1\r\n"]
        headers["Content-Length"] = str(len(body))
        for k, v in headers.items():
            req.append(f"{k}: {v}\r\n")
        req.append("\r\n")
        self._w.write("".join(req).encode() + body)
        await self._w.drain()
        status_line = await self._r.readline()
        status = int(status_line.split()[1])
        rhdrs = {}
        while True:
            line = await self._r.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode().partition(":")
            rhdrs[k.strip().lower()] = v.strip()
        length = int(rhdrs.get("content-length", "0"))
        rbody = await self._r.readexactly(length) if length and \
            method != "HEAD" else b""
        return status, rhdrs, rbody

    async def close(self):
        if self._w is not None:
            self._w.close()
            self._w = None


async def _stack(cluster):
    await cluster.client.create_replicated_pool(
        "rgw.meta", size=2, pg_num=4)
    await cluster.client.create_ec_pool(
        "rgw.data", {"plugin": "ec_jax", "technique": "reed_sol_van",
                     "k": "2", "m": "1", "crush-failure-domain": "osd",
                     "tpu": "false"}, pg_num=4)
    rgw = RGWLite(cluster.client, "rgw.data", "rgw.meta")
    fe = S3Frontend(rgw, {ACCESS: SECRET})
    addr = await fe.start()
    return fe, addr


def test_s3_http_object_lifecycle():
    async def run():
        cluster = Cluster(num_osds=3, osds_per_host=1)
        await cluster.start()
        fe = None
        try:
            fe, addr = await _stack(cluster)
            s3 = MiniS3(addr)
            # bucket create + list buckets
            st, _, _ = await s3.request("PUT", "/photos")
            assert st == 200
            st, _, xml_body = await s3.request("GET", "/")
            assert st == 200 and b"photos" in xml_body
            # PUT: ETag is the true MD5
            data = np.random.default_rng(3).integers(
                0, 256, 300_000, dtype=np.uint8).tobytes()
            st, h, _ = await s3.request("PUT", "/photos/cat.jpg",
                                        body=data)
            assert st == 200
            assert h["etag"].strip('"') == \
                hashlib.md5(data).hexdigest()
            # GET round-trips the bytes + ETag
            st, h, got = await s3.request("GET", "/photos/cat.jpg")
            assert st == 200 and got == data
            assert h["etag"].strip('"') == \
                hashlib.md5(data).hexdigest()
            # HEAD
            st, h, empty = await s3.request("HEAD", "/photos/cat.jpg")
            assert st == 200 and empty == b""
            # list with prefix
            st, _, xml_body = await s3.request(
                "GET", "/photos", query={"prefix": "cat"})
            assert b"cat.jpg" in xml_body
            # DELETE + 404 after
            st, _, _ = await s3.request("DELETE", "/photos/cat.jpg")
            assert st == 204
            st, _, _ = await s3.request("GET", "/photos/cat.jpg")
            assert st == 404
            # empty-bucket delete
            st, _, _ = await s3.request("DELETE", "/photos")
            assert st == 204
            await s3.close()
        finally:
            if fe is not None:
                await fe.stop()
            await cluster.stop()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_s3_put_feeds_the_gateway_stage_sums():
    """One PUT's critical path reaches the gateway's stage histograms:
    its own self-time as ``s3.PUT`` and its waits on RADOS as
    ``rados``, which together span the request."""
    async def run():
        cluster = Cluster(num_osds=3, osds_per_host=1)
        await cluster.start()
        fe = None
        try:
            fe, addr = await _stack(cluster)
            s3 = MiniS3(addr)
            st, _, _ = await s3.request("PUT", "/logs")
            assert st == 200
            before = {s: h.total for s, h in fe.tracer.stage_hist.items()}
            st, _, _ = await s3.request("PUT", "/logs/day.txt",
                                        body=b"z" * 100_000)
            assert st == 200
            hist = fe.tracer.stage_hist
            put = hist["s3.PUT"].total - before["s3.PUT"]
            rados = hist["rados"].total - before.get("rados", 0.0)
            assert put > 0 and rados > 0
            ingress = [s for s in fe.tracer.dump()
                       if s["name"] == "s3.PUT /logs/day.txt"]
            assert ingress
            assert put + rados == pytest.approx(
                ingress[-1]["duration_us"] / 1e6, rel=0.05, abs=1e-4)
            await s3.close()
        finally:
            if fe is not None:
                await fe.stop()
            await cluster.stop()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_s3_http_multipart_round_trip():
    async def run():
        cluster = Cluster(num_osds=3, osds_per_host=1)
        await cluster.start()
        fe = None
        try:
            fe, addr = await _stack(cluster)
            s3 = MiniS3(addr)
            await s3.request("PUT", "/vids")
            payload = np.random.default_rng(9).integers(
                0, 256, 12 << 20, dtype=np.uint8).tobytes()
            psize = 4 << 20
            st, _, body = await s3.request(
                "POST", "/vids/movie.bin", query={"uploads": ""})
            assert st == 200
            upload_id = ET.fromstring(body).findtext("UploadId")
            etags = []
            for num in range(1, 4):
                chunk = payload[(num - 1) * psize:num * psize]
                st, h, _ = await s3.request(
                    "PUT", "/vids/movie.bin",
                    query={"partNumber": str(num),
                           "uploadId": upload_id},
                    body=chunk)
                assert st == 200
                assert h["etag"].strip('"') == \
                    hashlib.md5(chunk).hexdigest()
                etags.append(h["etag"].strip('"'))
            comp = ET.Element("CompleteMultipartUpload")
            for num, etag in enumerate(etags, 1):
                p = ET.SubElement(comp, "Part")
                ET.SubElement(p, "PartNumber").text = str(num)
                ET.SubElement(p, "ETag").text = etag
            st, _, body = await s3.request(
                "POST", "/vids/movie.bin",
                query={"uploadId": upload_id},
                body=ET.tostring(comp))
            assert st == 200
            final_etag = ET.fromstring(body).findtext(
                "ETag").strip('"')
            want = hashlib.md5(b"".join(
                bytes.fromhex(e) for e in etags)).hexdigest() + "-3"
            assert final_etag == want
            st, h, got = await s3.request("GET", "/vids/movie.bin")
            assert st == 200 and got == payload
            assert h["etag"].strip('"') == want
            await s3.close()
        finally:
            if fe is not None:
                await fe.stop()
            await cluster.stop()

    asyncio.run(asyncio.wait_for(run(), 180))


def test_s3_http_auth_rejection():
    async def run():
        cluster = Cluster(num_osds=3, osds_per_host=1)
        await cluster.start()
        fe = None
        try:
            fe, addr = await _stack(cluster)
            # no auth header at all
            anon = MiniS3(addr)
            st, _, body = await anon.request("GET", "/", sign=False)
            assert st == 403 and b"AccessDenied" in body
            await anon.close()
            # wrong secret: SignatureDoesNotMatch
            bad = MiniS3(addr, secret="wrong-secret")
            st, _, body = await bad.request("GET", "/")
            assert st == 403 and b"SignatureDoesNotMatch" in body
            await bad.close()
            # unknown access key
            ghost = MiniS3(addr, access="AKIDGHOST")
            st, _, body = await ghost.request("GET", "/")
            assert st == 403
            await ghost.close()
            # tampered body under a signed payload hash
            s3 = MiniS3(addr)
            await s3.request("PUT", "/b1")
            headers = sign_request(
                "PUT", "/b1/obj", {}, {"Host": addr}, b"real body",
                ACCESS, SECRET)
            req = ["PUT /b1/obj HTTP/1.1\r\n"]
            headers["Content-Length"] = str(len(b"fake body"))
            for k, v in headers.items():
                req.append(f"{k}: {v}\r\n")
            req.append("\r\n")
            await s3._connect()
            s3._w.write("".join(req).encode() + b"fake body")
            await s3._w.drain()
            status = int((await s3._r.readline()).split()[1])
            assert status == 403
            await s3.close()
        finally:
            if fe is not None:
                await fe.stop()
            await cluster.stop()

    asyncio.run(asyncio.wait_for(run(), 120))
