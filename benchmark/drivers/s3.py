"""S3 client kind: multipart PUTs through the gateway's HTTP frontend.

As ``aws s3 cp`` sends them: every object is one CreateMultipartUpload,
``object_bytes / part_bytes`` UploadParts and one
CompleteMultipartUpload, and ``concurrency`` requests are in flight over
as many keep-alive connections (the CLI's max_concurrent_requests).
As the CLI's transfer queue does, the client works on enough objects at
once to keep every connection busy: ceil(concurrency / parts) + 1, so
that parts of the next object fill the slots an object's last parts
leave.  An op is one HTTP request; an object's bytes count when its
Complete returns.

Requests are signed with sigv4 and ``UNSIGNED-PAYLOAD`` (the AWS spec,
written here apart from the program), so the client hashes no body.
"""

from __future__ import annotations

import asyncio
import datetime
import hashlib
import hmac
import re
import time
import urllib.parse
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark import check
from benchmark.harness import Ctx, Names

ACCESS, SECRET = "benchaccess", "benchsecret"
BUCKET = "bench"
REGION = "us-east-1"


def _sig_key(date: str) -> bytes:
    k = hmac.new(("AWS4" + SECRET).encode(), date.encode(),
                 hashlib.sha256).digest()
    for part in (REGION, "s3", "aws4_request"):
        k = hmac.new(k, part.encode(), hashlib.sha256).digest()
    return k


def sign(method: str, path: str, query: Dict[str, str], host: str
         ) -> Dict[str, str]:
    now = datetime.datetime.now(datetime.timezone.utc)
    amz_date = now.strftime("%Y%m%dT%H%M%SZ")
    date = amz_date[:8]
    hdrs = {"host": host, "x-amz-date": amz_date,
            "x-amz-content-sha256": "UNSIGNED-PAYLOAD"}
    signed = sorted(hdrs)
    cq = "&".join(f"{k}={v}" for k, v in sorted(
        (urllib.parse.quote(k, safe="-_.~"),
         urllib.parse.quote(v, safe="-_.~")) for k, v in query.items()))
    creq = "\n".join([method, path, cq,
                      "".join(f"{h}:{hdrs[h]}\n" for h in signed),
                      ";".join(signed), "UNSIGNED-PAYLOAD"])
    scope = f"{date}/{REGION}/s3/aws4_request"
    to_sign = "\n".join(["AWS4-HMAC-SHA256", amz_date, scope,
                         hashlib.sha256(creq.encode()).hexdigest()])
    sig = hmac.new(_sig_key(date), to_sign.encode(),
                   hashlib.sha256).hexdigest()
    hdrs["authorization"] = (
        f"AWS4-HMAC-SHA256 Credential={ACCESS}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}")
    return hdrs


class Conn:
    """One keep-alive HTTP/1.1 connection."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.reader: Optional[asyncio.StreamReader] = None
        self.writer: Optional[asyncio.StreamWriter] = None

    async def request(self, method: str, path: str, query: Dict[str, str],
                      body: bytes = b"") -> Tuple[int, Dict[str, str],
                                                  bytes]:
        if self.writer is None:
            self.reader, self.writer = await asyncio.open_connection(
                self.host, self.port, limit=1 << 20)
        hdrs = sign(method, path, query, f"{self.host}:{self.port}")
        hdrs["content-length"] = str(len(body))
        target = path + ("?" + urllib.parse.urlencode(query)
                         if query else "")
        head = f"{method} {target} HTTP/1.1\r\n" + "".join(
            f"{k}: {v}\r\n" for k, v in hdrs.items()) + "\r\n"
        self.writer.write(head.encode())
        if body:
            self.writer.write(body)
        await self.writer.drain()
        status = int((await self.reader.readline()).split()[1])
        rh: Dict[str, str] = {}
        while True:
            line = await self.reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode("latin-1").partition(":")
            rh[k.strip().lower()] = v.strip()
        rbody = await self.reader.readexactly(
            int(rh.get("content-length", "0")))
        return status, rh, rbody

    async def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
            try:
                await self.writer.wait_closed()
            except (ConnectionError, OSError):
                pass


class S3Error(Exception):
    pass


def multipart_etag(parts: List[bytes]) -> str:
    """The S3 ETag of a multipart object: MD5 of the parts' MD5s."""
    digests = b"".join(hashlib.md5(p).digest() for p in parts)
    return f"{hashlib.md5(digests).hexdigest()}-{len(parts)}"


class Driver:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        t = ctx.spec.traffic
        if t["op"] != "multipart_put":
            raise ValueError(f"s3 driver: no op {t['op']!r}")
        self.part = int(t["part_bytes"])
        self.nparts = int(t["object_bytes"]) // self.part
        self.pool = ctx.spec.config["data_pool"]
        self.conns: asyncio.Queue = asyncio.Queue()
        self.frontend = None
        self.next = 0
        self.etags: Dict[int, str] = {}
        self.key = Names(ctx.seed, "obj-{:06d}", block=16)

    def payload(self, j: int, p: int) -> bytes:
        return self.ctx.payloads.get(j * self.nparts + p)

    async def start(self) -> None:
        from ceph_tpu.rgw.gateway import RGWLite
        from ceph_tpu.rgw.s3_frontend import S3Frontend

        gw = self.ctx.spec.config["gateway"]
        rgw = RGWLite(self.ctx.cluster.client, gw["data_pool"],
                      gw["meta_pool"], stripe_size=gw["rgw_obj_stripe_size"],
                      etag_hash=gw["etag_hash"])
        self.rgw = rgw
        self.frontend = S3Frontend(rgw, {ACCESS: SECRET},
                                   anonymous_ok=False)
        host, port = (await self.frontend.start()).rsplit(":", 1)
        self.ctx.cluster.services.append(self.frontend)
        for _ in range(int(self.ctx.spec.traffic["concurrency"])):
            self.conns.put_nowait(Conn(host, int(port)))
        status, _h, body = await self._call("PUT", f"/{BUCKET}", {})
        if status != 200:
            raise S3Error(f"create bucket: {status} {body[:200]!r}")

    async def _call(self, method, path, query, body=b"", timed=False):
        """One request on a free connection.  A timed one is an op of
        the percentile: its clock starts once a connection is free, as
        the request is sent.  A broken connection reads as status 0."""
        conn = await self.conns.get()
        t0 = time.monotonic()
        try:
            status, hdrs, rbody = await conn.request(method, path, query,
                                                     body)
        except (ConnectionError, OSError, asyncio.IncompleteReadError):
            await conn.close()
            conn = Conn(conn.host, conn.port)
            status, hdrs, rbody = 0, {}, b""
        finally:
            self.conns.put_nowait(conn)
        if timed:
            self.ctx.rec.op(t0, time.monotonic(), status == 200)
        return status, hdrs, rbody

    async def _upload(self, j: int) -> bool:
        path = f"/{BUCKET}/{self.key(j)}"
        status, _h, body = await self._call("POST", path, {"uploads": ""},
                                            timed=True)
        m = re.search(rb"<UploadId>([^<]+)</UploadId>", body)
        if status != 200 or m is None:
            return False
        uid = m.group(1).decode()

        async def part(p: int) -> Optional[str]:
            status, hdrs, _b = await self._call(
                "PUT", path, {"partNumber": str(p + 1), "uploadId": uid},
                self.payload(j, p), timed=True)
            return hdrs.get("etag", "").strip('"') if status == 200 \
                else None

        etags = await asyncio.gather(*(part(p) for p in
                                       range(self.nparts)))
        if any(e is None for e in etags):
            return False
        xml = "<CompleteMultipartUpload>" + "".join(
            f"<Part><PartNumber>{p + 1}</PartNumber><ETag>\"{e}\"</ETag>"
            "</Part>" for p, e in enumerate(etags)) + \
            "</CompleteMultipartUpload>"
        status, _h, body = await self._call("POST", path, {"uploadId": uid},
                                            xml.encode(), timed=True)
        if status != 200:
            return False
        m = re.search(rb"<ETag>\"?([^<\"]+)\"?</ETag>", body)
        self.etags[j] = m.group(1).decode() if m else ""
        self.ctx.rec.credit(time.monotonic(), self.nparts * self.part, j)
        return True

    async def _uploader(self) -> None:
        while not self.ctx.rec.stopping:
            j = self.next
            self.next += 1
            await self._upload(j)

    async def run(self) -> None:
        conc = int(self.ctx.spec.traffic["concurrency"])
        await asyncio.gather(*(self._uploader() for _ in range(
            -(-conc // self.nparts) + 1)))

    async def check(self):
        """A sample of the window's completed objects, drawn from the
        seed: the ETag the Complete returned and the GET's ETag against
        the reference MD5s, the GET's bytes, and every 4 MiB stripe
        object's shards on the stores."""
        acked = self.ctx.rec.acked
        rng = np.random.default_rng([self.ctx.seed, 1])
        n = min(int(self.ctx.spec.traffic["check_sample"]), len(acked))
        picks = sorted(int(x) for x in rng.choice(acked, n, replace=False))
        counts = {"etags_wrong": 0, "gets_wrong": 0}
        items: List[Tuple[str, bytes]] = []
        for j in picks:
            parts = [self.payload(j, p) for p in range(self.nparts)]
            want = multipart_etag(parts)
            whole = b"".join(parts)
            status, hdrs, body = await self._call(
                "GET", f"/{BUCKET}/{self.key(j)}", {})
            counts["etags_wrong"] += (self.etags.get(j) != want) + (
                hdrs.get("etag", "").strip('"') != want)
            counts["gets_wrong"] += status != 200 or body != whole
            items += await self._stripes(j, whole)
        counts.update(check.stored(self.ctx.cluster, self.pool,
                                          items))
        counts["window_empty"] = int(not acked)
        return counts

    async def _stripes(self, j: int, whole: bytes
                       ) -> List[Tuple[str, bytes]]:
        """(stripe object, its bytes) of one object, from its manifest."""
        doc = await self.rgw._load(self.rgw._meta_oid("head", BUCKET,
                                                   self.key(j)))
        out, off = [], 0
        for st in (doc or {}).get("manifest", {}).get("stripes", []):
            out.append((st["oid"], whole[off:off + st["size"]]))
            off += st["size"]
        if off != len(whole):
            out.append(("<manifest short>", whole[off:]))
        return out

    async def stop(self) -> None:
        while not self.conns.empty():
            await self.conns.get_nowait().close()
