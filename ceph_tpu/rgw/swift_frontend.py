"""Swift API frontend: the gateway's second dialect.

Reference parity: /root/reference/src/rgw/rgw_rest_swift.cc +
rgw_swift_auth.cc — the same RGW op layer served over the OpenStack
Swift REST shape: TempAuth-style token handshake (`GET /auth/v1.0`
with X-Auth-User/X-Auth-Key -> X-Auth-Token + X-Storage-Url), then
account/container/object verbs under /v1/AUTH_<account>/.

Re-design notes: the reference multiplexes S3 and Swift through one
frontend with per-API handler tables; here each dialect is its own
small asyncio server over the SAME RGWLite gateway — buckets ARE
containers (shared namespace, matching radosgw's default single-zone
behavior), so an object PUT via Swift is readable via S3 and vice
versa.  Tokens are in-memory with TTL (TempAuth keeps no durable
state either).
"""

from __future__ import annotations

import asyncio
import json
import logging
import secrets
import time
from typing import Dict, Optional, Tuple

from ceph_tpu.rgw.gateway import RGWError, RGWLite

log = logging.getLogger("rgw.swift")

TOKEN_TTL = 3600.0
MAX_BODY = 5 << 30

_ERR_STATUS = {
    "NoSuchBucket": 404, "NoSuchKey": 404,
    "BucketAlreadyExists": 202,  # Swift: container PUT is idempotent
    "BucketNotEmpty": 409, "AccessDenied": 401,
}


class SwiftFrontend:
    """TempAuth + account/container/object REST over RGWLite."""

    def __init__(self, rgw: RGWLite, users: Dict[str, str]):
        """users: account -> key (the X-Auth-User/X-Auth-Key pairs;
        `account:user` forms are accepted and collapse to account)."""
        self.rgw = rgw
        self.users = dict(users)
        self._tokens: Dict[str, Tuple[str, float]] = {}
        self._server: Optional[asyncio.base_events.Server] = None
        self.addr = ""

    async def start(self, host: str = "127.0.0.1",
                    port: int = 0) -> str:
        self._server = await asyncio.start_server(
            self._serve, host, port, limit=8 << 20)
        port = self._server.sockets[0].getsockname()[1]
        self.addr = f"{host}:{port}"
        return self.addr

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            try:
                await asyncio.wait_for(self._server.wait_closed(), 5)
            except (Exception, asyncio.TimeoutError):
                pass
            self._server = None
        self.rgw.stop_etag_pool()

    # -- HTTP plumbing (same shape as the S3 frontend) --------------------

    async def _serve(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                line = await reader.readline()
                if not line:
                    return
                try:
                    method, target, _ver = \
                        line.decode("latin-1").strip().split(" ", 2)
                except ValueError:
                    return
                headers: Dict[str, str] = {}
                while True:
                    hline = await reader.readline()
                    if hline in (b"\r\n", b"\n", b""):
                        break
                    name, _, value = \
                        hline.decode("latin-1").partition(":")
                    headers[name.strip().lower()] = value.strip()
                try:
                    length = int(headers.get("content-length", "0"))
                except ValueError:
                    return
                if length > MAX_BODY or length < 0:
                    return
                if length and not self._token_ok(headers):
                    return  # pre-body screen, like the S3 frontend
                body = await reader.readexactly(length) \
                    if length else b""
                keep = headers.get("connection",
                                   "").lower() != "close"
                status, rhdrs, rbody = await self._handle(
                    method.upper(), target, headers, body)
                reason = {200: "OK", 201: "Created", 202: "Accepted",
                          204: "No Content", 401: "Unauthorized",
                          404: "Not Found", 409: "Conflict",
                          500: "Internal Error"}.get(status, "OK")
                out = [f"HTTP/1.1 {status} {reason}\r\n".encode()]
                rhdrs.setdefault("Content-Length", str(len(rbody)))
                rhdrs.setdefault("Connection",
                                 "keep-alive" if keep else "close")
                for k, v in rhdrs.items():
                    out.append(f"{k}: {v}\r\n".encode())
                out.append(b"\r\n")
                writer.write(b"".join(out))
                if method.upper() != "HEAD" and rbody:
                    writer.write(rbody)
                await writer.drain()
                if not keep:
                    return
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    # -- TempAuth ----------------------------------------------------------

    def _token_ok(self, headers: Dict[str, str]) -> bool:
        tok = headers.get("x-auth-token", "")
        ent = self._tokens.get(tok)
        return ent is not None and ent[1] > time.monotonic()

    def _account_of(self, headers: Dict[str, str]) -> Optional[str]:
        ent = self._tokens.get(headers.get("x-auth-token", ""))
        if ent is None or ent[1] <= time.monotonic():
            return None
        return ent[0]

    def _auth(self, headers: Dict[str, str]
              ) -> Tuple[int, Dict[str, str], bytes]:
        user = headers.get("x-auth-user", "")
        account = user.split(":", 1)[0]
        key = headers.get("x-auth-key", "")
        if not account or self.users.get(account) != key:
            return 401, {}, b"auth failed\n"
        token = "AUTH_tk" + secrets.token_hex(16)
        self._tokens[token] = (account,
                               time.monotonic() + TOKEN_TTL)
        return 200, {
            "X-Auth-Token": token,
            "X-Storage-Token": token,
            "X-Storage-Url": f"http://{self.addr}/v1/AUTH_{account}",
        }, b""

    # -- dispatch ----------------------------------------------------------

    async def _handle(self, method: str, target: str,
                      headers: Dict[str, str], body: bytes
                      ) -> Tuple[int, Dict[str, str], bytes]:
        import urllib.parse

        path, _, query = target.partition("?")
        q = dict(urllib.parse.parse_qsl(query,
                                        keep_blank_values=True))
        if path.rstrip("/") == "/auth/v1.0" and method == "GET":
            return self._auth(headers)
        if not path.startswith("/v1/AUTH_"):
            return 404, {}, b"not found\n"
        account = self._account_of(headers)
        if account is None:
            return 401, {}, b"token required\n"
        rest = urllib.parse.unquote(
            path[len(f"/v1/AUTH_{account}"):]).strip("/")
        try:
            if not rest:
                return await self._account_op(method, q)
            if "/" not in rest:
                return await self._container_op(method, rest, q)
            container, obj = rest.split("/", 1)
            return await self._object_op(method, container, obj,
                                         headers, body)
        except RGWError as e:
            return (_ERR_STATUS.get(e.code, 400), {},
                    f"{e.code}\n".encode())
        except Exception:
            log.exception("swift: %s %s failed", method, target)
            return 500, {}, b"internal error\n"

    async def _account_op(self, method: str, q: Dict
                          ) -> Tuple[int, Dict[str, str], bytes]:
        if method not in ("GET", "HEAD"):
            return 405, {}, b""
        names = await self.rgw.list_buckets()
        if q.get("format") == "json":
            body = json.dumps([{"name": n} for n in names]).encode()
            ctype = "application/json"
        else:
            body = ("".join(n + "\n" for n in names)).encode()
            ctype = "text/plain"
        return ((204 if not body else 200),
                {"Content-Type": ctype,
                 "X-Account-Container-Count": str(len(names))}, body)

    async def _container_op(self, method: str, container: str,
                            q: Dict
                            ) -> Tuple[int, Dict[str, str], bytes]:
        if method == "PUT":
            try:
                await self.rgw.create_bucket(container)
                return 201, {}, b""
            except RGWError as e:
                if e.code == "BucketAlreadyExists":
                    return 202, {}, b""  # Swift PUT is idempotent
                raise
        if method == "DELETE":
            await self.rgw.delete_bucket(container)
            return 204, {}, b""
        if method in ("GET", "HEAD"):
            entries = await self.rgw.list_objects(
                container, prefix=q.get("prefix", ""))
            if q.get("format") == "json":
                body = json.dumps([
                    {"name": e["key"], "bytes": e.get("size", 0),
                     "hash": e.get("etag", "")}
                    for e in entries]).encode()
                ctype = "application/json"
            else:
                body = ("".join(e["key"] + "\n"
                                for e in entries)).encode()
                ctype = "text/plain"
            return ((204 if not body else 200),
                    {"Content-Type": ctype,
                     "X-Container-Object-Count": str(len(entries))},
                    body)
        return 405, {}, b""

    async def _object_op(self, method: str, container: str, obj: str,
                         headers: Dict, body: bytes
                         ) -> Tuple[int, Dict[str, str], bytes]:
        if method == "PUT":
            etag, _vid = await self.rgw.put_object_ex(container, obj,
                                                      body)
            return 201, {"ETag": etag}, b""
        if method in ("GET", "HEAD"):
            head = await self.rgw.head_object(container, obj)
            hdrs = {"ETag": head.get("etag", ""),
                    "Content-Type": "application/octet-stream",
                    "Content-Length": str(head.get("size", 0))}
            if method == "HEAD":
                return 200, hdrs, b""
            data, _etag = await self.rgw.get_object_ex(container, obj)
            return 200, hdrs, bytes(data)
        if method == "DELETE":
            await self.rgw.delete_object(container, obj)
            return 204, {}, b""
        return 405, {}, b""
