"""librados client kind: ``write_full`` of unique names, closed loop.

``concurrency`` workers, as ``rados bench -t`` has them: each sends its
next op when the last returns.  Object i's bytes and the order of the
names come from the seed; goodput counts an object's bytes when its write is acknowledged.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark import check
from benchmark.harness import Ctx, Names


class Driver:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        t = ctx.spec.traffic
        if t["op"] != "write_full":
            raise ValueError(f"rados driver: no op {t['op']!r}")
        self.size = int(t["object_bytes"])
        self.pool = ctx.spec.config["data_pool"]
        self.name = Names(ctx.seed, "obj.{:08d}")
        self.next = 0
        self.io = None

    async def start(self) -> None:
        self.io = self.ctx.cluster.client.open_ioctx(self.pool)

    async def _worker(self) -> None:
        rec = self.ctx.rec
        while not rec.stopping:
            i = self.next
            self.next += 1
            data = self.ctx.payloads.get(i)
            t0 = time.monotonic()
            try:
                await self.io.write_full(self.name(i), data)
                ok = True
            except Exception:
                ok = False
            t1 = time.monotonic()
            rec.op(t0, t1, ok)
            if ok:
                rec.credit(t1, self.size, i)

    async def run(self) -> None:
        await asyncio.gather(*(self._worker() for _ in
                               range(int(self.ctx.spec.traffic
                                         ["concurrency"]))))

    async def check(self):
        """A sample of the window's acknowledged writes, drawn from the
        seed: their shards on the stores, and their bytes read back."""
        acked = self.ctx.rec.acked
        rng = np.random.default_rng([self.ctx.seed, 1])
        n = min(int(self.ctx.spec.traffic["check_sample"]), len(acked))
        picks = sorted(int(x) for x in rng.choice(acked, n, replace=False))
        items = [(self.name(i), self.ctx.payloads.get(i)) for i in picks]
        counts = check.stored(self.ctx.cluster, self.pool, items)
        counts["reads_wrong"] = 0
        for oid, payload in items:
            try:
                got = await self.io.read(oid)
            except Exception:
                got = None
            counts["reads_wrong"] += got != payload
        counts["window_empty"] = int(not acked)
        return counts

    async def stop(self) -> None:
        pass
