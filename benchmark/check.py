"""The comparison that decides ``correct``: what acknowledged writes left
on the OSDs' stores, against the plain reference (reference/), and
whether the window ran on the device path its cell names.

For each sampled object in an EC pool, every one of its k+m shards is
read from the store of the OSD that the map says holds it, and compared
byte for byte with the reference's shard (data shards and the
technique's parity); the shard's hinfo crc is compared with the
reference crc32c.  A shard that is missing counts as wrong on both.  In
a replicated pool, each of the pool's ``size`` copies is compared with
the object's bytes.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from benchmark import cluster as cluster_mod
from benchmark.reference import ec as ref_ec


def geometry(config: Dict[str, Any]) -> Tuple[int, int, int, str]:
    """(k, m, stripe unit, technique) of the configuration's EC pool."""
    prof = config["ec_profile"]
    return (int(prof["k"]), int(prof["m"]),
            int(config["osd_config"]["osd_pool_erasure_code_stripe_unit"]),
            prof["technique"])


def stored(cl, pool: str, items: List[Tuple[str, bytes]]
           ) -> Dict[str, int]:
    """What the stores hold for acknowledged objects, by the pool's
    type."""
    kind = next(p["type"] for p in cl.config["pools"] if p["name"] == pool)
    if kind == "erasure":
        return stored_shards(cl, pool, items)
    return stored_replicas(cl, pool, items)


def stored_replicas(cl, pool: str, items: List[Tuple[str, bytes]]
                    ) -> Dict[str, int]:
    """Copies missing or differing from the object's bytes."""
    from ceph_tpu.os import ObjectId
    from ceph_tpu.rados.embedded import shard_collection

    size = next(p["size"] for p in cl.config["pools"] if p["name"] == pool)
    bad = {"replicas_wrong": 0}
    for oid, payload in items:
        pg, acting = cluster_mod.locate(cl, pool, oid)
        for i in range(size):
            store = cl.stores.get(acting[i] if i < len(acting) else -1)
            try:
                data = bytes(store.read(shard_collection(pg, -1),
                                        ObjectId(oid)))
            except (KeyError, IOError, AttributeError):
                data = None
            bad["replicas_wrong"] += data != payload
    return bad


def stored_shards(cl, pool: str, items: List[Tuple[str, bytes]]
                  ) -> Dict[str, int]:
    """Counts of shards and hinfo crcs that differ from the reference."""
    from ceph_tpu.os import ObjectId
    from ceph_tpu.osd import ec_util
    from ceph_tpu.rados.embedded import shard_collection

    k, m, chunk, technique = geometry(cl.config)
    bad = {"shards_wrong": 0, "hinfo_wrong": 0}
    by_size: Dict[int, List[Tuple[str, bytes]]] = {}
    for oid, payload in items:
        by_size.setdefault(len(payload), []).append((oid, payload))
    for group in by_size.values():
        shards, crcs = ref_ec.encode_objects([p for _o, p in group],
                                             k, m, chunk, technique)
        for n, (oid, _p) in enumerate(group):
            pg, acting = cluster_mod.locate(cl, pool, oid)
            for i in range(k + m):
                osd = acting[i] if i < len(acting) else -1
                store = cl.stores.get(osd)
                try:
                    cid = shard_collection(pg, i)
                    data = store.read(cid, ObjectId(oid))
                    attrs = store.getattrs(cid, ObjectId(oid))
                except (KeyError, IOError, AttributeError):
                    bad["shards_wrong"] += 1
                    bad["hinfo_wrong"] += 1
                    continue
                if bytes(data) != shards[n, i].tobytes():
                    bad["shards_wrong"] += 1
                try:
                    hinfo = json.loads(attrs[ec_util.HINFO_KEY])
                    crc = int(hinfo["cumulative_shard_hashes"][i])
                except (KeyError, IndexError, ValueError, TypeError):
                    crc = None
                if crc != int(crcs[n, i]):
                    bad["hinfo_wrong"] += 1
    return bad


def compared(counts: Dict[str, int], window: Dict[str, Any],
             expect_executor: Optional[str] = None
             ) -> Dict[str, Dict[str, int]]:
    """The numbers compared, each exact with limit 0.

    ``mismatches``: every mismatch of every kind, summed (a window that
    acknowledged nothing counts as one).  One sum and not a number per
    kind: every number compared needs a control reading above its limit,
    and the control (parity it never stored) moves one kind only.  The
    kinds are printed on stderr beside it.

    ``device_faults``: breaker failures, fallbacks, watchdog timeouts and
    trips, and the plan's host fallbacks, inside the window: a window
    served in part by the host is not the cell's device path.

    ``executor_idle``: 1 where the traffic names the executor its cell's
    why depends on (``expect_executor``) and that executor made no
    dispatch in the window."""
    faults = sum(sum(st.values()) for st in window["breaker"].values())
    faults += window["plan_host_fallbacks"]
    idle = int(bool(expect_executor)
               and window["executors"].get(expect_executor, 0) <= 0)
    return {"mismatches": {"value": int(sum(counts.values())), "limit": 0},
            "device_faults": {"value": int(faults), "limit": 0},
            "executor_idle": {"value": idle, "limit": 0}}
