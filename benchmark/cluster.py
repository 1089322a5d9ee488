"""The deployment of one configuration file, in this process.

The mon, every OSD and the client are the program's own classes on one
asyncio loop: the only topology in which the OSDs reach the chip.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass, field
from typing import Any, Dict, List

CLEAN_TIMEOUT_S = 120.0


@dataclass
class Cluster:
    config: Dict[str, Any]
    mon: Any = None
    osds: Dict[int, Any] = field(default_factory=dict)
    stores: Dict[int, Any] = field(default_factory=dict)
    client: Any = None
    services: List[Any] = field(default_factory=list)   # stopped first


def _store(kind: str):
    if kind != "memstore":
        raise ValueError(f"objectstore {kind!r} is not supported here")
    from ceph_tpu.os.memstore import MemStore

    return MemStore()


async def start(config: Dict[str, Any]) -> Cluster:
    from ceph_tpu.mon import MonDaemon
    from ceph_tpu.osd.daemon import OSDDaemon
    from ceph_tpu.rados.client import RadosClient

    cl = Cluster(config)
    if config["osds"] % config["hosts"]:
        raise ValueError("osds must spread evenly over hosts")
    cl.mon = MonDaemon(config["osds"],
                       osds_per_host=config["osds"] // config["hosts"],
                       config=dict(config["mon_config"]))
    addr = await cl.mon.start()
    for i in range(config["osds"]):
        store = _store(config["objectstore"])
        store.mkfs()
        store.mount()
        cl.stores[i] = store
        cl.osds[i] = OSDDaemon(i, [addr], store=store,
                               config=dict(config["osd_config"]))
        await cl.osds[i].start()
    cl.client = RadosClient([addr])
    await cl.client.connect()
    for pool in config["pools"]:
        if pool["type"] == "erasure":
            await cl.client.create_ec_pool(
                pool["name"], dict(config["ec_profile"]),
                pg_num=pool["pg_num"])
        else:
            await cl.client.create_replicated_pool(
                pool["name"], size=pool["size"], pg_num=pool["pg_num"])
    await wait_for_clean(cl)
    return cl


async def wait_for_clean(cl: Cluster) -> None:
    """Every OSD has the mon's map and every PG is active on its primary."""
    from ceph_tpu.osd.osdmap import PgId

    def clean() -> bool:
        osdmap = cl.mon.osdmap
        if any(o.osdmap is None or o.osdmap.epoch < osdmap.epoch
               for o in cl.osds.values()):
            return False
        for pool in osdmap.pools.values():
            for ps in range(pool.pg_num):
                pg = PgId(pool.id, ps)
                _acting, primary = osdmap.pg_to_acting_osds(pg)
                state = cl.osds[primary].pgs.get(pg) if primary >= 0 \
                    else None
                if state is None or state.state != "active" or \
                        state.unfound:
                    return False
        return True

    loop = asyncio.get_running_loop()
    deadline = loop.time() + CLEAN_TIMEOUT_S
    while not clean():
        if loop.time() > deadline:
            raise TimeoutError("the cluster never went clean")
        await asyncio.sleep(0.05)


def locate(cl: Cluster, pool_name: str, oid: str):
    """(pg, acting osds) of an object, from the mon's map."""
    from ceph_tpu.ops.rjenkins import ceph_str_hash_rjenkins
    from ceph_tpu.osd.osdmap import PgId

    osdmap = cl.mon.osdmap
    pool = osdmap.pools[osdmap.lookup_pool(pool_name)]
    pg = pool.raw_pg_to_pg(PgId(pool.id,
                                ceph_str_hash_rjenkins(oid.encode())))
    acting, _primary = osdmap.pg_to_acting_osds(pg)
    return pg, acting


async def stop(cl: Cluster) -> None:
    for svc in reversed(cl.services):
        await svc.stop()
    if cl.client is not None:
        await cl.client.shutdown()
    for osd in cl.osds.values():
        await osd.stop()
    for store in cl.stores.values():
        store.umount()
    if cl.mon is not None:
        await cl.mon.shutdown()
