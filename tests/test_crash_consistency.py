"""Crash-consistency tier: power-cut fault injection for TPUStore and
durable OSD restarts.

Store level (os/faultstore.py, the CrashMonkey/ALICE shape): a mixed
write/overwrite/deferred/omap workload is recorded, every legal
post-crash image (prefix cuts, dropped/reordered un-synced writes,
torn partial-sector writes) is synthesized, remounted and checked —
mount succeeds, acked transactions are fully visible, journal replay
is idempotent (including a second crash DURING replay), checksums are
clean, the freelist and blob map agree.  A deliberately broken store
(fsync removed / commit demoted) must be CAUGHT by the same sweep —
the harness self-test.

Cluster level (tests/cluster_helpers.py persistent mode): kill_osd
crash-closes (or power-cuts) a TPUStore and revive_osd REMOUNTS the
same directory — acked data survives real kill/remount cycles, a
revived OSD with an intact store recovers via the pg log (not full
backfill), scripted bit-rot is detected by the per-blob csum and
repaired from peers by scrub, and the fsid contract catches a fresh
store smuggled under a revived OSD id.

Sizing: CEPH_TPU_CRASH_SWEEP_TXNS shrinks the tier-1 sweep; the
full-duration thrash leg is marked slow.
"""

import asyncio
import os

import numpy as np
import pytest

from ceph_tpu.os import ObjectId, Transaction
from ceph_tpu.os.faultstore import (
    BrokenBlockStore,
    BrokenCommitStore,
    CrashSweep,
    FaultStore,
    build_image,
    durable_kv_prefix,
    snapshot_store,
    write_image,
)
from ceph_tpu.os.memstore import MemStore
from ceph_tpu.os.tpustore import TPUStore
from ceph_tpu.osd.pg_log import PGMETA_OID, PGLog, make_entry

from cluster_helpers import Cluster, tpustore_factory

SWEEP_TXNS = int(os.environ.get("CEPH_TPU_CRASH_SWEEP_TXNS", "24"))


# -- the sweep (tentpole acceptance) ---------------------------------------


def test_crash_sweep_mixed_workload_zero_violations(tmp_path):
    """The acceptance sweep: >= 200 distinct crash points (prefix,
    drop-subset, torn-write schedules) over the mixed workload, zero
    invariant violations, with double-crash-during-replay legs
    exercised."""
    rep = CrashSweep(str(tmp_path)).run(txns=SWEEP_TXNS, seed=0)
    assert not rep["violations"], rep["violations"][:5]
    floor = 200 if SWEEP_TXNS >= 24 else 8 * SWEEP_TXNS
    assert rep["points"] >= floor, rep
    assert rep["double_crash_points"] >= 1, \
        "no crash-during-replay schedule ran"
    assert rep["txns"] == SWEEP_TXNS


def test_crash_sweep_is_seed_sensitive_but_stable(tmp_path):
    """Two sweeps over the same seed explore the same trace (the
    synthesis is deterministic — a violation is reproducible)."""
    r1 = CrashSweep(str(tmp_path / "a")).run(txns=6, seed=3,
                                             double_crash=False)
    r2 = CrashSweep(str(tmp_path / "b")).run(txns=6, seed=3,
                                             double_crash=False)
    assert (r1["points"], r1["events"]) == (r2["points"], r2["events"])
    assert not r1["violations"] and not r2["violations"]


def test_sweep_catches_store_without_block_fsync(tmp_path):
    """Harness self-test: remove the pre-commit block fsync and the
    sweep must report violations (lost payloads under committed
    onodes surface as csum failures or model divergence)."""
    rep = CrashSweep(str(tmp_path), store_cls=BrokenBlockStore).run(
        txns=8, seed=1, double_crash=False)
    assert rep["violations"], "fsync-less store passed the sweep"


def test_sweep_catches_store_without_sync_commit(tmp_path):
    """Self-test twin: demote the commit point to a non-sync KV batch
    and acked transactions become losable — the sweep must flag the
    ack/durability inversion."""
    rep = CrashSweep(str(tmp_path), store_cls=BrokenCommitStore).run(
        txns=8, seed=1, double_crash=False)
    assert any("not durable" in v for v in rep["violations"]), \
        rep["violations"][:3]


def pg_log_workload(osd_min_pg_log_entries: int):
    """Sub-write shaped transactions: a data write plus the PG log's
    stage in the same transaction, with the log trimmed at
    `osd_min_pg_log_entries` and the missing set churning.  After each
    commit the log read back from a shadow of the committed state must
    be the in-memory log."""
    import random

    def workload(txns: int, seed: int):
        rng = random.Random(seed)
        log = PGLog()
        shadow = MemStore()
        shadow.mkfs()
        shadow.mount()
        t = Transaction()
        t.create_collection("cc")
        shadow.queue_transaction(t)
        for i in range(txns):
            oid = f"o{rng.randrange(6)}"
            n = rng.randrange(1000, 9000)
            t = Transaction()
            if i == 0:
                # TPUStore lists an object only once it has an onode
                # (the model lists omap-only objects too): create the
                # pgmeta object as the OSD's hitset persistence does
                t.touch("cc", ObjectId(PGMETA_OID))
            t.write("cc", ObjectId(oid), 0, n,
                    bytes(rng.getrandbits(8) for _ in range(n)))
            log.append(make_entry((1, i + 1), log.info.last_update,
                                  oid, "modify", n))
            log.trim_to(osd_min_pg_log_entries)
            if i % 4 == 1:
                log.missing[f"o{rng.randrange(6)}"] = (1, i)
            elif i % 4 == 3 and log.missing:
                log.missing.pop(sorted(log.missing)[0])
            log.stage(t, "cc")
            mirror = Transaction()
            mirror.ops = list(t.ops)
            yield t
            # the sweep committed t before drawing the next txn
            shadow.queue_transaction(mirror)
            got = PGLog.load(shadow, "cc")
            assert got.entries == log.entries
            assert got.info.to_dict() == log.info.to_dict()
            assert got.missing == log.missing

    return workload


@pytest.mark.parametrize("store_cls", [FaultStore, BrokenBlockStore,
                                       BrokenCommitStore])
def test_crash_sweep_pg_log_trims(tmp_path, store_cls):
    """The sweep over a workload whose PG log trims inside it (entry
    keys written and removed in the data's transaction): zero
    violations on the real store, and both broken stores are still
    caught."""
    rep = CrashSweep(str(tmp_path), store_cls=store_cls).run(
        workload=pg_log_workload(osd_min_pg_log_entries=3),
        txns=min(SWEEP_TXNS, 12), seed=2,
        double_crash=store_cls is FaultStore)
    if store_cls is FaultStore:
        assert not rep["violations"], rep["violations"][:5]
        assert rep["points"] >= 8 * rep["txns"], rep
    else:
        assert rep["violations"], f"{store_cls.__name__} passed"


def test_powercut_preserves_acked_writes(tmp_path):
    """Unit shape of the tentpole claim: acked direct AND deferred
    writes survive crash_powercut + remount; the deferred WAL replays
    on mount."""
    d = str(tmp_path / "s")
    s = FaultStore(d)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection("c")
    s.queue_transaction(t)
    acked = []
    t = Transaction()
    t.write("c", ObjectId("a"), 0, 5000, b"x" * 5000)
    t.register_on_commit(lambda: acked.append("direct"))
    s.queue_transaction(t)
    t = Transaction()
    t.write("c", ObjectId("a"), 100, 50, b"Y" * 50)  # deferred path
    t.register_on_commit(lambda: acked.append("deferred"))
    s.queue_transaction(t)
    assert acked == ["direct", "deferred"]
    assert s.perf["deferred_writes"] >= 1
    fsid = s.fsid
    s.crash_powercut()
    s2 = TPUStore(d)
    s2.mount()
    assert s2.fsid == fsid
    got = s2.read("c", ObjectId("a"))
    assert got[100:150] == b"Y" * 50 and got[:100] == b"x" * 100
    assert s2.perf["journal_replays"] == 1
    assert s2.perf["journal_replayed_bytes"] >= 50
    s2.umount()


def test_double_crash_inside_replay_is_idempotent(tmp_path):
    """tpustore.py claims replay idempotence; prove it: power-cut with
    pending deferred entries, then cut the REPLAY's own writes at
    every point and remount a third time — the deferred data must
    still be exactly visible."""
    d = str(tmp_path / "s")
    s = FaultStore(d)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection("c")
    s.queue_transaction(t)
    t = Transaction()
    t.write("c", ObjectId("a"), 0, 8000, b"x" * 8000)
    s.queue_transaction(t)
    for i in range(3):  # several live journal entries
        t = Transaction()
        t.write("c", ObjectId("a"), 1000 * i, 64, bytes([65 + i]) * 64)
        s.queue_transaction(t)
    assert s.perf["deferred_writes"] == 3
    s.crash_powercut()

    # first remount records its replay trace
    probe = FaultStore(d)
    probe.mount()
    replay = list(probe.crashlog.events)
    base_block, base_kv = probe.base_block, probe.base_kv
    probe.crash()
    assert any(ev[0] == "write" for ev in replay), "replay did nothing"

    img = str(tmp_path / "img")
    checked = 0
    for inner in range(1, len(replay) + 1):
        block, ops = build_image(replay, inner, drop_pending=True,
                                 kv_keep="min", base_block=base_block)
        write_image(img, block, ops, base_kv=base_kv)
        s3 = TPUStore(img)
        s3.mount()  # second replay
        got = s3.read("c", ObjectId("a"))
        for i in range(3):
            assert got[1000 * i:1000 * i + 64] == bytes([65 + i]) * 64
        s3.umount()
        checked += 1
    assert checked == len(replay)


def test_bitrot_detected_not_silently_served(tmp_path):
    """Scripted bit-rot flips a stored byte; the per-blob csum must
    fail the read (EIO shape), never return corrupt bytes."""
    s = FaultStore(str(tmp_path / "s"))
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection("c")
    t.write("c", ObjectId("b"), 0, 4000, b"z" * 4000)
    s.queue_transaction(t)
    s.inject_bitrot("c", ObjectId("b"), byte=123)
    with pytest.raises(IOError):
        s.read("c", ObjectId("b"))
    assert s.perf["csum_read_failures"] == 1
    s.umount()


def test_snapshot_store_matches_itself_across_remount(tmp_path):
    """The model snapshot is remount-stable (the sweep's equality
    check is meaningful)."""
    d = str(tmp_path / "s")
    s = TPUStore(d)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection("c")
    t.write("c", ObjectId("o"), 0, 3000, b"m" * 3000)
    t.setattr("c", ObjectId("o"), "a", b"v")
    t.omap_setkeys("c", ObjectId("o"), {"k": b"w"})
    t.omap_setheader("c", ObjectId("o"), b"h")
    s.queue_transaction(t)
    snap = snapshot_store(s)
    s.umount()
    s2 = TPUStore(d)
    s2.mount()
    assert snapshot_store(s2) == snap
    s2.umount()


def test_durable_kv_prefix_semantics():
    """min cuts at the last sync batch; max keeps the whole prefix."""
    events = [
        ("kv", [("set", "S", b"a", b"1")], True),
        ("kv", [("set", "S", b"b", b"2")], False),
        ("kv", [("set", "S", b"c", b"3")], True),
        ("kv", [("set", "S", b"d", b"4")], False),
    ]
    assert len(durable_kv_prefix(events, 4, "min")) == 3
    assert len(durable_kv_prefix(events, 4, "max")) == 4
    assert len(durable_kv_prefix(events, 2, "min")) == 1


# -- persistent clusters ---------------------------------------------------


def _run(coro, timeout):
    asyncio.run(asyncio.wait_for(coro, timeout))


def test_persistent_cluster_kill_remount_acked_data(tmp_path):
    """The thrash leg (smoke size): TPUStore-backed OSDs, real
    kill -> power-cut -> remount cycles with fault injection armed
    (CEPH_TPU_CRASH_INJECT default-on + FaultStore), RadosModel acked
    -data discipline — no acked write lost, bit-exact readback — and
    store_status shows remounts replaying the WAL."""
    import random

    async def main():
        rng = random.Random(17)
        cluster = Cluster(
            num_osds=4, osds_per_host=1,
            store_factory=tpustore_factory(tmp_path, fault=True),
            persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "crash", size=2, pg_num=8)
            io = cluster.client.open_ioctx("crash")
            nrng = np.random.default_rng(17)
            model: dict = {}

            async def write_some(n):
                for _ in range(n):
                    oid = f"obj-{rng.randrange(10)}"
                    data = nrng.integers(
                        0, 256, rng.randrange(500, 20_000),
                        dtype=np.uint8).tobytes()
                    await io.write_full(oid, data)
                    model[oid] = data  # acked: must survive anything

            await write_some(6)
            for cycle in range(4):
                osd = rng.choice(sorted(cluster.osds))
                await cluster.kill_osd(osd)
                await cluster.wait_for_osd_down(osd)
                await write_some(4)
                await cluster.revive_osd(osd)
                await cluster.wait_for_osd_up(osd)
                await cluster.wait_for_clean(timeout=90)
            for oid, want in model.items():
                assert await io.read(oid) == want, \
                    f"{oid}: acked write lost across kill/remount"
            # every store is a remount of its original disk
            for osd_id, store in cluster.stores.items():
                assert store.fsid == cluster.fsids[osd_id]
            rc, st = await cluster.client.osd_command(
                sorted(cluster.osds)[0], {"prefix": "store_status"})
            assert rc == 0
            assert st["type"] == "FaultStore" and st["mounted"]
            assert st["fsid"]
            assert "journal_replays" in st["perf"]
        finally:
            await cluster.stop()

    _run(main(), 420)


def test_persistent_revive_recovers_via_pg_log(tmp_path):
    """A revived OSD whose store is intact recovers the LOG DIFF
    (objects written while it was down), not the whole PG — the
    log-based-vs-backfill acceptance.  The log is trimmed aggressively
    so a fresh store WOULD have to backfill everything."""

    async def main():
        cluster = Cluster(
            num_osds=4, osds_per_host=1,
            osd_config={"osd_min_pg_log_entries": 8},
            store_factory=tpustore_factory(tmp_path, fault=True),
            persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "logs", size=2, pg_num=8)
            io = cluster.client.open_ioctx("logs")
            nrng = np.random.default_rng(5)
            total = 24
            for i in range(total):
                await io.write_full(
                    f"base-{i}",
                    nrng.integers(0, 256, 2000,
                                  dtype=np.uint8).tobytes())
            victim = 1
            await cluster.kill_osd(victim)
            await cluster.wait_for_osd_down(victim)
            while_down = 6
            for i in range(while_down):
                await io.write_full(
                    f"new-{i}",
                    nrng.integers(0, 256, 2000,
                                  dtype=np.uint8).tobytes())
            await cluster.revive_osd(victim)
            await cluster.wait_for_osd_up(victim)
            await cluster.wait_for_clean(timeout=120)
            rc, perf = await cluster.client.osd_command(
                victim, {"prefix": "perf dump"})
            assert rc == 0
            installs = perf["recovery_installs"]
            # log-driven: only what landed while down (about half the
            # new objects map to the victim), never the ~half of ALL
            # 30 objects a backfill would push
            assert 1 <= installs <= while_down + 2, installs
            assert installs < total // 2
        finally:
            await cluster.stop()

    _run(main(), 300)


def test_bitrot_repaired_from_peers_by_scrub(tmp_path):
    """End-to-end bit-rot repair: corrupt a TPUStore blob under a
    LIVE cluster; the per-blob csum turns the shard read into EIO,
    scrub detects the inconsistency and repairs it from peers through
    _scrub_repair, after which the shard reads clean again."""

    async def main():
        cluster = Cluster(
            num_osds=3, osds_per_host=1,
            store_factory=tpustore_factory(tmp_path, fault=True),
            persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_ec_pool(
                "rot", {"plugin": "ec_jax",
                        "technique": "reed_sol_van",
                        "k": "2", "m": "1",
                        "crush-failure-domain": "osd"}, pg_num=4)
            io = cluster.client.open_ioctx("rot")
            data = np.random.default_rng(9).integers(
                0, 256, 16_384, dtype=np.uint8).tobytes()
            await io.write_full("victim", data)
            pg = io.object_pg("victim")
            acting, primary = \
                cluster.mon.osdmap.pg_to_acting_osds(pg)
            # corrupt a NON-primary shard's stored blob
            idx, osd = next((i, o) for i, o in enumerate(acting)
                            if o != primary)
            cid = f"{pg.pool}.{pg.ps:x}s{idx}_head"
            store = cluster.stores[osd]
            store.inject_bitrot(cid, ObjectId("victim"), byte=77)
            with pytest.raises(IOError):
                store.read(cid, ObjectId("victim"))
            assert store.perf["csum_read_failures"] >= 1
            # the client still reads clean (decode works around EIO)
            assert await io.read("victim") == data
            # scrub on the primary detects + repairs via recovery
            prim = cluster.osds[primary]
            state = prim.pgs[pg]
            pool = prim.osdmap.pools[pg.pool]
            run = await prim.scrub_pg(state, pool)
            assert run["errors"] >= 1, run
            assert run["repaired"] >= 1, run
            # the corrupt shard was reinstalled: reads clean now
            assert store.read(cid, ObjectId("victim")) is not None
            assert await io.read("victim") == data
        finally:
            await cluster.stop()

    _run(main(), 300)


def test_revive_with_fresh_store_trips_fsid_assert(tmp_path):
    """The explicit revive contract: a wiped + re-mkfs'd directory
    under a revived OSD id fails the fsid assertion instead of
    silently booting loss-and-backfill."""
    import shutil

    async def main():
        cluster = Cluster(
            num_osds=3, osds_per_host=1,
            store_factory=tpustore_factory(tmp_path),
            persistent=True)
        await cluster.start()
        try:
            await cluster.kill_osd(2)
            await cluster.wait_for_osd_down(2)
            # wipe the disk and format a FRESH store at the same path
            shutil.rmtree(os.path.join(str(tmp_path), "osd-2"))
            fresh = tpustore_factory(tmp_path)(2)
            fresh.mkfs()
            with pytest.raises(AssertionError, match="fsid"):
                await cluster.revive_osd(2)
        finally:
            await cluster.stop()

    _run(main(), 180)


def test_store_counters_scrapeable_via_prometheus(tmp_path):
    """The perf-dump `store` section flattens to ceph_osd_store_*
    gauges (journal replays, csum failures, deferred depth) — the
    operator can alert on durability health."""

    async def main():
        from ceph_tpu.mgr import MgrDaemon

        cluster = Cluster(
            num_osds=3, osds_per_host=1,
            store_factory=tpustore_factory(tmp_path, fault=True),
            persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "pm", size=2, pg_num=4)
            io = cluster.client.open_ioctx("pm")
            await io.write_full("x", b"p" * 9000)
            mgr = MgrDaemon(cluster.mon.addr, config={})
            await mgr.start()
            try:
                prom = mgr.modules["prometheus"]
                host, port = prom.addr.split(":")
                reader, writer = await asyncio.open_connection(
                    host, int(port))
                writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(), 10.0)
                writer.close()
                body = raw.decode().split("\r\n\r\n", 1)[1]
                assert "ceph_osd_store_kv_commits" in body
                assert "ceph_osd_store_journal_replays" in body
                assert "ceph_osd_store_csum_read_failures" in body
                assert "ceph_osd_store_deferred_queue_depth" in body
            finally:
                await mgr.stop()
        finally:
            await cluster.stop()

    _run(main(), 240)


def test_pg_log_staging_live_counters(tmp_path):
    """Steady writes on a live TPUStore cluster (group commit on, logs
    trimmed at 4): no whole-log rewrite, 2-3 omap keys per sub-write's
    stage, every OSD's in-memory log equal to its stored log once the
    commit lane drains, and both counters in perf dump and
    prometheus."""

    async def main():
        from ceph_tpu.mgr import MgrDaemon

        cluster = Cluster(
            num_osds=3, osds_per_host=1,
            osd_config={"osd_min_pg_log_entries": 4},
            store_factory=tpustore_factory(tmp_path), persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "pl", size=2, pg_num=4)
            io = cluster.client.open_ioctx("pl")
            await io.write_full("warm", b"w" * 100)
            await cluster.wait_for_clean(timeout=90)

            async def dump():
                out = {}
                for o in sorted(cluster.osds):
                    rc, perf = await cluster.client.osd_command(
                        o, {"prefix": "perf dump"})
                    assert rc == 0
                    out[o] = perf
                return out

            before = await dump()
            writes = 30
            for i in range(writes):
                await io.write_full(f"s{i}", bytes([i]) * 3000)
            after = await dump()
            stages = 2 * writes  # the primary's shard and one replica
            keys = sum(after[o]["pglog_stage_keys"]
                       - before[o]["pglog_stage_keys"] for o in after)
            assert 2 * stages <= keys <= 3 * stages, (keys, stages)
            assert all(after[o]["pglog_full_rewrites"]
                       == before[o]["pglog_full_rewrites"]
                       for o in after)

            for osd in cluster.osds.values():
                await osd.committer.drain()
                for pg, state in osd.pgs.items():
                    pool = osd.osdmap.pools.get(pg.pool)
                    if state.log is None or pool is None:
                        continue
                    got = PGLog.load(osd.store, osd._cid(
                        pg, state.my_shard(osd.osd_id, pool.type)))
                    assert got.entries == state.log.entries, pg
                    assert got.info.to_dict() == \
                        state.log.info.to_dict(), pg
                    assert got.missing == state.log.missing, pg

            mgr = MgrDaemon(cluster.mon.addr, config={})
            await mgr.start()
            try:
                body = await mgr.modules["prometheus"].collect()
                assert "ceph_osd_pglog_stage_keys{" in body
                assert "ceph_osd_pglog_full_rewrites{" in body
            finally:
                await mgr.stop()
        finally:
            await cluster.stop()

    _run(main(), 240)


def test_crash_inject_kill_switch(tmp_path, monkeypatch):
    """CEPH_TPU_CRASH_INJECT=0: kill_osd degrades to the plain
    process-crash close (no power-cut synthesis) — everything the
    process wrote survives, including un-synced journal tails."""
    monkeypatch.setenv("CEPH_TPU_CRASH_INJECT", "0")

    async def main():
        cluster = Cluster(
            num_osds=3, osds_per_host=1,
            store_factory=tpustore_factory(tmp_path, fault=True),
            persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "ks", size=2, pg_num=4)
            io = cluster.client.open_ioctx("ks")
            await io.write_full("o", b"k" * 5000)
            await cluster.kill_osd(1)
            await cluster.wait_for_osd_down(1)
            await cluster.revive_osd(1)
            await cluster.wait_for_osd_up(1)
            await cluster.wait_for_clean(timeout=90)
            assert await io.read("o") == b"k" * 5000
        finally:
            await cluster.stop()

    _run(main(), 240)


# -- slow tier -------------------------------------------------------------


@pytest.mark.slow
def test_crash_sweep_full(tmp_path):
    """The exhaustive sweep: a bigger workload, two seeds, every
    schedule + double-crash legs."""
    for seed in (0, 7):
        rep = CrashSweep(str(tmp_path / f"s{seed}")).run(
            txns=40, seed=seed)
        assert not rep["violations"], rep["violations"][:5]
        assert rep["points"] >= 300


@pytest.mark.slow
def test_thrash_tpustore_persistent(tmp_path):
    """Full-duration thrash over TPUStore-backed OSDs: concurrent
    writes racing kill -> power-cut -> remount cycles, the acked-data
    discipline checked object by object."""
    import random

    async def main():
        rng = random.Random(4321)
        cluster = Cluster(
            num_osds=5, osds_per_host=1,
            store_factory=tpustore_factory(tmp_path, fault=True),
            persistent=True)
        await cluster.start()
        try:
            await cluster.client.create_replicated_pool(
                "tp", size=3, pg_num=8)
            io = cluster.client.open_ioctx("tp")
            nrng = np.random.default_rng(4321)
            # RadosModel discipline: an ACKED write must stick; an
            # UNACKED attempt may still have committed, so the legal
            # readback states are {last acked} U {attempts since}
            model: dict = {}
            maybe: dict = {}
            stop = False

            async def workload():
                seq = 0
                while not stop:
                    seq += 1
                    oid = f"obj-{rng.randrange(12)}"
                    data = nrng.integers(
                        0, 256, rng.randrange(1000, 40_000),
                        dtype=np.uint8).tobytes()
                    maybe.setdefault(oid, []).append(data)
                    try:
                        await io.write_full(oid, data)
                        model[oid] = data
                        maybe[oid] = []
                    except Exception:
                        pass  # indeterminate: stays in maybe
                    await asyncio.sleep(0)

            task = asyncio.get_running_loop().create_task(workload())
            try:
                for _ in range(10):
                    osd = rng.choice(sorted(cluster.osds))
                    await cluster.kill_osd(osd)
                    await cluster.wait_for_osd_down(osd)
                    await asyncio.sleep(1.0)
                    await cluster.revive_osd(osd)
                    await cluster.wait_for_osd_up(osd)
                    await cluster.wait_for_clean(timeout=120)
            finally:
                stop = True
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
            await cluster.wait_for_clean(timeout=120)
            for oid, want in model.items():
                got = await io.read(oid)
                legal = [want] + maybe.get(oid, [])
                assert any(got == w for w in legal), \
                    f"{oid}: readback matches neither the acked" \
                    f" state nor any of {len(maybe.get(oid, []))}" \
                    " indeterminate attempts"
        finally:
            await cluster.stop()

    _run(main(), 900)
