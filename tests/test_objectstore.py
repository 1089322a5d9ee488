"""ObjectStore tests, mirroring store_test.cc: the same suite runs against
MemStore and TPUStore (parameterized fixture, like the reference's
bluestore/memstore fixture), plus TPUStore-specific persistence, checksum
corruption detection, and compression behavior."""

import os

import numpy as np
import pytest

from ceph_tpu.common.config import Config
from ceph_tpu.kv import MemDB, SQLiteDB
from ceph_tpu.os import ObjectId, Transaction
from ceph_tpu.os.memstore import MemStore
from ceph_tpu.os.tpustore import Allocator, TPUStore

CID = "1.0_head"
OID = ObjectId("obj1")


# -- kv --------------------------------------------------------------------


@pytest.mark.parametrize("make_db", [
    lambda p: MemDB(),
    lambda p: SQLiteDB(os.path.join(p, "kv.db")),
])
def test_kv_basic(tmp_path, make_db):
    db = make_db(str(tmp_path))
    db.create_and_open()
    t = db.get_transaction()
    t.set("P", b"a", b"1")
    t.set("P", b"b", b"2")
    t.set("Q", b"a", b"other")
    db.submit_transaction(t)
    assert db.get("P", b"a") == b"1"
    assert db.get("Q", b"a") == b"other"
    assert db.get("P", b"z") is None
    assert list(db.get_iterator("P")) == [(b"a", b"1"), (b"b", b"2")]
    t2 = db.get_transaction()
    t2.rmkey("P", b"a")
    t2.rm_range_keys("P", b"b", b"c")
    db.submit_transaction(t2)
    assert list(db.get_iterator("P")) == []
    assert db.get("Q", b"a") == b"other"
    db.close()


def test_sqlite_persistence(tmp_path):
    path = os.path.join(str(tmp_path), "kv.db")
    db = SQLiteDB(path)
    db.create_and_open()
    t = db.get_transaction()
    t.set("P", b"k", b"v")
    db.submit_transaction(t)
    db.close()
    db2 = SQLiteDB(path)
    db2.create_and_open()
    assert db2.get("P", b"k") == b"v"
    db2.close()


# -- allocator -------------------------------------------------------------


def test_allocator_first_fit_and_merge():
    a = Allocator()
    o1 = a.allocate(100)
    o2 = a.allocate(50)
    assert (o1, o2) == (0, 100)
    a.release(o1, 100)
    assert a.allocate(40) == 0      # reuses the freed hole
    a.release(0, 40)
    assert a.free == [(0, 100)]     # adjacent frees merged back
    assert a.allocate(100) == 0


# -- parameterized store suite (store_test.cc shape) -----------------------


@pytest.fixture(params=["memstore", "tpustore"])
def store(request, tmp_path):
    if request.param == "memstore":
        s = MemStore()
        s.mkfs()
        s.mount()
    else:
        s = TPUStore(str(tmp_path / "store"))
        s.mkfs()
        s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    yield s
    s.umount()


def _write(store, oid, offset, data, cid=CID):
    t = Transaction()
    t.write(cid, oid, offset, len(data), data)
    store.queue_transaction(t)


def test_write_read_round_trip(store):
    data = np.random.default_rng(0).integers(
        0, 256, 200_000, dtype=np.uint8).tobytes()
    _write(store, OID, 0, data)
    assert store.read(CID, OID) == data
    assert store.stat(CID, OID)["size"] == len(data)
    assert store.read(CID, OID, 1000, 500) == data[1000:1500]
    assert store.read(CID, OID, len(data) - 10, 100) == data[-10:]


def test_overwrite_and_extend(store):
    _write(store, OID, 0, b"a" * 1000)
    _write(store, OID, 500, b"b" * 1000)      # overlap + extend
    out = store.read(CID, OID)
    assert out == b"a" * 500 + b"b" * 1000
    _write(store, OID, 100_000, b"far")       # sparse write
    out = store.read(CID, OID)
    assert len(out) == 100_003
    assert out[1500:100_000] == bytes(98_500)  # hole reads as zeros
    assert out.endswith(b"far")


def test_zero_truncate(store):
    _write(store, OID, 0, b"x" * 10_000)
    t = Transaction()
    t.zero(CID, OID, 1000, 2000)
    t.truncate(CID, OID, 5000)
    store.queue_transaction(t)
    out = store.read(CID, OID)
    assert len(out) == 5000
    assert out[:1000] == b"x" * 1000
    assert out[1000:3000] == bytes(2000)
    assert out[3000:] == b"x" * 2000


def test_touch_remove_exists(store):
    t = Transaction()
    t.touch(CID, OID)
    store.queue_transaction(t)
    assert store.exists(CID, OID)
    assert store.stat(CID, OID)["size"] == 0
    t = Transaction()
    t.remove(CID, OID)
    store.queue_transaction(t)
    assert not store.exists(CID, OID)
    with pytest.raises(KeyError):
        store.read(CID, OID)


def test_xattrs(store):
    t = Transaction()
    t.touch(CID, OID)
    t.setattr(CID, OID, "_", b"object_info")
    t.setattrs(CID, OID, {"snapset": b"\x01\x02", "hinfo_key": b"{}"})
    store.queue_transaction(t)
    assert store.getattr(CID, OID, "_") == b"object_info"
    attrs = store.getattrs(CID, OID)
    assert set(attrs) == {"_", "snapset", "hinfo_key"}
    t = Transaction()
    t.rmattr(CID, OID, "snapset")
    store.queue_transaction(t)
    assert "snapset" not in store.getattrs(CID, OID)


def test_omap(store):
    t = Transaction()
    t.touch(CID, OID)
    t.omap_setheader(CID, OID, b"hdr")
    t.omap_setkeys(CID, OID, {"k1": b"v1", "k2": b"v2", "k3": b"v3"})
    store.queue_transaction(t)
    assert store.omap_get(CID, OID) == {"k1": b"v1", "k2": b"v2",
                                        "k3": b"v3"}
    assert store.omap_get_header(CID, OID) == b"hdr"
    t = Transaction()
    t.omap_rmkeys(CID, OID, ["k2"])
    store.queue_transaction(t)
    assert set(store.omap_get(CID, OID)) == {"k1", "k3"}
    t = Transaction()
    t.omap_clear(CID, OID)
    store.queue_transaction(t)
    assert store.omap_get(CID, OID) == {}


def test_omap_rmkeyrange(store):
    """[first, last) goes, keys on either side stay, and the removal
    orders with the keys set after it in one transaction."""
    t = Transaction()
    t.touch(CID, OID)
    t.omap_setkeys(CID, OID, {k: b"v" for k in
                              ("info", "log", "log.01", "log.02", "log/",
                               "missing")})
    store.queue_transaction(t)
    t = Transaction()
    t.omap_rmkeyrange(CID, OID, "log", "log/")
    t.omap_setkeys(CID, OID, {"log.03": b"w"})
    t.omap_rmkeyrange(CID, ObjectId("absent"), "a", "z")
    store.queue_transaction(t)
    assert store.omap_get(CID, OID) == {"info": b"v", "log.03": b"w",
                                        "log/": b"v", "missing": b"v"}


def test_clone(store):
    _write(store, OID, 0, b"payload" * 100)
    t = Transaction()
    t.setattr(CID, OID, "a", b"1")
    t.omap_setkeys(CID, OID, {"ok": b"ov"})
    store.queue_transaction(t)
    dst = ObjectId("obj1", snap=4)
    t = Transaction()
    t.clone(CID, OID, dst)
    store.queue_transaction(t)
    assert store.read(CID, dst) == b"payload" * 100
    assert store.getattr(CID, dst, "a") == b"1"
    assert store.omap_get(CID, dst) == {"ok": b"ov"}
    # diverge the clone; the original is untouched
    _write(store, dst, 0, b"CHANGED")
    assert store.read(CID, OID)[:7] == b"payload"


def test_collection_move_rename(store):
    cid2 = "1.1_head"
    t = Transaction()
    t.create_collection(cid2)
    store.queue_transaction(t)
    _write(store, OID, 0, b"moving")
    t = Transaction()
    t.omap_setkeys(CID, OID, {"k": b"v"})
    store.queue_transaction(t)
    dst = ObjectId("obj1_renamed")
    t = Transaction()
    t.collection_move_rename(CID, OID, cid2, dst)
    store.queue_transaction(t)
    assert not store.exists(CID, OID)
    assert store.read(cid2, dst) == b"moving"
    assert store.omap_get(cid2, dst) == {"k": b"v"}


def test_list_objects_and_collections(store):
    assert CID in store.list_collections()
    for i in range(5):
        _write(store, ObjectId(f"o{i}"), 0, b"d")
    names = [str(o) for o in store.list_objects(CID)]
    assert names == [f"o{i}" for i in range(5)]


def test_on_commit_callback(store):
    fired = []
    t = Transaction()
    t.touch(CID, OID)
    t.register_on_commit(lambda: fired.append(1))
    store.queue_transaction(t)
    assert fired == [1]


def test_statfs(store):
    _write(store, OID, 0, np.random.default_rng(1).integers(
        0, 256, 100_000, dtype=np.uint8).tobytes())
    fs = store.statfs()
    assert fs["allocated"] > 0


# -- TPUStore specifics ----------------------------------------------------


def test_tpustore_remount_persistence(tmp_path):
    path = str(tmp_path / "store")
    s = TPUStore(path)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    data = np.random.default_rng(2).integers(
        0, 256, 300_000, dtype=np.uint8).tobytes()
    _write(s, OID, 0, data)
    t = Transaction()
    t.setattr(CID, OID, "hinfo_key", b"ledger")
    t.omap_setkeys(CID, OID, {"pk": b"pv"})
    s.queue_transaction(t)
    alloc_before = s.statfs()["allocated"]
    fsid = s.fsid
    assert fsid
    s.umount()

    s2 = TPUStore(path)
    s2.mount()
    assert s2.fsid == fsid  # the same disk presents the same identity
    assert s2.read(CID, OID) == data
    assert s2.getattr(CID, OID, "hinfo_key") == b"ledger"
    assert s2.omap_get(CID, OID) == {"pk": b"pv"}
    assert s2.statfs()["allocated"] == alloc_before
    # COW overwrite reuses freed extents rather than leaking
    _write(s2, OID, 0, data)
    _write(s2, OID, 0, data)
    assert s2.statfs()["allocated"] <= alloc_before + s2.max_blob_size
    s2.umount()


def test_tpustore_detects_bitrot(tmp_path):
    """_verify_csum: a flipped bit on the device fails the read."""
    path = str(tmp_path / "store")
    s = TPUStore(path)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    data = np.random.default_rng(3).integers(
        0, 256, 50_000, dtype=np.uint8).tobytes()
    _write(s, OID, 0, data)
    s.umount()
    # corrupt one byte in the block file
    with open(os.path.join(path, "block"), "r+b") as f:
        f.seek(12345)
        b = f.read(1)
        f.seek(12345)
        f.write(bytes([b[0] ^ 0x40]))
    s2 = TPUStore(path)
    s2.mount()
    with pytest.raises(IOError):
        s2.read(CID, OID)
    s2.umount()


def test_tpustore_compression(tmp_path):
    cfg = Config()
    cfg.set_val("bluestore_compression_mode", "aggressive")
    cfg.set_val("bluestore_compression_algorithm", "lz4")
    s = TPUStore(str(tmp_path / "store"), config=cfg)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    compressible = (b"the quick brown fox " * 20_000)  # 400 KB
    _write(s, OID, 0, compressible)
    assert s.read(CID, OID) == compressible
    fs = s.statfs()
    assert fs["allocated"] < len(compressible) // 2   # actually compressed
    # incompressible data is stored raw (ratio gate)
    rnd = np.random.default_rng(4).integers(
        0, 256, 200_000, dtype=np.uint8).tobytes()
    _write(s, ObjectId("rand"), 0, rnd)
    assert s.read(CID, ObjectId("rand")) == rnd
    s.umount()


def test_tpustore_csum_disabled(tmp_path):
    cfg = Config()
    cfg.set_val("bluestore_csum_type", "none")
    s = TPUStore(str(tmp_path / "store"), config=cfg)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    _write(s, OID, 0, b"no csums")
    assert s.read(CID, OID) == b"no csums"
    s.umount()


def test_tpustore_requires_collection(tmp_path):
    s = TPUStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    with pytest.raises(KeyError):
        _write(s, OID, 0, b"x", cid="nonexistent")
    s.umount()


def test_tpustore_failed_txn_leaves_store_intact(tmp_path):
    """A transaction failing mid-apply must not corrupt the allocator or
    commit partial state (review finding: released extents of live data)."""
    s = TPUStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    data = b"live data " * 5000
    _write(s, OID, 0, data)
    free_before = list(s._alloc.free)
    # txn: overwrite OID (releases its extent) then fail on a missing object
    t = Transaction()
    t.write(CID, OID, 0, 9, b"newdata!!")
    t.rmattr(CID, ObjectId("missing"), "x")
    with pytest.raises(KeyError):
        s.queue_transaction(t)
    # old data still intact, allocator restored, later writes safe
    assert s._alloc.free == free_before
    assert s.read(CID, OID) == data
    _write(s, ObjectId("other"), 0, b"z" * 100_000)
    assert s.read(CID, OID) == data
    s.umount()


def test_tpustore_mkcoll_and_write_one_txn(tmp_path):
    """create_collection + write in one transaction (no mid-txn commit)."""
    s = TPUStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection("c2")
    t.write("c2", OID, 0, 5, b"hello")
    s.queue_transaction(t)
    assert s.read("c2", OID) == b"hello"
    s.umount()


def test_tpustore_csum_config_change_keeps_data_readable(tmp_path):
    """Blobs carry their csum params; switching bluestore_csum_type must not
    invalidate existing data (review finding)."""
    path = str(tmp_path / "store")
    s = TPUStore(path)
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    _write(s, OID, 0, b"written with crc32c" * 100)
    s.umount()
    cfg = Config()
    cfg.set_val("bluestore_csum_type", "xxhash64")
    s2 = TPUStore(path, config=cfg)
    s2.mount()
    assert s2.read(CID, OID) == b"written with crc32c" * 100
    s2.umount()


def test_tpustore_deferred_release_within_txn(tmp_path):
    """Extents freed by one op must NOT be reusable by a later op in the
    same transaction (advisor high finding; sizes above
    prefer_deferred_size so the COW path — the one with extent
    churn — is what's exercised): a txn that rewrites A, writes
    B (first-fit would reuse A's freed extent), then fails must leave
    committed A readable after the abort — and the same early-release
    crash window must not exist on the success path either."""
    s = TPUStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    data_a = b"A" * 40_000
    _write(s, OID, 0, data_a)
    a_off = s._get_onode(CID, OID).blobs[0].offset

    # failing txn: rewrite A (frees its extent), write B (same size —
    # first-fit would grab A's extent if released early), then fail
    t = Transaction()
    t.write(CID, OID, 0, len(data_a), b"a" * 40_000)
    t.write(CID, ObjectId("B"), 0, 40_000, b"B" * 40_000)
    t.rmattr(CID, ObjectId("missing"), "x")
    with pytest.raises(KeyError):
        s.queue_transaction(t)
    assert s.read(CID, OID) == data_a          # A survives the abort
    with pytest.raises(KeyError):
        s.read(CID, ObjectId("B"))

    # success path: same shape without the failure — B must not have been
    # written over A's old extent before the commit point
    t = Transaction()
    t.write(CID, OID, 0, len(data_a), b"a" * 40_000)
    t.write(CID, ObjectId("B"), 0, 40_000, b"B" * 40_000)
    s.queue_transaction(t)
    assert s.read(CID, OID) == b"a" * 40_000
    assert s.read(CID, ObjectId("B")) == b"B" * 40_000
    b_off = s._get_onode(CID, ObjectId("B")).blobs[0].offset
    assert b_off != a_off
    # after commit the freed extent IS reusable
    t = Transaction()
    t.write(CID, ObjectId("C"), 0, 40_000, b"C" * 40_000)
    s.queue_transaction(t)
    assert s._get_onode(CID, ObjectId("C")).blobs[0].offset == a_off
    s.umount()


def test_tpustore_remove_defers_release(tmp_path):
    """_object_remove frees extents only after the KV commit: a remove+write
    txn that fails must leave the removed object fully readable."""
    s = TPUStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    data = b"keep me " * 4000
    _write(s, OID, 0, data)
    t = Transaction()
    t.remove(CID, OID)
    t.write(CID, ObjectId("B"), 0, len(data), b"B" * len(data))
    t.rmattr(CID, ObjectId("missing"), "x")
    with pytest.raises(KeyError):
        s.queue_transaction(t)
    assert s.read(CID, OID) == data
    s.umount()


def test_tpustore_deferred_write_wal(tmp_path):
    """Small overwrites take the deferred path: journaled in the KV
    batch, applied in place after commit, REPLAYED on mount if the
    block file never caught up (BlueStore _deferred_replay)."""
    s = TPUStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    _write(s, OID, 0, b"x" * 8000)
    base_off = s._get_onode(CID, OID).blobs[0].offset

    # small overwrite: same extent (in-place), journal entry present
    _write(s, OID, 1000, b"Y" * 500)
    assert s._get_onode(CID, OID).blobs[0].offset == base_off
    got = s.read(CID, OID)
    assert got[1000:1500] == b"Y" * 500 and got[:1000] == b"x" * 1000

    # crash before the lazy block flush: nuke the block file's new
    # bytes by restoring pre-overwrite content, then remount — the
    # journal must replay the overwrite
    s._block.flush()
    import os

    with open(s._block_path, "r+b") as f:
        f.seek(base_off)
        f.write(b"x" * 8000)  # simulate lost in-place write
    s._kv.close()
    s._block.close()
    s._mounted = False
    s2 = TPUStore(str(tmp_path / "store"))
    s2.mount()
    got = s2.read(CID, OID)
    assert got[1000:1500] == b"Y" * 500, "WAL replay lost the write"
    # replay trims the journal
    assert list(s2._kv.get_iterator("D")) == []
    s2.umount()


def test_tpustore_deferred_batch_trim(tmp_path):
    s = TPUStore(str(tmp_path / "store"))
    s.deferred_batch = 4
    s.mkfs()
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    s.queue_transaction(t)
    _write(s, OID, 0, b"x" * 4000)
    for i in range(6):
        _write(s, OID, 100 * i, bytes([i]) * 50)
    # after 4+ deferred commits the batch flushed: <= 2 entries remain
    assert len(list(s._kv.get_iterator("D"))) <= 2
    assert len(s._pending_defer) <= 2
    out = s.read(CID, OID)
    for i in range(6):
        assert out[100 * i:100 * i + 50] == bytes([i]) * 50, i
    s.umount()
    # umount flushed everything
    s3 = TPUStore(str(tmp_path / "store"))
    s3.mount()
    assert list(s3._kv.get_iterator("D")) == []
    s3.umount()
