"""PG log persistence: incremental staging against the in-memory log.

Every check holds the store to one rule: after a transaction commits,
`PGLog.load` reads back exactly the in-memory log (entries, info and
missing).  Staging writes one omap key per new entry and removes the
keys of trimmed ones, so its cost per write does not depend on the
log's length; a replaced log (merge, split, the older single-key form)
is rewritten whole.
"""

import collections
import json
import random

import pytest

from ceph_tpu.os import ObjectId, Transaction
from ceph_tpu.os.memstore import MemStore
from ceph_tpu.osd.pg_log import (
    K_ENTRY_PREFIX,
    K_INFO,
    K_LOG,
    K_MISSING,
    PGMETA_OID,
    PGInfo,
    PGLog,
    ZERO,
    entry_key,
    ev,
    make_entry,
)

CID = "1.0_head"
CID2 = "1.0s2_head"  # the same PG at another shard position


class FailingStore(MemStore):
    """MemStore whose marked transactions fail whole: nothing of them
    is applied and their on_commit never fires (the EIO path)."""

    def __init__(self) -> None:
        super().__init__()
        self.fail = set()

    def queue_transaction(self, txn: Transaction) -> None:
        if id(txn) in self.fail:
            raise IOError("injected commit failure")
        super().queue_transaction(txn)


def _store(cls=MemStore):
    store = cls()
    store.mkfs()
    store.mount()
    t = Transaction()
    t.create_collection(CID)
    t.create_collection(CID2)
    store.queue_transaction(t)
    return store


def _counters():
    return collections.Counter()


def _commit(store, log, counters=None, cid=CID):
    t = Transaction()
    log.stage(t, cid, counters)
    store.queue_transaction(t)
    return t


def _assert_same(store, log, cid=CID):
    got = PGLog.load(store, cid)
    assert got.entries == log.entries
    assert got.info.to_dict() == log.info.to_dict()
    assert got.missing == log.missing


def _entry_keys(store, cid=CID):
    omap = store.omap_get(cid, ObjectId(PGMETA_OID))
    return sorted(k for k in omap if k.startswith(K_ENTRY_PREFIX))


def _scan_newest(log, oid):
    for e in reversed(log.entries):
        if e["oid"] == oid:
            return e
    return None


class _Writer:
    """Appends entries at increasing versions to one log."""

    def __init__(self, log, rng, oids=12):
        self.log = log
        self.rng = rng
        self.oids = [f"o{i}" for i in range(oids)]

    def append(self, n=1, epoch=None):
        for _ in range(n):
            head = self.log.info.last_update
            version = (epoch or max(head[0], 1), head[1] + 1)
            self.log.append(make_entry(version, head,
                                       self.rng.choice(self.oids),
                                       self.rng.choice(
                                           ["modify", "modify",
                                            "delete"]),
                                       self.rng.randrange(1, 4096)))


def _divergent_auth(log, rng):
    """An authoritative log that shares a prefix of `log` and then
    moved on in a newer epoch (the local tail past it is divergent)."""
    keep = rng.randrange(0, len(log.entries) + 1)
    shared = [dict(e) for e in log.entries[:keep]]
    auth = PGLog(PGInfo(log_tail=log.info.log_tail), shared)
    auth.info.last_update = ev(shared[-1]["version"]) if shared \
        else log.info.log_tail
    epoch = log.info.last_update[0] + 1
    _Writer(auth, rng).append(rng.randrange(0, 5), epoch=epoch)
    return auth


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_load_equals_memory_after_every_commit(seed):
    """Seeded append / trim across the cap / merge-rewind / split /
    missing churn / moves between two collections, with some
    transactions failing and some staged in batches before any
    commits: after every committed batch whose last transaction
    committed, the stored log is the in-memory log."""
    rng = random.Random(seed)
    store = _store(FailingStore)
    log = PGLog()
    writer = _Writer(log, rng)
    counters = _counters()
    cap = rng.choice([3, 5, 8])
    cid = CID
    checked = 0
    for _step in range(120):
        batch = []
        for _ in range(rng.randrange(1, 4)):
            if rng.random() < 0.05:
                cid = CID2 if cid == CID else CID
            roll = rng.random()
            if roll < 0.08 and log.entries:
                auth = _divergent_auth(log, rng)
                missing = log.merge(auth.info, auth.entries)
                for oid, need in missing.items():
                    log.missing.setdefault(oid, need)
            elif roll < 0.12:
                half = rng.randrange(2)
                log.replace(
                    [e for e in log.entries
                     if int(e["oid"][1:]) % 2 == half],
                    {o: v for o, v in log.missing.items()
                     if int(o[1:]) % 2 == half})
            elif roll < 0.2:
                if log.missing and rng.random() < 0.5:
                    log.missing.pop(rng.choice(sorted(log.missing)))
                else:
                    log.missing[rng.choice(writer.oids)] = \
                        log.info.last_update
            else:
                writer.append(rng.randrange(1, 3))
                log.trim_to(cap)
            t = Transaction()
            log.stage(t, cid, counters)
            batch.append(t)
        fails = {id(t) for t in batch[:-1] if rng.random() < 0.3}
        last_fails = rng.random() < 0.15
        if last_fails:
            fails.add(id(batch[-1]))
        store.fail = fails
        results = store.submit_batch(batch)
        assert [r is not None for r in results] == \
            [id(t) in fails for t in batch]
        if not last_fails:
            _assert_same(store, log, cid)
            checked += 1
    assert checked >= 80
    assert counters["pglog_full_rewrites"] >= 1


@pytest.mark.parametrize("length", [10, 100])
def test_keys_per_stage_do_not_grow_with_the_log(length):
    """Steady writes at the cap: one new entry, one trimmed key and
    info per stage (3 keys), at 10 entries as at 100; no rewrite."""
    store = _store()
    log = PGLog()
    writer = _Writer(log, random.Random(length))
    for _ in range(length):
        writer.append()
        _commit(store, log)
    counters = _counters()
    writes = 40
    for _ in range(writes):
        writer.append()
        log.trim_to(length)
        _commit(store, log, counters)
    assert counters["pglog_stage_keys"] == 3 * writes
    assert counters["pglog_full_rewrites"] == 0
    assert len(_entry_keys(store)) == length
    _assert_same(store, log)


def test_stage_below_the_cap_writes_entry_and_info():
    store = _store()
    log = PGLog.load(store, CID)  # as an OSD starts a PG's log
    writer = _Writer(log, random.Random(7))
    counters = _counters()
    for _ in range(5):
        writer.append()
        log.trim_to(100)
        _commit(store, log, counters)
    assert counters["pglog_stage_keys"] == 2 * 5
    assert counters["pglog_full_rewrites"] == 0
    _assert_same(store, log)


def test_old_single_key_log_loads_and_is_rewritten():
    """A log in the older form (the whole log under `log`) loads as
    it was; the next stage rewrites it as entry keys, drops `log`,
    and counts one full rewrite."""
    store = _store()
    entries = [make_entry((1, v), (1, v - 1), f"o{v % 3}", "modify", v)
               for v in range(1, 8)]
    info = PGInfo(last_update=(1, 7), same_interval_since=1)
    t = Transaction()
    t.omap_setkeys(CID, ObjectId(PGMETA_OID), {
        K_INFO: json.dumps(info.to_dict()).encode(),
        K_LOG: json.dumps(entries).encode(),
        K_MISSING: json.dumps({"o1": [1, 7]}).encode()})
    store.queue_transaction(t)

    log = PGLog.load(store, CID)
    assert log.entries == entries
    assert log.missing == {"o1": (1, 7)}
    assert log.newest("o1") == entries[6]
    counters = _counters()
    _commit(store, log, counters)
    omap = store.omap_get(CID, ObjectId(PGMETA_OID))
    assert K_LOG not in omap
    assert _entry_keys(store) == [entry_key(e["version"])
                                  for e in entries]
    assert counters["pglog_full_rewrites"] == 1
    _assert_same(store, log)
    # and from then on it is appended to, not rewritten
    log.append(make_entry((1, 8), (1, 7), "o2", "modify"))
    _commit(store, log, counters)
    assert counters["pglog_full_rewrites"] == 1
    _assert_same(store, log)


def test_failed_transaction_entry_reaches_store_with_next_stage():
    """A sub-write whose transaction failed keeps its entry in the
    in-memory log; the next stage must write it, and a failed trim's
    removal is retried too."""
    store = _store(FailingStore)
    log = PGLog()
    writer = _Writer(log, random.Random(3))
    for _ in range(4):
        writer.append()
        _commit(store, log)

    writer.append()
    lost = log.entries[-1]
    log.trim_to(4)  # trims the oldest: its key removal is lost too
    t = Transaction()
    log.stage(t, CID)
    store.fail = {id(t)}
    with pytest.raises(IOError):
        store.queue_transaction(t)
    store.fail = set()
    assert entry_key(lost["version"]) not in _entry_keys(store)

    writer.append()
    log.trim_to(4)
    _commit(store, log)
    assert entry_key(lost["version"]) in _entry_keys(store)
    assert len(_entry_keys(store)) == 4
    _assert_same(store, log)


def test_stage_before_an_earlier_commit_carries_its_entries():
    """Two stages in flight before either commits (group commit): the
    second carries the first's entry, so the first failing loses
    nothing."""
    store = _store(FailingStore)
    log = PGLog()
    writer = _Writer(log, random.Random(5))
    writer.append()
    t1 = Transaction()
    log.stage(t1, CID)
    writer.append()
    t2 = Transaction()
    log.stage(t2, CID)
    store.fail = {id(t1)}
    results = store.submit_batch([t1, t2])
    assert results[0] is not None and results[1] is None
    _assert_same(store, log)


def test_trimmed_key_left_in_store_is_dropped_on_load():
    """A stored entry at or below the log tail is not part of the log:
    load leaves it out and the next stage removes its key."""
    store = _store()
    log = PGLog()
    writer = _Writer(log, random.Random(9))
    for _ in range(3):
        writer.append()
        _commit(store, log)
    stale = make_entry((0, 5), ZERO, "ghost", "modify")
    t = Transaction()
    t.omap_setkeys(CID, ObjectId(PGMETA_OID), {
        entry_key(stale["version"]): json.dumps(stale).encode()})
    store.queue_transaction(t)
    log.info.log_tail = (0, 9)
    _commit(store, log)

    again = PGLog.load(store, CID)
    assert again.entries == log.entries
    assert entry_key(stale["version"]) in _entry_keys(store)
    _commit(store, again)
    assert entry_key(stale["version"]) not in _entry_keys(store)


def test_split_child_replaces_what_its_collection_held():
    """A split replaces a log: the next stage removes every key the
    replacement dropped and rewrites the rest."""
    store = _store()
    log = PGLog()
    writer = _Writer(log, random.Random(11))
    for _ in range(10):
        writer.append()
        _commit(store, log)
    keep = [e for e in log.entries if e["oid"] in ("o1", "o2", "o3")]
    loaded = PGLog.load(store, CID)
    loaded.replace([dict(e) for e in keep], {})
    counters = _counters()
    _commit(store, loaded, counters)
    assert counters["pglog_full_rewrites"] == 1
    assert _entry_keys(store) == [entry_key(e["version"]) for e in keep]
    _assert_same(store, loaded)


def test_stage_into_another_collection_writes_the_log_whole():
    """An OSD whose shard position changed stages its cached log into
    the new shard's collection: the whole log lands there, with what
    that collection held from an earlier stay cleared, and both
    collections load as the log they were last given."""
    store = _store()
    log = PGLog.load(store, CID2)
    writer = _Writer(log, random.Random(17))
    for _ in range(3):
        writer.append()
        _commit(store, log, cid=CID2)
    # an earlier stay at CID2 left entries that were later rewound,
    # above the log's tail when it comes back
    log.replace(log.entries[:1], {})
    log.info.last_update = ev(log.entries[-1]["version"])
    t = Transaction()
    log.stage(t, CID)
    store.queue_transaction(t)
    stale_keys = _entry_keys(store, CID2)
    assert len(stale_keys) == 3

    for _ in range(12):
        writer.append(epoch=2)
        log.trim_to(20)
        _commit(store, log)
    _assert_same(store, log)
    at_cid = [dict(e) for e in log.entries]

    counters = _counters()
    _commit(store, log, counters, cid=CID2)
    assert counters["pglog_full_rewrites"] == 1
    assert _entry_keys(store, CID2) == [entry_key(e["version"])
                                        for e in log.entries]
    _assert_same(store, log, CID2)
    for _ in range(4):
        writer.append(epoch=2)
        log.trim_to(20)
        _commit(store, log, counters, cid=CID2)
    assert counters["pglog_full_rewrites"] == 1
    _assert_same(store, log, CID2)
    assert PGLog.load(store, CID).entries == at_cid

    _commit(store, log, counters)  # and back again
    assert counters["pglog_full_rewrites"] == 2
    _assert_same(store, log)


def test_merge_of_an_equal_log_is_not_a_rewrite():
    store = _store()
    log = PGLog()
    writer = _Writer(log, random.Random(13))
    for _ in range(6):
        writer.append()
    _commit(store, log)
    counters = _counters()
    assert log.merge(log.info, [dict(e) for e in log.entries]) == {}
    _commit(store, log, counters)
    assert counters["pglog_full_rewrites"] == 0
    assert counters["pglog_stage_keys"] == 1  # info alone
    _assert_same(store, log)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_oid_index_agrees_with_backward_scan(seed):
    """The oid -> newest entry index matches a backward scan of the
    log after appends, trims, merge/rewinds, replacements and loads."""
    rng = random.Random(seed)
    store = _store()
    log = PGLog()
    writer = _Writer(log, rng, oids=6)
    for step in range(200):
        roll = rng.random()
        if roll < 0.06 and log.entries:
            auth = _divergent_auth(log, rng)
            log.merge(auth.info, auth.entries)
        elif roll < 0.09:
            log.replace([e for e in log.entries if rng.random() < 0.7],
                        {})
        elif roll < 0.12:
            _commit(store, log)
            log = PGLog.load(store, CID)
            writer.log = log
        else:
            writer.append()
            log.trim_to(rng.choice([2, 4, 7]))
        for oid in writer.oids:
            assert log.newest(oid) is _scan_newest(log, oid), \
                (step, oid)
