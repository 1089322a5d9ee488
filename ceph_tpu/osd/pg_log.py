"""Per-PG op log: crash consistency + divergence repair.

Reference parity: PGLog (/root/reference/src/osd/PGLog.h) — the per-PG
replicated journal that lets a crashed/partitioned shard rejoin: the
primary elects the authoritative log (max last_update — GetLog,
PeeringState.h:249), peers merge it (`merge_log` PGLog.h:1247), entries
the authoritative log does not contain are divergent and rewound
(`rewind_divergent_log` PGLog.h:1241 — here: the touched object is
marked missing and recovered to the authoritative state), and objects
written past a peer's last_update form its missing set, driving
log-based recovery.  A peer whose last_update predates the log tail
cannot be caught up by log replay and needs backfill (whole-PG scan).

Design: entries are JSON-friendly dicts (they ride MPGLogMsg / sub-op
messages); the log and pg info persist in the pgmeta object's omap of
the shard's collection, committed in the SAME ObjectStore transaction as
the data mutation they journal — the store's transactional atomicity
gives the log its WAL semantics.

Persistence is incremental (`PGLog::_write_log_and_missing`): one omap
key per entry, named by its zero-padded eversion so key order is
version order; a stage writes the entries the store is not known to
hold and removes the keys of trimmed ones, so its cost does not grow
with the log.  "Known to hold" means confirmed by a committed
transaction's on_commit, never merely staged: a transaction that fails
leaves its entries to the next stage.  The bookkeeping belongs to one
collection.  A log that was replaced (merge, split), or is staged into
another collection than the one it describes (the OSD's shard moved),
is rewritten whole: its log keys are cleared and all written again.
Logs stored in the older single-key form (`log`) still load; the next
stage rewrites them.

eversion_t = (epoch, version), ordered lexicographically.
"""

from __future__ import annotations

import collections
import json
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

from ceph_tpu.os import ObjectId, Transaction

PGMETA_OID = "_pgmeta_"
K_INFO = "info"
K_LOG = "log"  # the older single-key form: the whole log in one value
K_MISSING = "missing"
K_ENTRY_PREFIX = "log."
# [K_LOG, K_LOG_END) holds every log key, of either form, and nothing
# else of the pgmeta omap (info, missing, hit sets)
K_LOG_END = "log/"

Ever = Tuple[int, int]


def ev(v) -> Ever:
    """Coerce a wire-form [epoch, version] to a comparable tuple."""
    return (int(v[0]), int(v[1]))


ZERO: Ever = (0, 0)


def entry_key(version) -> str:
    """The omap key of the entry at `version`: zero-padded, so the
    store's key order is version order (eversion_t::get_key_name)."""
    return f"{K_ENTRY_PREFIX}{int(version[0]):010d}.{int(version[1]):020d}"


def make_entry(version: Ever, prior: Ever, oid: str, op: str,
               size: int = 0) -> Dict[str, Any]:
    """op: 'modify' (incl. create) | 'delete'."""
    return {"version": list(version), "prior": list(prior),
            "oid": oid, "op": op, "size": size}


class PGInfo:
    """pg_info_t role: identity + log bounds of one shard's PG state."""

    def __init__(self, last_update: Ever = ZERO, log_tail: Ever = ZERO,
                 same_interval_since: int = 0, last_epoch_started: int = 0):
        self.last_update = last_update
        self.log_tail = log_tail
        self.same_interval_since = same_interval_since
        self.last_epoch_started = last_epoch_started

    def to_dict(self) -> Dict[str, Any]:
        return {"last_update": list(self.last_update),
                "log_tail": list(self.log_tail),
                "same_interval_since": self.same_interval_since,
                "last_epoch_started": self.last_epoch_started}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PGInfo":
        return cls(ev(d["last_update"]), ev(d["log_tail"]),
                   int(d.get("same_interval_since", 0)),
                   int(d.get("last_epoch_started", 0)))


class _Staged:
    """One stage awaiting its transaction's commit."""

    __slots__ = ("written", "removed", "missing")

    def __init__(self, written: List[str], removed: List[str],
                 missing: Optional[Dict[str, Ever]]):
        self.written = written
        self.removed = removed
        self.missing = missing


class PGLog:
    """Ordered entries (oldest first) + info, with merge/rewind."""

    def __init__(self, info: Optional[PGInfo] = None,
                 entries: Optional[List[Dict[str, Any]]] = None,
                 missing: Optional[Dict[str, Ever]] = None):
        self.info = info or PGInfo()
        self.entries: List[Dict[str, Any]] = entries or []
        # objects whose on-disk state lags the log head (pg_missing_t):
        # oid -> version needed ((0,0) = unknown, recover to auth state).
        # Persisted so a shard that crashes mid-recovery still knows what
        # it must not serve.
        self.missing: Dict[str, Ever] = missing or {}
        # oid -> its newest entry (IndexedLog's role): the sub-write
        # floor and scrub look an object up without scanning the log
        self._newest: Dict[str, Dict[str, Any]] = {}
        # Store bookkeeping, by entry key, for the collection `_cid`
        # (None: no collection yet).  `_unsaved`: entries the store is
        # not known to hold; every stage writes them until a commit
        # confirms one.  `_doomed`: keys the store may hold that the
        # log no longer wants; every stage removes them until a commit
        # confirms it.  `_since`: for each of those keys, the stage
        # count when it last became so; only a stage issued later
        # confirms it, so a commit of an older stage never settles a
        # key that changed since.  `_rewrite`: the stage count when
        # the log was replaced; until a later stage commits, every
        # stage clears the log keys and writes the log whole.
        self._cid: Optional[str] = None
        self._unsaved: Dict[str, Dict[str, Any]] = {}
        self._doomed: Set[str] = set()
        self._since: Dict[str, int] = {}
        self._saved_missing: Dict[str, Ever] = {}
        self._rewrite: Optional[int] = None
        self._seq = 0
        self._staged: Dict[int, _Staged] = {}
        # seqs whose transaction committed; on_commit may fire on the
        # store's commit thread, and a deque appends atomically
        self._committed: Deque[int] = collections.deque()
        # a log built in memory has no collection: its first stage
        # writes it whole
        self.replace(self.entries, self.missing)

    # -- append / trim -----------------------------------------------------

    def append(self, entry: Dict[str, Any]) -> None:
        version = ev(entry["version"])
        assert version > self.info.last_update, \
            f"log entry {version} <= head {self.info.last_update}"
        self.entries.append(entry)
        self.info.last_update = version
        self._newest[entry["oid"]] = entry
        key = entry_key(version)
        # a rewound version written again overwrites the old entry
        self._doomed.discard(key)
        self._unsaved[key] = entry
        self._since[key] = self._seq

    def trim_to(self, keep: int) -> None:
        """Keep at most `keep` entries; advances log_tail."""
        if len(self.entries) > keep:
            cut = self.entries[:len(self.entries) - keep]
            self.entries = self.entries[len(cut):]
            self.info.log_tail = ev(cut[-1]["version"])
            for e in cut:
                if self._newest.get(e["oid"]) is e:
                    del self._newest[e["oid"]]
                key = entry_key(e["version"])
                self._unsaved.pop(key, None)
                self._doomed.add(key)
                self._since[key] = self._seq

    def replace(self, entries: List[Dict[str, Any]],
                missing: Dict[str, Ever]) -> None:
        """Swap in another log (merge's rewind, PG split): the next
        stage clears the log keys and rewrites it whole."""
        self.entries = entries
        self.missing = missing
        self._index()
        self._unsaved = {entry_key(e["version"]): e for e in entries}
        self._doomed = set()
        self._since = dict.fromkeys(self._unsaved, self._seq)
        self._rewrite = self._seq

    def _index(self) -> None:
        self._newest = {e["oid"]: e for e in self.entries}

    # -- queries -----------------------------------------------------------

    def newest(self, oid: str) -> Optional[Dict[str, Any]]:
        """The newest entry for `oid`, or None if the log has none."""
        return self._newest.get(oid)

    def versions(self) -> Dict[Ever, Dict[str, Any]]:
        return {ev(e["version"]): e for e in self.entries}

    def objects_newer_than(self, bound: Ever) -> Dict[str, Ever]:
        """oid -> latest version, over entries with version > bound.
        `delete` entries count too (the peer must learn the delete)."""
        out: Dict[str, Ever] = {}
        for e in self.entries:
            if ev(e["version"]) > bound:
                out[e["oid"]] = ev(e["version"])
        return out

    # -- merge (merge_log + rewind_divergent_log) --------------------------

    def merge(self, auth_info: PGInfo,
              auth_entries: List[Dict[str, Any]]) -> Dict[str, Ever]:
        """Adopt the authoritative log; returns this shard's missing set
        {oid: version needed}.

        Divergence point = the newest local version that also appears in
        the authoritative log.  Local entries past it are divergent ->
        their objects are missing (to be recovered to auth state);
        authoritative entries past it are ops this shard never saw ->
        missing too.  If the local head predates the auth log tail, log
        replay can't catch up: every object in the auth log window is
        missing and the caller should treat the peer as backfill.
        """
        auth_versions = {ev(e["version"]) for e in auth_entries}
        missing: Dict[str, Ever] = {}

        # divergence point: newest local version the auth log also knows
        # (in its entries, or at/before its tail = in its trimmed past)
        common: Ever = ZERO
        divergent: List[Dict[str, Any]] = []
        if not self.entries:
            common = self.info.last_update
        else:
            for e in reversed(self.entries):
                version = ev(e["version"])
                if version in auth_versions or \
                        version <= auth_info.log_tail:
                    common = version
                    break
                divergent.append(e)
            # no break -> common stays ZERO: whole local log divergent

        for e in divergent:  # rewind_divergent_log
            missing[e["oid"]] = ZERO  # unknown good version yet

        # adopt auth entries newer than the divergence point
        for e in auth_entries:
            version = ev(e["version"])
            if version > common:
                missing[e["oid"]] = version

        # divergent objects with no auth entry: roll back to whatever the
        # auth primary holds now (recovery source resolves it); keep ZERO
        if auth_entries != self.entries:
            self.replace([dict(e) for e in auth_entries], self.missing)
        self.info.last_update = auth_info.last_update
        self.info.log_tail = auth_info.log_tail
        return missing

    # -- persistence -------------------------------------------------------

    def stage(self, t: Transaction, cid: str,
              counters: Optional[Dict[str, int]] = None) -> None:
        """Write what changed since the store's last confirmed state
        into the transaction (same txn as the data mutation it
        journals): unsaved entries, trimmed keys, info, and missing
        when it differs.  Staged into another collection than the one
        the bookkeeping describes, the log is written whole there.
        `counters` (the OSD's perf dump) gets `pglog_stage_keys` and
        `pglog_full_rewrites`."""
        self._settle()
        if cid != self._cid:
            # what `cid` holds is unknown: clear it and write it all
            self._cid = cid
            self.replace(self.entries, self.missing)
        meta = ObjectId(PGMETA_OID)
        rewrite = self._rewrite is not None
        if rewrite:
            t.omap_rmkeyrange(cid, meta, K_LOG, K_LOG_END)
        keys = {k: json.dumps(e).encode()
                for k, e in self._unsaved.items()}
        keys[K_INFO] = json.dumps(self.info.to_dict()).encode()
        missing = None
        if rewrite or self.missing != self._saved_missing or any(
                s.missing is not None for s in self._staged.values()):
            # a stage still in flight may land another missing set
            missing = dict(self.missing)
            keys[K_MISSING] = json.dumps(
                {k: list(v) for k, v in missing.items()}).encode()
        t.omap_setkeys(cid, meta, keys)
        removed = sorted(self._doomed)
        if removed:
            t.omap_rmkeys(cid, meta, removed)
        self._seq += 1
        self._staged[self._seq] = _Staged(
            list(self._unsaved), removed, missing)
        t.register_on_commit(
            lambda seq=self._seq: self._committed.append(seq))
        if counters is not None:
            counters["pglog_stage_keys"] += len(keys) + len(removed)
            counters["pglog_full_rewrites"] += int(rewrite)

    def _settle(self) -> None:
        """Fold committed stages into the store bookkeeping.  The
        commit lane is FIFO, so a stage older than a committed one
        that never confirmed has failed: its entries stay unsaved and
        its removals stay due."""
        while self._committed:
            seq = self._committed.popleft()
            done = self._staged.pop(seq, None)
            if done is None:
                continue
            for older in [s for s in self._staged if s < seq]:
                del self._staged[older]
            for key in done.written:
                if key in self._unsaved and self._since[key] < seq:
                    del self._unsaved[key]
                    del self._since[key]
            for key in done.removed:
                if key in self._doomed and self._since[key] < seq:
                    self._doomed.discard(key)
                    del self._since[key]
            if done.missing is not None:
                self._saved_missing = done.missing
            if self._rewrite is not None and self._rewrite < seq:
                self._rewrite = None

    @classmethod
    def load(cls, store, cid: str) -> "PGLog":
        try:
            omap = store.omap_get(cid, ObjectId(PGMETA_OID))
        except KeyError:
            omap = {}
        info = PGInfo.from_dict(json.loads(omap[K_INFO])) \
            if K_INFO in omap else PGInfo()
        missing = {k: ev(v) for k, v in json.loads(
            omap.get(K_MISSING, b"{}")).items()}
        log = cls(info, None, missing)
        log._cid = cid
        log._saved_missing = dict(missing)
        log._rewrite = None
        if K_LOG in omap:
            # the older single-key form: the next stage rewrites it
            log.replace(json.loads(omap[K_LOG]), log.missing)
            return log
        for key in sorted(k for k in omap
                          if k.startswith(K_ENTRY_PREFIX)):
            e = json.loads(omap[key])
            if ev(e["version"]) <= info.log_tail:
                # trimmed, yet still stored: the next stage removes it
                log._doomed.add(key)
                log._since[key] = 0
                continue
            log.entries.append(e)
        log._index()
        return log
