"""The store's pure state machine: keys, revisions, leases, watch matching.

Semantics are etcd-shaped because that is what the reference's control plane
is written against (python/edl/discovery/etcd_client.py:40-257):

- every mutation gets a monotonically increasing ``revision``;
- a key may be attached to a *lease*; when the lease expires (TTL seconds
  without keepalive) all its keys are deleted — this is the liveness
  primitive behind registration/heartbeat (reference register.py:120-129);
- ``put_if_absent`` is the put-if-key-absent transaction used for rank
  racing (reference etcd_client.py:172-197 ``set_server_not_exists``);
- prefix watches receive every event with revision > start point, enabling
  push-based membership diffing (reference watcher.py polls at 1 Hz; we
  push instead).

Networking-free so it can be unit-tested directly and reused verbatim by
alternative frontends.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

PUT = "put"
DELETE = "del"


@dataclass(frozen=True)
class Event:
    type: str  # PUT | DELETE
    key: str
    value: Optional[bytes]
    rev: int
    lease: int = 0

    def to_wire(self) -> dict:
        return {
            "t": self.type,
            "k": self.key,
            "v": self.value,
            "r": self.rev,
            "l": self.lease,
        }

    @staticmethod
    def from_wire(d: dict) -> "Event":
        return Event(d["t"], d["k"], d.get("v"), d["r"], d.get("l", 0))


@dataclass
class _KeyValue:
    value: bytes
    create_rev: int
    mod_rev: int
    lease: int  # 0 = no lease


@dataclass
class _Lease:
    id: int
    ttl: float
    deadline: float
    keys: Set[str]


class StoreState:
    """In-memory KV with revisions, leases and an event history ring.

    The history ring lets watchers resume from a past revision after a
    reconnect without a full re-read (bounded; a too-old resume point
    raises so the client knows to re-range).
    """

    HISTORY_LIMIT = 200_000

    def __init__(self, clock=time.monotonic) -> None:
        self._clock = clock
        self._rev = 0
        self._kvs: Dict[str, _KeyValue] = {}
        self._leases: Dict[int, _Lease] = {}
        self._next_lease = 1
        self._history: deque[Event] = deque(maxlen=self.HISTORY_LIMIT)
        self._first_hist_rev = 1  # revision of the oldest retained event
        # MVCC version chains: key -> append-only [(mod_rev, value, lease,
        # alive)] so reads can answer at a PAST revision (the released
        # horizon, a pinned snapshot rev). Each global revision adds
        # exactly one entry across all chains, so total retained versions
        # are bounded by the compaction span plus one live base per key.
        self._vers: Dict[str, List[Tuple[int, Optional[bytes], int, bool]]] = {}
        self._nvers = 0
        self._compact_rev = 0  # reads strictly below this raise (compacted)
        # fencing epoch: bumped (and persisted) whenever a standby
        # promotes itself; a response carrying a LOWER epoch than the
        # client has already seen identifies a stale, fenced-off primary
        self._epoch = 0

    # -- internals ---------------------------------------------------------

    def _next_rev(self) -> int:
        self._rev += 1
        return self._rev

    def _record(self, ev: Event) -> Event:
        if len(self._history) == self._history.maxlen:
            self._first_hist_rev = self._history[0].rev + 1
        self._history.append(ev)
        return ev

    def _note_version(
        self, key: str, rev: int, value: Optional[bytes], lease: int, alive: bool
    ) -> None:
        """Append one entry to a key's version chain. Guarded against
        replays (a journal applied twice must not fork the chain)."""
        chain = self._vers.get(key)
        if chain is None:
            chain = self._vers[key] = []
        if chain and chain[-1][0] >= rev:
            return
        chain.append((rev, value, lease, alive))
        self._nvers += 1

    @staticmethod
    def _version_at(
        chain: List[Tuple[int, Optional[bytes], int, bool]], rev: int
    ) -> Optional[Tuple[int, Optional[bytes], int, bool]]:
        """Newest chain entry with mod_rev <= rev (None if the key did
        not exist yet at ``rev``)."""
        lo, hi = 0, len(chain)
        while lo < hi:
            mid = (lo + hi) // 2
            if chain[mid][0] <= rev:
                lo = mid + 1
            else:
                hi = mid
        if lo == 0:
            return None
        return chain[lo - 1]

    def _attach_lease(self, key: str, lease: int) -> None:
        if lease:
            entry = self._leases.get(lease)
            if entry is None:
                raise KeyError("lease %d not found" % lease)
            entry.keys.add(key)

    def _detach_lease(self, key: str, lease: int) -> None:
        if lease and lease in self._leases:
            self._leases[lease].keys.discard(key)

    # -- KV operations -----------------------------------------------------

    @property
    def revision(self) -> int:
        return self._rev

    @property
    def epoch(self) -> int:
        return self._epoch

    def set_epoch(self, epoch: int) -> None:
        """Epochs only move forward (a promotion or a fence, never a rollback)."""
        self._epoch = max(self._epoch, int(epoch))

    @property
    def lease_count(self) -> int:
        return len(self._leases)

    def put(self, key: str, value: bytes, lease: int = 0) -> Event:
        if lease and lease not in self._leases:
            raise KeyError("lease %d not found" % lease)
        old = self._kvs.get(key)
        if old is not None and old.lease != lease:
            self._detach_lease(key, old.lease)
        self._attach_lease(key, lease)
        rev = self._next_rev()
        if old is None:
            self._kvs[key] = _KeyValue(value, rev, rev, lease)
        else:
            old.value, old.mod_rev, old.lease = value, rev, lease
        self._note_version(key, rev, value, lease, True)
        return self._record(Event(PUT, key, value, rev, lease))

    def put_if_absent(
        self, key: str, value: bytes, lease: int = 0
    ) -> Tuple[bool, Optional[Event], Optional[bytes]]:
        """Returns (created, event_if_created, existing_value_if_not)."""
        cur = self._kvs.get(key)
        if cur is not None:
            return False, None, cur.value
        return True, self.put(key, value, lease), None

    def cas(
        self, key: str, expect_mod_rev: int, value: bytes, lease: int = 0
    ) -> Tuple[bool, Optional[Event]]:
        """Compare-and-swap on mod revision; ``expect_mod_rev=0`` = absent."""
        cur = self._kvs.get(key)
        cur_rev = cur.mod_rev if cur is not None else 0
        if cur_rev != expect_mod_rev:
            return False, None
        return True, self.put(key, value, lease)

    def get(
        self, key: str, rev: Optional[int] = None
    ) -> Optional[Tuple[bytes, int, int]]:
        """Returns (value, mod_rev, lease) or None.

        ``rev`` pins the read to a past revision (MVCC): the answer is the
        key's state as of that revision. ``rev >= revision`` (or None) is
        the fast path straight off the live map. A pin below the
        compaction floor raises ``ValueError``.
        """
        if rev is None or rev >= self._rev:
            kv = self._kvs.get(key)
            if kv is None:
                return None
            return kv.value, kv.mod_rev, kv.lease
        self._check_compacted(rev)
        chain = self._vers.get(key)
        ver = self._version_at(chain, rev) if chain else None
        if ver is None or not ver[3]:
            return None
        return ver[1], ver[0], ver[2]

    def range(
        self, prefix: str, rev: Optional[int] = None
    ) -> Tuple[List[Tuple[str, bytes, int, int]], int]:
        """All (key, value, mod_rev, lease) under prefix + the revision
        the answer is AS OF (current, or the ``rev`` pin clamped to
        current). A pinned range is snapshot-coherent: every row reflects
        the same revision, regardless of writes racing the scan."""
        if rev is None or rev >= self._rev:
            items = [
                (k, kv.value, kv.mod_rev, kv.lease)
                for k, kv in sorted(self._kvs.items())
                if k.startswith(prefix)
            ]
            return items, self._rev
        self._check_compacted(rev)
        items = []
        for k in sorted(self._vers):
            if not k.startswith(prefix):
                continue
            ver = self._version_at(self._vers[k], rev)
            if ver is not None and ver[3]:
                items.append((k, ver[1], ver[0], ver[2]))
        return items, rev

    def delete(self, key: str) -> Optional[Event]:
        kv = self._kvs.pop(key, None)
        if kv is None:
            return None
        self._detach_lease(key, kv.lease)
        rev = self._next_rev()
        self._note_version(key, rev, None, 0, False)
        return self._record(Event(DELETE, key, None, rev))

    def delete_range(self, prefix: str) -> List[Event]:
        keys = [k for k in self._kvs if k.startswith(prefix)]
        return [ev for k in keys if (ev := self.delete(k)) is not None]

    # -- MVCC version chains -----------------------------------------------

    @property
    def compact_rev(self) -> int:
        """Oldest revision versioned reads can still answer at."""
        return self._compact_rev

    @property
    def version_count(self) -> int:
        """Retained MVCC versions across all chains (gauge feed)."""
        return self._nvers

    def _check_compacted(self, rev: int) -> None:
        if rev < self._compact_rev:
            raise ValueError(
                "revision %d compacted (oldest readable: %d)"
                % (rev, self._compact_rev)
            )

    def compact(self, horizon: int) -> int:
        """Drop versions no read will ever need again: keep everything
        newer than ``horizon`` plus, per key, the newest alive version
        at-or-below it (the base a read AT the horizon resolves to;
        a tombstone base is droppable — absent and compacted-away read
        the same). Returns how many versions were dropped. The horizon
        never regresses."""
        if horizon <= self._compact_rev:
            return 0
        horizon = min(horizon, self._rev)
        dropped = 0
        for key in list(self._vers):
            chain = self._vers[key]
            lo, hi = 0, len(chain)
            while lo < hi:  # first entry with mod_rev > horizon
                mid = (lo + hi) // 2
                if chain[mid][0] <= horizon:
                    lo = mid + 1
                else:
                    hi = mid
            keep_base = lo > 0 and chain[lo - 1][3]
            start = lo - 1 if keep_base else lo
            if start <= 0:
                continue
            dropped += start
            self._nvers -= start
            if start == len(chain):
                del self._vers[key]
            else:
                self._vers[key] = chain[start:]
        self._compact_rev = horizon
        return dropped

    # -- leases ------------------------------------------------------------

    def lease_grant(self, ttl: float) -> int:
        lease_id = self._next_lease
        self._next_lease += 1
        self._leases[lease_id] = _Lease(
            lease_id, ttl, self._clock() + ttl, set()
        )
        return lease_id

    def lease_keepalive(self, lease_id: int) -> bool:
        entry = self._leases.get(lease_id)
        if entry is None:
            return False
        entry.deadline = self._clock() + entry.ttl
        return True

    def lease_revoke(self, lease_id: int) -> List[Event]:
        entry = self._leases.pop(lease_id, None)
        if entry is None:
            return []
        return [
            ev for k in sorted(entry.keys) if (ev := self.delete(k)) is not None
        ]

    def expire_leases(self) -> List[Event]:
        """Delete keys of every lease whose deadline passed. Call regularly."""
        return self.expire_leases_with_ids()[0]

    def expire_leases_with_ids(self) -> Tuple[List[Event], List[int]]:
        """Like :meth:`expire_leases` but also reports WHICH leases died —
        durability needs the revocations journaled, not just the deletes
        (replaying only the deletes would resurrect the lease with a fresh
        TTL and let a partitioned owner keep heartbeating a registration
        the cluster already saw expire)."""
        now = self._clock()
        expired = [l.id for l in self._leases.values() if l.deadline <= now]
        events: List[Event] = []
        for lease_id in expired:
            events.extend(self.lease_revoke(lease_id))
        return events, expired

    def next_lease_deadline(self) -> Optional[float]:
        if not self._leases:
            return None
        return min(l.deadline for l in self._leases.values())

    def reset_lease_deadlines(self) -> int:
        """Give every lease a fresh ``now + ttl`` window; returns how many
        were reset. Used when a store that cannot know the keepalive
        history takes over liveness duty (recovery restart, standby
        promotion) — expiring immediately would kill every live
        registration at once."""
        now = self._clock()
        for lease in self._leases.values():
            lease.deadline = now + lease.ttl
        return len(self._leases)

    def extend_lease_deadlines(self, seconds: float) -> int:
        """Push every lease's deadline out by ``seconds``; returns how many
        moved. For a server that was itself not running for that long: no
        keepalive could have reached it meanwhile, so the silence says
        nothing about the owners."""
        for lease in self._leases.values():
            lease.deadline += seconds
        return len(self._leases)

    # -- durability (snapshot + journal replay) ----------------------------
    #
    # The reference survives control-plane restarts because etcd is an
    # external disk-persistent daemon (reference scripts/download_etcd.sh;
    # clients ride a bounce via the ``_handle_errors`` reconnect decorator,
    # etcd_client.py:40-50). The in-tree store earns the same property with
    # the C++ master's Save/Load pattern (native/master): full-state
    # snapshots plus a journal of every mutation since, replayed on boot.

    def to_snapshot(self) -> dict:
        """Full durable state. Lease deadlines are stored as TTLs — on
        restore every lease gets a fresh ``now + ttl`` grace window (the
        store can't know how long it was down; expiring immediately would
        kill every live registration at once)."""
        return {
            "rev": self._rev,
            "epoch": self._epoch,
            "next_lease": self._next_lease,
            "kvs": [
                [k, kv.value, kv.create_rev, kv.mod_rev, kv.lease]
                for k, kv in self._kvs.items()
            ],
            "leases": [[l.id, l.ttl] for l in self._leases.values()],
        }

    def load_snapshot(self, snap: dict) -> None:
        now = self._clock()
        self._rev = snap["rev"]
        self._epoch = int(snap.get("epoch", 0))  # pre-HA snapshots: epoch 0
        self._next_lease = snap["next_lease"]
        self._leases = {
            lid: _Lease(lid, ttl, now + ttl, set())
            for lid, ttl in snap["leases"]
        }
        self._kvs = {}
        self._vers = {}
        self._nvers = 0
        for k, value, create_rev, mod_rev, lease in snap["kvs"]:
            self._kvs[k] = _KeyValue(value, create_rev, mod_rev, lease)
            self._note_version(k, mod_rev, value, lease, True)
            if lease in self._leases:
                self._leases[lease].keys.add(k)
        # a snapshot carries only the live map: versions older than it
        # are gone, so versioned reads below the snapshot rev are
        # compacted by construction (journal replay rebuilds the suffix)
        self._compact_rev = self._rev
        self._mark_history_lost()

    def _mark_history_lost(self) -> None:
        """After a restore the event history is gone: any watch resuming
        from an older revision must get a compaction error (the client
        then re-ranges and resyncs)."""
        self._history.clear()
        self._first_hist_rev = self._rev + 1

    def apply_journal(self, entry: dict, record: bool = False) -> None:
        """Replay one journal entry. Events carry their ORIGINAL revisions
        so restored mod_revs equal what clients observed (a CAS taken
        before the restart must still match after it).

        ``record=True`` also appends events to the watch-history ring —
        the live-replication apply path, where a promoted standby must be
        able to resume client watches from pre-failover revisions (disk
        replay keeps ``record=False``: that history died with the
        process, and resuming watches must resync).
        """
        op = entry["op"]
        if op == "grant":
            lid, ttl = entry["id"], entry["ttl"]
            self._leases[lid] = _Lease(lid, ttl, self._clock() + ttl, set())
            self._next_lease = max(self._next_lease, lid + 1)
        elif op == "revoke":
            self._leases.pop(entry["id"], None)
        elif op == "epoch":
            self.set_epoch(entry["e"])
        elif op == "ev":
            ev = Event.from_wire(entry)
            self._rev = max(self._rev, ev.rev)
            if record:
                self._record(ev)
            if ev.type == PUT:
                old = self._kvs.get(ev.key)
                if old is not None and old.lease != ev.lease:
                    self._detach_lease(ev.key, old.lease)
                if ev.lease in self._leases:
                    self._leases[ev.lease].keys.add(ev.key)
                if old is None:
                    self._kvs[ev.key] = _KeyValue(ev.value, ev.rev, ev.rev, ev.lease)
                else:
                    old.value, old.mod_rev, old.lease = ev.value, ev.rev, ev.lease
                self._note_version(ev.key, ev.rev, ev.value, ev.lease, True)
            elif ev.type == DELETE:
                kv = self._kvs.pop(ev.key, None)
                if kv is not None:
                    self._detach_lease(ev.key, kv.lease)
                self._note_version(ev.key, ev.rev, None, 0, False)
        else:
            raise ValueError("unknown journal op %r" % op)

    # -- watch support -----------------------------------------------------

    def history_since(self, rev: int, prefix: str) -> List[Event]:
        """Events with revision > rev matching prefix.

        Raises ``ValueError`` if the history ring no longer covers ``rev``
        (client must re-range and restart the watch from the fresh revision).
        """
        if rev + 1 < self._first_hist_rev:
            raise ValueError(
                "revision %d compacted (oldest retained: %d)"
                % (rev, self._first_hist_rev)
            )
        return [
            ev for ev in self._history if ev.rev > rev and ev.key.startswith(prefix)
        ]
