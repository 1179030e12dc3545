"""Event-loop TCP server exposing :class:`StoreState` over the wire protocol.

Single-threaded, selector-driven (the shape of the reference's epoll balance
server, python/edl/distill/redis/balance_server.py:39-216, applied to the
coordination store): every connection is nonblocking, frames are decoded
incrementally, watch events are pushed as server-initiated frames.

Run standalone as ``python -m edl_tpu.store.server --port 2379`` (the role
``scripts/download_etcd.sh`` + an external etcd daemon play for the
reference), or embedded in-process via ``StoreServer(port=0).start()`` —
the launcher embeds one in the leader pod.

Wire methods (see rpc/wire.py for framing):
  put(k, v, l?) / put_absent / cas(k, er, v, l?) / get(k) / range(p) /
  del(k) / del_range(p) / lease_grant(ttl) / lease_keepalive(l) /
  lease_revoke(l) / watch(p, r?) / unwatch(w) / ping / state /
  repl_sync(e, ep, prio) / repl_status / repl_fence(e)

Control-plane HA (see DESIGN.md "Control-plane HA"): ``follow=`` turns a
server into a **warm standby** — it bootstraps from the primary's
streamed snapshot (``repl_sync``), tails journal entries live (``rl``
push frames, replication lag exported as gauges), and on primary death
promotes itself: bump the persisted fencing epoch, reset lease clocks,
take slot 0 in the ``/store/endpoints/`` keyspace, and fence every other
known endpoint so a resurrected stale primary refuses service.
"""

from __future__ import annotations

import argparse
import os
import selectors
import socket
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple, Union

from edl_tpu.chaos.plane import fault_point as _fault_point
from edl_tpu.obs import http as obs_http
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.rpc.wire import (
    TC_FIELD,
    FrameReader,
    WireError,
    pack_frame,
    server_span,
)
from edl_tpu.store import replica as replica_mod
from edl_tpu.store.kv import Event, StoreState
from edl_tpu.utils.exceptions import (
    EdlCompactedError,
    EdlFencedError,
    EdlNotPrimaryError,
    EdlStoreError,
    serialize_exception,
)
from edl_tpu.utils.log import get_logger

logger = get_logger("store.server")

_FP_DISPATCH = _fault_point(
    "store.server.dispatch",
    "one store RPC server-side: delay (slow tail) or drop (conn reset)",
)
_FP_WAL = _fault_point(
    "store.server.wal", "journal append: delay (slow disk) before fsync"
)
_FP_REPL_SYNC = _fault_point(
    "store.replication.sync",
    "standby bootstrap dial: delay or drop (primary looks unreachable)",
)
_FP_REPL_STREAM = _fault_point(
    "store.replication.stream",
    "one replicated journal batch primary->standby: delay or drop "
    "(the standby sees a dead link and re-syncs)",
)

_LEASE_SWEEP_INTERVAL = 0.2
# the serve loop woke this much later than it asked to: the process (or the
# whole host) was not running. Measured on a one-chip v5e VM: every TPU
# runtime initialisation — the launcher's device probe, each worker's
# backend start — froze ALL processes for 5-7 s, and a launcher-embedded
# store woke, swept, and expired its own launcher's 10 s leases before the
# equally frozen keepalive thread could speak (worker SIGKILLed, restage).
_STALL_FORGIVEN_ABOVE = 1.0
_COMPACT_EVERY = 10_000  # journal entries between snapshots
# semi-sync replication: how long a client ack may be held waiting for
# every live standby to apply+journal the write before the primary
# degrades that ONE commit to async (metered + alertable). <= 0 turns
# semi-sync off entirely (the pre-shard async behavior).
_REPL_SYNC_TIMEOUT = float(os.environ.get("EDL_STORE_REPL_SYNC_TIMEOUT", "0.5"))
# max replica staleness: with a replica_dir, compaction (and thus the
# replicated snapshot) is also triggered on a timer
_REPLICA_INTERVAL = float(os.environ.get("EDL_STORE_REPLICA_INTERVAL", "30"))
_REPL_HEARTBEAT = 0.25  # primary -> standby keepalive (also carries lag data)
_REPL_DIAL_INTERVAL = 0.25  # min pause between standby reconnect attempts
_FENCE_INTERVAL = 1.0  # promoted primary's fence-campaign pass interval

# the only methods a standby (or a fenced primary, minus repl_sync)
# answers: liveness probes and the replication control plane
_STANDBY_OK = ("ping", "state", "repl_status", "repl_fence")


class _Conn:
    __slots__ = (
        "sock", "reader", "out", "watches", "addr", "closed", "repl",
        "repl_tx", "repl_ack",
    )

    def __init__(self, sock: socket.socket, addr) -> None:
        self.sock = sock
        self.reader = FrameReader()
        self.out = bytearray()
        # wid -> (prefix, high-water revision): fan-out only delivers
        # events NEWER than the registration revision — the backlog push
        # already covered everything at-or-below it, so a watch
        # registered while a semi-sync commit is still held can never
        # see that commit's events twice
        self.watches: Dict[int, Tuple[str, int]] = {}
        self.addr = addr
        self.closed = False
        self.repl = False  # a replication subscriber (a standby's link)
        # async-replication loss-window accounting: cumulative journal
        # bytes streamed to this subscriber, and the highest cumulative
        # count it has echoed back (repl_ack frames)
        self.repl_tx = 0
        self.repl_ack = 0


class _SyncWait:
    """One semi-sync GROUP of commits held open: the client responses
    (and the watch fan-out of their events) release only once every
    target standby has echoed a ``repl_ack`` covering the batch — or
    the bounded degrade deadline passes. Waits release strictly FIFO so
    watchers observe events in revision order."""

    __slots__ = ("completions", "first_rev", "targets", "deadline")

    def __init__(self, completions, first_rev, targets, deadline) -> None:
        # [(conn|None, resp|None, events)] — conn None for
        # server-initiated commits (lease sweeps, endpoint publication)
        self.completions = completions
        self.first_rev = first_rev  # lowest event revision held here
        self.targets = targets  # [(subscriber _Conn, cumulative tx target)]
        self.deadline = deadline


class StoreServer:
    """``data_dir`` turns on durability (≙ the external etcd daemon's disk
    state in the reference): state is recovered from ``snapshot.bin`` +
    ``wal.bin`` at startup, every mutation is journaled (flush+fsync — the
    control plane is low-rate), and the journal is compacted into a fresh
    snapshot every ``_COMPACT_EVERY`` entries and on clean stop. A store
    killed -9 and restarted on the same ``data_dir`` loses at most nothing:
    clients reconnect, watches resume from their last revision (older
    resume points get a compaction error and resync), leases restart with
    a full fresh TTL (the store can't know how long it was down)."""

    def __init__(
        self,
        host: str = "0.0.0.0",
        port: int = 0,
        data_dir: Optional[str] = None,
        replica_dir: Optional[str] = None,
        follow: Union[str, Sequence[str], None] = None,
        priority: int = 1,
        failover_grace: float = 2.0,
        advertise: Optional[str] = None,
        repl_sync_timeout: Optional[float] = None,
        name: str = "store",
    ) -> None:
        from edl_tpu.chaos.plane import arm_from_env

        arm_from_env("store")  # no-op without EDL_CHAOS in the env
        self._host = host
        self._state = StoreState()
        self._data_dir = data_dir
        # ``name`` labels this server's RPC histograms — a sharded
        # deployment names each shard (store-0, store-1, ...) so the
        # trace plane's edl_rpc_server_seconds attributes tail latency
        # per shard, not per blurred fleet
        self.name = name
        # semi-sync replication (DESIGN.md "Sharded control plane"):
        # with a positive timeout, a mutation's ack is HELD until every
        # live replication subscriber has applied+journaled it (its
        # repl_ack covers the batch) — the async loss window the
        # edl_store_repl_unacked_bytes gauge measures drains to zero
        # before the client hears "ok". The bounded escape hatch
        # degrades one commit to async after the timeout, metered.
        self._repl_sync_timeout = (
            _REPL_SYNC_TIMEOUT if repl_sync_timeout is None
            else float(repl_sync_timeout)
        )
        self._sync_q: deque = deque()  # FIFO of held _SyncWait batches
        self._sync_last_warn = 0.0
        # group-commit pass buffer: (conn, resp, events, entries) of
        # every mutation dispatched in the current event-loop pass,
        # journaled+replicated+released together by _flush_commits().
        # EDL_STORE_GROUP_COMMIT=0 restores the per-write fsync of the
        # pre-shard store (the store_bench --baseline lane; ~5x slower
        # under pipelined write load on the CPU rig)
        self._txn_buf: List[tuple] = []
        self._group_commit = (
            os.environ.get("EDL_STORE_GROUP_COMMIT", "1") != "0"
        )
        # MVCC released-revision reads (DESIGN.md "Consistency model"):
        # get/range answer from the last RELEASED revision by default, so
        # a reader can never observe a commit still held in the semi-sync
        # window (it could die with this primary). EDL_STORE_MVCC=0
        # restores the pre-MVCC applied-state reads — the chaos plane's
        # red drill uses it to reproduce the stale-read anomaly.
        self._mvcc = os.environ.get("EDL_STORE_MVCC", "1") != "0"
        # how many revisions behind the released horizon version chains
        # retain — the budget for pinned snapshot reads and watch resume
        self._mvcc_retain = max(
            1, int(os.environ.get("EDL_STORE_MVCC_RETAIN", "4096"))
        )
        self._mvcc_last_compact = 0.0
        # standby read serving: a standby answers get/range/watch at its
        # applied (= released: it holds no commit queues) revision when
        # the client opted in ("rm": "s"), refusing — so the client falls
        # through to the primary — once its replication lag exceeds this
        self._standby_max_lag = max(
            0, int(os.environ.get("EDL_STORE_STANDBY_MAX_LAG", "1024"))
        )
        self._standby_reads_n = 0  # cumulative, exposed via repl_status
        # -- HA role (see module docstring) --------------------------------
        # ``follow`` makes this server a warm standby of the listed
        # primary endpoint(s); ``priority`` orders promotion among
        # standbys (1 = first in line); ``failover_grace`` is how long the
        # replication link must stay dead before promotion is considered.
        self._follow = replica_mod.parse_endpoints(follow)
        self.role = "standby" if self._follow else "primary"
        self.priority = 0 if self.role == "primary" else max(1, int(priority))
        self._failover_grace = max(0.1, float(failover_grace))
        self._advertise = advertise  # resolved after the bind (needs port)
        self._fenced_by: Optional[int] = None
        self._crash = False  # kill(): skip the clean-stop compaction
        self._repl_sock: Optional[socket.socket] = None
        self._repl_reader: Optional[FrameReader] = None
        self._follow_i = 0
        self._has_state = False  # a standby may only promote WITH state
        self._repl_down_since = time.monotonic()
        self._repl_last_attempt = 0.0
        self._repl_last_contact = 0.0
        self._repl_last_hb = 0.0
        # the fence-campaign thread and the serve loop race only toward
        # higher epochs; a stale read just delays fencing one tick
        self._primary_epoch = 0  # edl: lock-free(GIL-atomic int, raised monotonically via max)
        self._primary_rev = 0
        # replicated entries applied to memory but not yet journaled: the
        # standby defers its WAL fsync to the ACK boundary (a per-frame
        # fsync would stall standby-served reads while releasing nothing
        # earlier — acks only ride the primary's ~0.25s heartbeat stamps)
        self._apply_buf: List[dict] = []
        self._fence_thread: Optional[threading.Thread] = None
        # Store-HOST loss answer (the one availability asymmetry vs the
        # reference's replicable etcd): every compaction also lands the
        # snapshot in ``replica_dir`` — point it at shared storage (the
        # job's ckpt volume, a PVC) and a replacement store on a FRESH
        # host seeds itself from the replica when its own data_dir is
        # empty. Time-based compaction (below) bounds replica staleness.
        if replica_dir and not data_dir:
            raise ValueError(
                "replica_dir requires data_dir: snapshots are produced by "
                "the durability layer (an in-memory store has nothing to "
                "replicate)"
            )
        self._replica_dir = replica_dir
        self._last_compact = time.monotonic()
        self._wal_file = None
        self._wal_count = 0
        self._sel = selectors.DefaultSelector()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(128)
        self._listener.setblocking(False)
        self.port = self._listener.getsockname()[1]
        # observability plane: request/fanout counters + live-state
        # gauges, scraped via /metrics when EDL_OBS_PORT opts the
        # process in (obs is a process-level plane; a replacement store
        # in the same process reuses the mounted endpoint). Created
        # before recovery — _recover() compacts, which counts — and the
        # gauges' referents (_conns) before the mount, so a scrape during
        # a long WAL replay sees a sane recovering store.
        self._conns: Dict[socket.socket, _Conn] = {}
        self._m_requests = obs_metrics.counter(
            "edl_store_requests_total", "store RPCs dispatched, by method"
        )
        self._m_fanout = obs_metrics.counter(
            "edl_store_watch_events_total", "watch events pushed to clients"
        )
        self._m_compactions = obs_metrics.counter(
            "edl_store_compactions_total", "journal compactions (snapshots written)"
        )
        self._m_failovers = obs_metrics.counter(
            "edl_store_failovers_total", "standby promotions to primary"
        )
        self._m_lease_resets = obs_metrics.counter(
            "edl_store_lease_resets_total",
            "leases restarted with a fresh TTL (recovery or promotion), by cause",
        )
        self._m_fenced = obs_metrics.counter(
            "edl_store_fenced_total",
            "times this store fenced itself on seeing a higher epoch",
        )
        self._m_sync_degraded = obs_metrics.counter(
            "edl_store_repl_sync_degraded_total",
            "semi-sync commits degraded to async (escape hatch engaged), "
            "by cause: timeout (standby too slow past "
            "EDL_STORE_REPL_SYNC_TIMEOUT) or subscriber_lost (the standby "
            "link died before acking)",
        )
        self._m_standby_reads = obs_metrics.counter(
            "edl_store_standby_reads_total",
            "reads (get/range/watch registrations) this standby served "
            "from its applied released revision instead of the primary",
        )
        self._obs_gauges = obs_metrics.bind_gauges((
            ("edl_store_connections_open", "live client connections",
             lambda: len(self._conns)),
            ("edl_store_standby_lag_revs",
             "revisions this standby's applied state trails the primary "
             "by — the staleness bound on reads it serves (reads are "
             "refused past EDL_STORE_STANDBY_MAX_LAG)",
             lambda: self._repl_lag_entries()),
            ("edl_store_mvcc_versions",
             "MVCC versions retained across all per-key chains "
             "(compacted past the released horizon minus "
             "EDL_STORE_MVCC_RETAIN)",
             lambda: self._state.version_count),
            ("edl_store_revision_seq", "current store revision",
             lambda: self._state.revision),
            ("edl_store_epoch_seq", "current fencing epoch",
             lambda: self._state.epoch),
            ("edl_store_replication_lag_entries",
             "journal entries this standby trails its primary by",
             lambda: self._repl_lag_entries()),
            ("edl_store_replication_lag_seconds",
             "seconds since this standby last heard from its primary",
             lambda: self._repl_lag_seconds()),
            ("edl_store_repl_unacked_bytes",
             "journal bytes streamed to standbys but not yet standby-"
             "acked: the async-replication loss window a primary death "
             "can lose (ROADMAP item 2's semi-sync fix is judged "
             "against this)",
             lambda: self._repl_unacked_bytes()),
        ))
        self._health_fn = lambda: {
            "revision": self._state.revision,
            "conns": len(self._conns),
            "store_port": self.port,
            "role": self.role,
            "epoch": self._state.epoch,
            "fenced": self._fenced_by is not None,
        }
        self._obs = obs_http.start_from_env("store", health_fn=self._health_fn)
        if data_dir:
            # AFTER the bind on purpose: a losing "first pod on the host
            # wins" contender must fail on EADDRINUSE before it can touch
            # (compact, truncate) the live leader's snapshot/WAL. Recovery
            # faults are re-raised as RuntimeError so bind-contention
            # handlers (except OSError) never mistake them for a busy port.
            try:
                os.makedirs(data_dir, exist_ok=True)
                self._snap_path = os.path.join(data_dir, "snapshot.bin")
                self._wal_path = os.path.join(data_dir, "wal.bin")
                self._recover()
            except OSError as exc:
                self._listener.close()
                self._sel.close()
                raise RuntimeError(
                    "store data_dir %s unusable: %s" % (data_dir, exc)
                ) from exc
        self._sel.register(self._listener, selectors.EVENT_READ, None)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # wake pipe so stop() interrupts a sleeping select
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, "wake")
        if self._advertise is None:
            self._advertise = self.endpoint
        if self.role == "primary":
            # membership slot 0: clients refresh their ordered endpoint
            # list from here; standbys register via their repl_sync
            self._has_state = True
            self._fence_self_if_a_peer_is_newer()
            self._publish_endpoint(0, self._advertise)
        else:
            # a restarted standby recovering real local state may promote
            # even if it can never re-sync (the primary died with it); a
            # blank standby must first bootstrap — promoting an empty
            # store would trade an outage for data loss
            self._has_state = self._state.revision > 0

    @property
    def endpoint(self) -> str:
        return "127.0.0.1:%d" % self.port

    # -- durability --------------------------------------------------------

    def _recover(self) -> None:
        import msgpack

        if (
            not os.path.exists(self._snap_path)
            and not os.path.exists(self._wal_path)
            and self._replica_dir
            and os.path.exists(os.path.join(self._replica_dir, "snapshot.bin"))
        ):
            # fresh host, replicated state available: seed from the
            # replica (the restore-on-new-host procedure — staleness is
            # bounded by the compaction interval; leases restart fresh
            # and watch resumes past the jump resync, both by design).
            # Copy-then-rename: a crash mid-seed must not leave a torn
            # snapshot.bin that the next boot mistakes for local state.
            import shutil

            seed_tmp = "%s.seed.%d.tmp" % (self._snap_path, os.getpid())
            shutil.copyfile(
                os.path.join(self._replica_dir, "snapshot.bin"), seed_tmp
            )
            os.replace(seed_tmp, self._snap_path)
            logger.warning(
                "store seeded from replica %s (fresh data_dir %s)",
                self._replica_dir, self._data_dir,
            )
        if os.path.exists(self._snap_path):
            try:
                with open(self._snap_path, "rb") as f:
                    self._state.load_snapshot(
                        msgpack.unpackb(f.read(), raw=False)
                    )
            except Exception as exc:
                # A torn snapshot (e.g. a non-atomic replica filesystem
                # caught mid-replace) must not crash-loop the store: set
                # it aside and continue from whatever the WAL salvages —
                # a degraded recovery beats a control plane that can
                # never come back.
                corrupt = self._snap_path + ".corrupt"
                logger.error(
                    "snapshot %s unreadable (%s); moving to %s and "
                    "recovering from the journal alone",
                    self._snap_path, exc, corrupt,
                )
                try:
                    os.replace(self._snap_path, corrupt)
                except OSError:
                    pass
                self._state = StoreState()
        replayed = 0
        if os.path.exists(self._wal_path):
            with open(self._wal_path, "rb") as f:
                data = f.read()
            for entry in self._salvage_wal(data):
                self._state.apply_journal(entry)
                replayed += 1
        # the event history did not survive: watches resuming from any
        # pre-restart revision must resync
        self._state._mark_history_lost()
        if replayed or os.path.exists(self._snap_path):
            logger.info(
                "store recovered from %s: rev=%d, epoch=%d, %d wal entr%s "
                "replayed",
                self._data_dir, self._state.revision, self._state.epoch,
                replayed, "y" if replayed == 1 else "ies",
            )
        # recovery restarted every lease with a fresh TTL (the store
        # can't know how long it was down); say so OBSERVABLY — the chaos
        # downtime-attribution invariant reads this instead of inferring
        # lease-clock resets from expiry timing
        if self._state.lease_count:
            self._note_lease_resets(self._state.lease_count, "recovery")
        self._compact()

    @staticmethod
    def _salvage_wal(data: bytes):
        """Decode journal frames, tolerating a torn tail (crash mid-append:
        complete frames before it are all recoverable)."""
        reader = FrameReader(fault=False)  # disk replay, not network rx
        try:
            yield from reader.feed(data)
        except WireError as exc:
            logger.warning("wal tail unreadable (%s); recovered prefix", exc)

    def _compact(self) -> None:
        """Snapshot current state atomically, then truncate the journal.
        With a ``replica_dir``, the fresh snapshot is also copied there
        (best-effort: replica faults degrade availability of the
        RECOVERY path, never the live store)."""
        import msgpack

        blob = msgpack.packb(self._state.to_snapshot(), use_bin_type=True)
        tmp = self._snap_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._snap_path)
        if self._replica_dir:
            try:
                os.makedirs(self._replica_dir, exist_ok=True)
                # atomic publication: tmp IN the replica dir (rename never
                # crosses filesystems), pid-unique (two stores sharing one
                # replica volume must not clobber each other's tmp),
                # fsync'd file + dir (the rename itself must be durable —
                # this is the copy a REPLACEMENT host recovers from)
                rtmp = os.path.join(
                    self._replica_dir, "snapshot.bin.%d.tmp" % os.getpid()
                )
                with open(rtmp, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(
                    rtmp, os.path.join(self._replica_dir, "snapshot.bin")
                )
                dir_fd = os.open(self._replica_dir, os.O_RDONLY)
                try:
                    os.fsync(dir_fd)
                finally:
                    os.close(dir_fd)
            except OSError as exc:
                logger.warning(
                    "snapshot replica %s unwritable (%s); live store "
                    "unaffected", self._replica_dir, exc,
                )
        if self._wal_file is not None:
            self._wal_file.close()
        self._wal_file = open(self._wal_path, "wb")
        self._wal_count = 0
        self._last_compact = time.monotonic()
        self._m_compactions.inc()

    def _journal(self, entries: List[dict]) -> None:
        if self._wal_file is None or not entries:
            return
        if _FP_WAL.armed:
            _FP_WAL.fire(n=len(entries))
        # fault=False: the rpc.wire.tx point must never reach the journal
        # (a "network" fault corrupting durable state); WAL faults have
        # their own store.server.wal point above
        self._wal_file.write(
            b"".join(pack_frame(e, fault=False) for e in entries)
        )
        self._wal_file.flush()
        os.fsync(self._wal_file.fileno())
        self._wal_count += len(entries)
        if self._wal_count >= _COMPACT_EVERY or (
            self._replica_dir
            and time.monotonic() - self._last_compact >= _REPLICA_INTERVAL
        ):
            self._compact()

    def _commit(
        self,
        conn: Optional[_Conn],
        resp: Optional[dict],
        events: List[Event],
        entries: List[dict],
    ) -> None:
        """One commit: read-only commits answer immediately; mutations
        are buffered for the GROUP COMMIT that ends the current event-
        loop pass (``_flush_commits``). Grouping amortizes the WAL
        fsync — the dominant per-write cost on a durable store — across
        every request decoded in the pass: under pipelined load the
        journal syncs once per batch instead of once per write, while a
        lone write still flushes immediately (one commit = one fsync,
        exactly the old latency). The ack contract is unchanged: a
        response is only sent AFTER the batch containing its entries is
        fsynced (and, under semi-sync, standby-acked)."""
        if not entries:
            if resp is not None and conn is not None:
                self._send(conn, resp)
            self._fanout(events)
            return
        self._txn_buf.append((conn, resp, list(events), entries))
        if not self._group_commit:
            self._flush_commits()

    def _flush_applies(self) -> None:
        """Journal the standby's buffered replicated entries (one
        write+fsync for the whole buffer). Must run before any ack, any
        LOCAL commit's journal (WAL stays in apply order), and
        promotion."""
        if self._apply_buf:
            buf, self._apply_buf = self._apply_buf, []
            self._journal(buf)

    def _flush_commits(self) -> None:
        """End-of-pass group commit: journal every buffered entry with
        ONE write+fsync, stream the whole batch to subscribers as ONE
        replication frame, then release the responses and watch
        fan-out — held on the semi-sync queue when standbys must ack
        first, in FIFO order always."""
        if not self._txn_buf:
            return
        self._flush_applies()  # WAL order: replicated before local entries
        buffered, self._txn_buf = self._txn_buf, []
        all_entries: List[dict] = []
        for _conn, _resp, _events, entries in buffered:
            all_entries.extend(entries)
        self._journal(all_entries)
        targets = self._repl_broadcast(all_entries)
        completions = [
            (conn, resp, events) for conn, resp, events, _e in buffered
        ]
        if targets:
            first_rev = min(
                (evs[0].rev for _c, _r, evs in completions if evs),
                default=self._state.revision + 1,
            )
            self._sync_q.append(_SyncWait(
                completions, first_rev, targets,
                time.monotonic() + self._repl_sync_timeout,
            ))
            return
        self._release(completions)

    def _release(self, completions) -> None:
        for conn, resp, events in completions:
            if resp is not None and conn is not None:
                self._send(conn, resp)
            self._fanout(events)

    def _sync_drain(self, now: float) -> None:
        """Release held semi-sync batches, strictly FIFO (head-of-line:
        a later batch's ack never overtakes an earlier one's fanout, so
        watchers observe revision order). A batch releases when every
        target standby acked it; it DEGRADES to async — metered, the
        repl-sync-degraded rule's signal — when the deadline passes or
        the last subscriber died unacked."""
        while self._sync_q:
            wait = self._sync_q[0]
            lost = [s for s, t in wait.targets if s.closed and s.repl_ack < t]
            pending = [
                (s, t) for s, t in wait.targets
                if not s.closed and s.repl_ack < t
            ]
            if pending and now < wait.deadline:
                return
            self._sync_q.popleft()
            if pending or lost:
                cause = "timeout" if pending else "subscriber_lost"
                self._m_sync_degraded.inc(cause=cause)
                obs_trace.get_tracer().instant(
                    "store_repl_sync_degraded", cause=cause,
                    held=str(len(pending)),
                )
                if now - self._sync_last_warn >= 1.0:  # bound the log rate
                    self._sync_last_warn = now
                    logger.warning(
                        "semi-sync commit degraded to async (%s); the "
                        "replication loss window is OPEN until the "
                        "standby catches up", cause,
                    )
            self._release(wait.completions)

    def _released_rev(self) -> int:
        """The highest revision whose commit has been RELEASED to
        clients (acked / fanned out). While commits are held — buffered
        for the pass's group commit, or awaiting a semi-sync ack —
        watch registrations must not leak the held suffix through the
        history backlog: a watcher would observe a write that can
        still die with this primary alone."""
        # the sync queue holds OLDER batches than the pass buffer: the
        # earliest held event bounds what a fresh watch may be told
        for wait in self._sync_q:
            if wait.first_rev <= self._state.revision:
                return wait.first_rev - 1
        for conn_resp_events in self._txn_buf:
            events = conn_resp_events[2]
            if events:
                return events[0].rev - 1
        return self._state.revision

    def _note_lease_resets(self, count: int, cause: str) -> None:
        self._m_lease_resets.inc(count, cause=cause)
        obs_trace.get_tracer().instant(
            "store_lease_reset", cause=cause, count=str(count)
        )
        logger.warning(
            "store restarted %d lease(s) with a fresh TTL (%s)", count, cause
        )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "StoreServer":
        self._thread = threading.Thread(
            target=self.serve_forever, name="edl-store", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        try:
            self._wake_w.send(b"x")
        except OSError:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5)

    def kill(self) -> None:
        """Crash simulation for failover drills: stop serving WITHOUT the
        clean-stop compaction, leaving snapshot + WAL exactly as a real
        SIGKILL would — the in-process stand-in for killing the daemon
        (every open connection sees a reset, a restart on the same
        data_dir replays the journal)."""
        self._crash = True
        self.stop()

    def serve_forever(self) -> None:  # edl: event-loop(store server: every RPC and lease sweep rides this thread)
        logger.info(
            "store serving on port %d (%s, epoch %d)",
            self.port, self.role, self._state.epoch,
        )
        last_sweep = time.monotonic()
        try:
            # commits buffered before the loop started (boot-time
            # endpoint publication) become durable on the first pass
            self._flush_commits()
            while not self._stop.is_set():
                timeout = _LEASE_SWEEP_INTERVAL
                # deadlines only matter to the acting primary: a standby's
                # replicated leases see no keepalives, and waking on their
                # (stale) deadlines would spin the loop
                deadline = (
                    self._state.next_lease_deadline()
                    if self.role == "primary" and self._fenced_by is None
                    else None
                )
                if deadline is not None:
                    timeout = min(timeout, max(0.0, deadline - time.monotonic()))
                if self._sync_q:
                    # wake by the head commit's degrade deadline: a held
                    # ack must not wait out a full sweep interval
                    timeout = min(timeout, max(
                        0.0, self._sync_q[0].deadline - time.monotonic()
                    ))
                asked = time.monotonic()
                ready = self._sel.select(timeout)
                overslept = time.monotonic() - asked - timeout
                if overslept > _STALL_FORGIVEN_ABOVE:
                    # no keepalive could arrive while nothing ran: the
                    # owners get the lost time back before any sweep
                    n = self._state.extend_lease_deadlines(overslept)
                    self._m_lease_resets.inc(n, cause="stall")
                    logger.warning(
                        "serve loop stalled %.1fs (process not scheduled); "
                        "%d lease deadline(s) extended by as much",
                        overslept, n,
                    )
                for key, _ in ready:
                    if key.data == "wake":
                        try:
                            self._wake_r.recv(4096)
                        except OSError:
                            pass
                    elif key.data == "repl":
                        self._on_repl_readable()
                    elif key.fileobj is self._listener:
                        self._accept()
                    else:
                        self._service(key.fileobj, key.events)
                # end of the service pass: group-commit everything the
                # pass dispatched (one WAL fsync + one repl frame for
                # the whole batch), then release/hold the responses
                self._flush_commits()
                now = time.monotonic()
                if self._sync_q:
                    self._sync_drain(now)
                self._repl_tick(now)
                # MVCC chain compaction: versions older than the released
                # horizon minus the retain budget serve no read (pinned
                # snapshots and watch resumes both live above it). Runs
                # on standbys too — their chains grow at apply time.
                if now - self._mvcc_last_compact >= 1.0:
                    self._mvcc_last_compact = now
                    self._state.compact(
                        self._released_rev() - self._mvcc_retain
                    )
                # liveness duty belongs to the serving primary alone: a
                # standby's lease deadlines tick without keepalives (they
                # land on the primary), and a fenced primary no longer
                # speaks for the cluster
                sweep_due = (
                    self.role == "primary"
                    and self._fenced_by is None
                    and (
                        now - last_sweep >= _LEASE_SWEEP_INTERVAL
                        or (deadline is not None and deadline <= now)
                    )
                )
                if sweep_due:
                    last_sweep = now
                    expired, dead_ids = self._state.expire_leases_with_ids()
                    if expired or dead_ids:
                        # server-initiated commits ride the same group-
                        # commit + semi-sync queue as client writes:
                        # expiry events reach watchers only once
                        # standby-durable, in order
                        self._commit(
                            None, None, expired,
                            [{"op": "revoke", "id": lid} for lid in dead_ids]
                            + [{"op": "ev", **ev.to_wire()} for ev in expired],
                        )
                        self._flush_commits()
                    if (
                        self._replica_dir
                        and self._wal_count > 0
                        and time.monotonic() - self._last_compact
                        >= _REPLICA_INTERVAL
                    ):
                        # a QUIET store must still honor the replica
                        # staleness bound: mutation-triggered compaction
                        # alone would strand the final pre-quiescence
                        # writes outside the replica forever
                        self._compact()
        finally:
            if self._wal_file is not None:
                if not self._crash:
                    self._compact()  # clean stop: durable snapshot, empty wal
                self._wal_file.close()
                self._wal_file = None
            self._repl_close()
            for conn in list(self._conns.values()):
                self._close(conn)
            self._sel.unregister(self._listener)
            self._listener.close()
            self._wake_r.close()
            self._wake_w.close()
            self._sel.close()
            self._obs_gauges.release()
            obs_http.release_health("store", self._health_fn)
            logger.info("store on port %d stopped", self.port)

    # -- event loop internals ---------------------------------------------

    def _accept(self) -> None:
        try:
            sock, addr = self._listener.accept()
        except OSError:
            return
        sock.setblocking(False)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _Conn(sock, addr)
        self._conns[sock] = conn
        self._sel.register(sock, selectors.EVENT_READ, conn)

    def _service(self, sock: socket.socket, events: int) -> None:
        conn = self._conns.get(sock)
        if conn is None:
            return
        if events & selectors.EVENT_READ:
            self._on_readable(conn)
        if not conn.closed and events & selectors.EVENT_WRITE:
            self._flush(conn)

    def _on_readable(self, conn: _Conn) -> None:
        try:
            data = conn.sock.recv(256 * 1024)
        except BlockingIOError:
            return
        except OSError:
            self._close(conn)
            return
        if not data:
            self._close(conn)
            return
        try:
            requests = conn.reader.feed(data)
        except (WireError, ConnectionError) as exc:
            # ConnectionError: an injected rpc.wire.rx drop — one dead
            # connection, and it must not escape into (and kill) the
            # shared event loop, same as the tx guard in _send
            logger.warning("protocol error from %s: %s", conn.addr, exc)
            self._close(conn)
            return
        for req in requests:
            self._dispatch(conn, req)
            if conn.closed:
                return

    def _send(self, conn: _Conn, payload: dict) -> None:
        if conn.closed:
            return
        try:
            frame = pack_frame(payload)
        except ConnectionError:
            # an injected tx drop means THIS connection reset mid-send; it
            # must not escape into (and kill) the shared event loop
            self._close(conn)
            return
        conn.out += frame
        self._flush(conn)

    def _flush(self, conn: _Conn) -> None:
        try:
            while conn.out:
                sent = conn.sock.send(conn.out)
                if sent == 0:
                    break
                del conn.out[:sent]
        except BlockingIOError:
            pass
        except OSError:
            self._close(conn)
            return
        mask = selectors.EVENT_READ
        if conn.out:
            mask |= selectors.EVENT_WRITE
        try:
            self._sel.modify(conn.sock, mask, conn)
        except (KeyError, ValueError, OSError):
            pass

    def _close(self, conn: _Conn) -> None:
        if conn.closed:
            return
        conn.closed = True
        try:
            self._sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        self._conns.pop(conn.sock, None)
        try:
            conn.sock.close()
        except OSError:
            pass

    def _fanout(self, events: List[Event]) -> None:
        """Push events to every connection watching a matching prefix.
        Deliveries to one connection are BATCHED into a single frame
        (``wb``) when more than one of its watches matched — at 10k-pod
        scale one membership event can match hundreds of watches, and
        per-watch frames were a frame-rate multiplier on the fan-out
        path. Events at-or-below a watch's registration revision are
        skipped: the registration's backlog already delivered them."""
        if not events:
            return
        for conn in list(self._conns.values()):
            batch: List[list] = []
            for wid, (prefix, hwm) in list(conn.watches.items()):
                matched = [
                    e.to_wire() for e in events
                    if e.rev > hwm and e.key.startswith(prefix)
                ]
                if matched:
                    self._m_fanout.inc(len(matched))
                    batch.append([wid, matched])
            if not batch:
                continue
            if len(batch) == 1:
                self._send(conn, {"w": batch[0][0], "ev": batch[0][1]})
            else:
                self._send(conn, {"wb": batch})

    # -- replication (warm standby + failover) -----------------------------
    #
    # All follower-side work runs on the event-loop thread: the link to
    # the primary is just another selector-registered socket, so the
    # state machine stays single-threaded (the same invariant the client
    # connections rely on). The only extra thread is the promoted
    # primary's fence campaign, which never touches ``_state``.

    def _repl_lag_entries(self) -> float:
        if self.role != "standby":
            return 0.0
        return float(max(0, self._primary_rev - self._state.revision))

    def _repl_lag_seconds(self) -> float:
        if self.role != "standby":
            return 0.0
        anchor = self._repl_last_contact or self._repl_down_since
        return max(0.0, time.monotonic() - anchor)

    def _repl_unacked_bytes(self) -> float:
        """Journal bytes in flight toward standbys: streamed (kernel-
        buffered at best) but not yet echoed back by a ``repl_ack``.
        This is the exact measurement of the known store-failover
        async-replication window — acked writes the primary already
        answered for can still die with it while this is nonzero."""
        total = 0
        for conn in list(self._conns.values()):
            if conn.repl and not conn.closed:
                total += max(0, conn.repl_tx - conn.repl_ack)
        return float(total)

    def _known_endpoints(self) -> List[str]:
        """Every member endpoint this store has heard of: the replicated
        membership keyspace plus the configured follow list."""
        rows, _rev = self._state.range(replica_mod.ENDPOINTS_PREFIX)
        out = replica_mod.parse_endpoint_rows(rows)
        for ep in self._follow:
            if ep not in out:
                out.append(ep)
        return out

    def _fence_self_if_a_peer_is_newer(self) -> None:
        """A primary that recovered state naming other members asks each
        for its epoch BEFORE it serves a request: a standby that promoted
        while this store was dead answers with the higher one, and this
        store starts fenced. The promoted primary's fence campaign only
        passes once a ``_FENCE_INTERVAL``; without this a client that had
        not yet met the new primary could reconnect here in between, have
        writes acknowledged from stale state and lose them."""
        for ep in self._known_endpoints():
            if ep == self._advertise:
                continue
            # no sender: a question, not a claim in an equal-epoch tie
            resp = replica_mod.send_fence(ep, self._state.epoch, timeout=0.5)
            peer_epoch = int(resp.get("e", 0)) if resp is not None else 0
            if peer_epoch > self._state.epoch:
                self._fence_self(
                    peer_epoch, "%s answered with a newer epoch at boot" % ep
                )
                return

    def _publish_endpoint(
        self, slot: int, endpoint: str, role: Optional[str] = None
    ) -> None:
        ev = self._state.put(
            replica_mod.endpoint_key(slot),
            replica_mod.endpoint_value(
                endpoint, self._state.epoch, role or self.role
            ),
        )
        self._commit(None, None, [ev], [{"op": "ev", **ev.to_wire()}])

    def _retract_endpoint(self, slot: int) -> None:
        ev = self._state.delete(replica_mod.endpoint_key(slot))
        if ev is not None:
            self._commit(None, None, [ev], [{"op": "ev", **ev.to_wire()}])

    def _repl_broadcast(self, entries: List[dict]) -> List[Tuple[_Conn, int]]:
        """Stream a journal batch (or an empty heartbeat) to every
        replication subscriber. Under semi-sync, entry batches carry the
        per-subscriber cumulative byte stamp (``tb``) so the standby
        acks the moment it has applied+journaled — and the returned
        ``(subscriber, target)`` list is what the commit's release
        waits on. Async mode returns ``[]`` (stamps ride the 0.25s
        heartbeats instead, converging the loss-window gauge without
        per-write chatter)."""
        subs = [c for c in self._conns.values() if c.repl and not c.closed]
        if not subs:
            return []
        payload = {
            "rl": entries,
            "e": self._state.epoch,
            "r": self._state.revision,
        }
        if entries:
            sync = self._repl_sync_timeout > 0
            # ONE serialization per batch shared by every subscriber and
            # by the loss-window accounting; under semi-sync, the
            # per-subscriber cumulative stamp rides a tiny empty-batch
            # frame AFTER the shared one (TCP orders them, so the
            # standby's ack certifies the batch was applied+journaled)
            # instead of re-packing the whole batch per subscriber
            try:
                base = pack_frame(payload)
            except ConnectionError:
                # injected rpc.wire.tx drop: every subscriber link dies
                for conn in subs:
                    self._close(conn)
                return []
            targets: List[Tuple[_Conn, int]] = []
            for conn in subs:
                if _FP_REPL_STREAM.armed:
                    try:
                        _FP_REPL_STREAM.fire(side="tx", n=len(entries))
                    except ConnectionError:
                        self._close(conn)  # the standby sees a dead link
                        continue
                conn.repl_tx += len(base)
                conn.out += base
                if sync:
                    try:
                        conn.out += pack_frame({
                            "rl": [], "e": self._state.epoch,
                            "r": self._state.revision, "tb": conn.repl_tx,
                        })
                    except ConnectionError:
                        self._close(conn)
                        continue
                self._flush(conn)
                if sync and not conn.closed:
                    targets.append((conn, conn.repl_tx))
            return targets
        # heartbeat: per-subscriber, carrying the cumulative streamed
        # byte count; the standby echoes it back as a repl_ack, so the
        # edl_store_repl_unacked_bytes window converges at heartbeat
        # cadence without any per-write ack chatter
        for conn in subs:
            if _FP_REPL_STREAM.armed:
                try:
                    _FP_REPL_STREAM.fire(side="tx", n=0)
                except ConnectionError:
                    self._close(conn)
                    continue
            self._send(conn, dict(payload, tb=conn.repl_tx))
        return []

    def _repl_tick(self, now: float) -> None:
        if self.role == "primary":
            if self._fenced_by is None and now - self._repl_last_hb >= _REPL_HEARTBEAT:
                self._repl_last_hb = now
                self._repl_broadcast([])
            return
        if self._repl_sock is not None:
            # a silent partition gives no socket error: declare the link
            # dead once heartbeats stop arriving
            stale_after = max(self._failover_grace, 4 * _REPL_HEARTBEAT)
            if (
                self._repl_last_contact
                and now - self._repl_last_contact > stale_after
            ):
                self._repl_lost("heartbeats stopped")
            return
        if now - self._repl_down_since >= self._failover_grace * self.priority:
            self._consider_promotion(now)
            if self.role == "primary":
                return
        if now - self._repl_last_attempt >= _REPL_DIAL_INTERVAL:
            self._repl_last_attempt = now
            self._repl_connect()

    def _repl_connect(self) -> None:
        """One bootstrap attempt against the current follow target. The
        sync response (snapshot) arrives through the selector like every
        other frame."""
        if not self._follow:
            return
        target = self._follow[self._follow_i % len(self._follow)]
        if target == self._advertise:
            self._follow_i += 1
            return
        try:
            if _FP_REPL_SYNC.armed:
                _FP_REPL_SYNC.fire(endpoint=target)  # drop is an OSError
            from edl_tpu.utils.net import split_endpoint

            sock = socket.create_connection(  # edl: blocking-ok(bounded 0.5s dial, standby only: a disconnected standby's loop has no client traffic to starve)
                split_endpoint(target), timeout=0.5
            )
        except OSError:
            self._follow_i += 1  # rotate: the primary may have moved
            return
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.sendall(pack_frame({
                "i": 0,
                "m": "repl_sync",
                "e": max(self._state.epoch, self._primary_epoch),
                "ep": self._advertise,
                "prio": self.priority,
            }))
        except OSError:
            sock.close()
            self._follow_i += 1
            return
        sock.setblocking(False)
        self._repl_sock = sock
        self._repl_reader = FrameReader(fault=False)  # repl has its own points
        self._sel.register(sock, selectors.EVENT_READ, "repl")
        self._repl_last_contact = time.monotonic()
        logger.info("standby syncing from %s", target)

    def _on_repl_readable(self) -> None:
        sock = self._repl_sock
        if sock is None:
            return
        try:
            data = sock.recv(256 * 1024)
        except BlockingIOError:
            return
        except OSError as exc:
            self._repl_lost("recv failed: %s" % exc)
            return
        if not data:
            self._repl_lost("primary closed the link")
            return
        try:
            frames = self._repl_reader.feed(data)
            self._repl_last_contact = time.monotonic()
            for frame in frames:
                if "snap" in frame:
                    self._repl_bootstrap(frame)
                elif "rl" in frame:
                    self._repl_apply(frame)
                elif frame.get("ok") is False:
                    # the peer refused the sync (a standby, or fenced):
                    # rotate to the next candidate WITHOUT resetting the
                    # promotion grace clock — reaching a fellow standby
                    # is not contact with a primary, and treating it as
                    # such would keep a standby whose follow list names
                    # its peers from ever promoting
                    self._repl_lost(
                        "sync rejected: %s"
                        % frame.get("err", {}).get("detail", "?"),
                        reset_down=False,
                    )
                    self._follow_i += 1
                    return
        except (WireError, ConnectionError) as exc:
            self._repl_lost(str(exc))

    def _repl_bootstrap(self, frame: dict) -> None:
        import msgpack

        self._state.load_snapshot(msgpack.unpackb(frame["snap"], raw=False))
        # a demoted ex-primary re-syncing discards any diverged local
        # suffix here: the snapshot is authoritative, full resync by design
        self._primary_epoch = int(frame.get("e", 0))
        self._state.set_epoch(self._primary_epoch)
        self._primary_rev = int(frame.get("r", self._state.revision))
        self._has_state = True
        self._repl_down_since = time.monotonic()
        if self._data_dir:
            self._compact()  # persist the bootstrap before tailing
        logger.info(
            "standby bootstrapped from primary: rev=%d epoch=%d",
            self._state.revision, self._state.epoch,
        )

    def _repl_apply(self, frame: dict) -> None:
        entries = frame.get("rl") or ()
        if entries and _FP_REPL_STREAM.armed:
            _FP_REPL_STREAM.fire(side="rx", n=len(entries))
        for entry in entries:
            # record=True: the history ring must survive into promotion
            # so client watches resume from pre-failover revisions
            self._state.apply_journal(entry, record=True)
        if entries:
            # journaling is DEFERRED to the ack boundary (_flush_applies):
            # the ack contract — acked implies applied AND journaled —
            # holds because the flush always precedes the ack send below,
            # and an un-journaled entry is by construction un-acked (the
            # primary holds or degrades, never trusts it)
            self._apply_buf.extend(entries)
            # standby read serving: watches registered HERE fan out at
            # apply time — on a standby applied == released (it holds no
            # commit queues), and the primary only streamed this batch
            # after journaling it, so nothing pushed here can be undone
            # by the primary dying mid-window
            applied = [
                Event.from_wire(e) for e in entries if e.get("op") == "ev"
            ]
            if applied:
                self._fanout(applied)
        self._primary_epoch = max(self._primary_epoch, int(frame.get("e", 0)))
        self._primary_rev = max(self._primary_rev, int(frame.get("r", 0)))
        # ack the cumulative byte count we have APPLIED (and journaled):
        # the primary's edl_store_repl_unacked_bytes gauge is the stream
        # minus these echoes. The stamp arrives only on the primary's
        # 0.25s heartbeats, so acks are naturally throttled — an
        # in-process primary+standby pair shares the GIL, and per-write
        # ack chatter would be exactly what PR 6/8 pace out of HA rigs.
        # Best-effort on the nonblocking link: a lost ack just means the
        # next heartbeat's (cumulative) echo covers us.
        tb = frame.get("tb")
        if tb is not None and self._repl_sock is not None:
            # the ack boundary: everything applied so far must be
            # journaled BEFORE the cumulative byte echo goes out — one
            # fsync per heartbeat interval instead of one per frame
            self._flush_applies()
            try:
                ack = pack_frame(
                    {"i": 0, "m": "repl_ack", "tb": int(tb)}, fault=False
                )
                sent = self._repl_sock.send(ack)
                if sent != len(ack):
                    # a partial write on the (nearly idle) ack direction
                    # would desync the primary's frame reader: treat it
                    # as a dead link and resync rather than corrupt the
                    # stream — the ack protocol has no resume point
                    self._repl_lost("partial ack write (%d/%d)"
                                    % (sent, len(ack)))
            except BlockingIOError:
                pass  # buffer full: the next batch's cumulative ack covers
            except (OSError, TypeError, ValueError):
                pass

    def _repl_lost(self, reason: str, reset_down: bool = True) -> None:
        # the link may never stamp another ack boundary: journal what
        # was applied so the buffer cannot outlive a healthy-link window
        self._flush_applies()
        sock, self._repl_sock = self._repl_sock, None
        self._repl_reader = None
        if sock is None:
            return
        try:
            self._sel.unregister(sock)
        except (KeyError, ValueError):
            pass
        try:
            sock.close()
        except OSError:
            pass
        if reset_down:
            self._repl_down_since = time.monotonic()
        self._repl_last_contact = 0.0
        logger.warning("replication link lost (%s)", reason)

    def _repl_close(self) -> None:
        if self._repl_sock is not None:
            self._repl_lost("server stopping")

    def _consider_promotion(self, now: float) -> None:
        """The link has been dead past this standby's share of the grace
        window. Probe the world first — promotion must lose to any live
        primary of an equal-or-newer generation (a link blip, or a
        better-placed standby that already took over)."""
        if not self._has_state:
            return  # nothing to serve: promoting an empty store loses data
        for ep in self._known_endpoints():
            if ep == self._advertise:
                continue
            status = replica_mod.probe_status(ep, timeout=0.3)
            if (
                status is not None
                and status.get("role") == "primary"
                and not status.get("fenced")
                and int(status.get("e", 0)) >= self._primary_epoch
            ):
                # someone is serving this generation (or a newer one):
                # follow them instead of splitting the brain
                self._primary_epoch = max(
                    self._primary_epoch, int(status.get("e", 0))
                )
                if ep not in self._follow:
                    self._follow.insert(0, ep)
                self._follow_i = self._follow.index(ep)
                self._repl_down_since = now  # restart the grace clock
                return
        self._promote()

    def _promote(self) -> None:
        # everything applied while standby becomes durable BEFORE this
        # store starts speaking as the primary
        self._flush_applies()
        new_epoch = max(self._state.epoch, self._primary_epoch) + 1
        self._state.set_epoch(new_epoch)
        self.role = "primary"
        fence_targets = [
            ep for ep in self._known_endpoints() if ep != self._advertise
        ]
        self._commit(None, None, [], [{"op": "epoch", "e": new_epoch}])
        resets = self._state.reset_lease_deadlines()
        if resets:
            self._note_lease_resets(resets, "promotion")
        # membership: take slot 0, clear whichever standby slot(s) hold
        # my endpoint (slot may have been bumped past my priority if it
        # collided with another standby's — never retract by number
        # alone, that could delete a peer's row)
        import json as _json

        rows, _rev = self._state.range(replica_mod.ENDPOINTS_PREFIX)
        for key, value, *_rest in rows:
            try:
                slot = int(key[len(replica_mod.ENDPOINTS_PREFIX):])
                mine = _json.loads(value).get("endpoint") == self._advertise
            except (ValueError, TypeError):
                continue
            if mine and slot != 0:
                self._retract_endpoint(slot)
        self._publish_endpoint(0, self._advertise)
        self._m_failovers.inc()
        # the epoch bump must be durable BEFORE this store serves as
        # primary: flush the group-commit buffer here, not next pass
        self._flush_commits()
        # operation root: the failover's trace id derives from the new
        # epoch, so any other process touching the op (edl-trace, a
        # future semi-sync handshake) stitches to it deterministically
        if obs_trace.PROPAGATION.armed:
            ctx = obs_trace.record_op_root(
                "store_failover", str(new_epoch), endpoint=self._advertise
            )
        else:
            ctx = None
        with obs_trace.use(ctx):
            obs_trace.get_tracer().instant(
                "store_promote", epoch=str(new_epoch),
                endpoint=self._advertise,
            )
        logger.warning(
            "standby PROMOTED to primary: epoch %d, rev %d, fencing %s",
            new_epoch, self._state.revision, fence_targets or "(nobody)",
        )
        self._start_fence_campaign(fence_targets)

    def _start_fence_campaign(self, targets: List[str]) -> None:
        if not targets:
            return
        self._fence_thread = threading.Thread(
            target=self._fence_loop, args=(list(targets),),
            name="edl-store-fence", daemon=True,
        )
        self._fence_thread.start()

    def _fence_loop(self, targets: List[str]) -> None:
        """Keep delivering our epoch to every other known endpoint while
        we are the primary — a stale primary resurrected at ANY later
        point gets fenced within one pass, before fresh clients can
        write to it."""
        while (
            not self._stop.is_set()
            and self.role == "primary"
            and self._fenced_by is None
        ):
            epoch = self._state.epoch
            for ep in targets:
                resp = replica_mod.send_fence(
                    ep, epoch, sender=self._advertise, timeout=0.5
                )
                if resp is None:
                    continue
                peer_epoch = int(resp.get("e", 0))
                if peer_epoch > epoch:
                    # a newer generation exists: WE are the stale one
                    self._fence_self(
                        peer_epoch, "fence race lost against %s" % ep
                    )
                    return
                if (
                    peer_epoch == epoch
                    and resp.get("role") == "primary"
                    and not resp.get("fenced")
                    and self._advertise > ep
                ):
                    # equal-epoch tie against a surviving primary: the
                    # lexically larger endpoint loses (mirror of the
                    # receiver-side rule in _op_repl_fence)
                    self._fence_self(
                        epoch, "equal-epoch tie lost to %s" % ep
                    )
                    return
            self._stop.wait(_FENCE_INTERVAL)

    def _fence_self(self, epoch: int, why: str) -> None:
        if self._fenced_by is not None and self._fenced_by >= epoch:
            return
        self._fenced_by = epoch
        self._m_fenced.inc()
        obs_trace.get_tracer().instant(
            "store_fenced", epoch=str(epoch), why=why
        )
        logger.error(
            "store FENCED by epoch %d (%s): refusing all client "
            "operations — a newer primary owns this cluster", epoch, why,
        )

    # -- method dispatch ---------------------------------------------------

    def _response_epoch(self) -> int:
        """The epoch stamped on every response. A fenced store reports
        the epoch that fenced it, so clients learn the NEW generation
        from the stale server itself and refuse it thereafter."""
        if self._fenced_by is not None:
            return self._fenced_by
        return self._state.epoch

    def _send_error(self, conn: _Conn, rid, exc: Exception) -> None:
        self._send(conn, {
            "i": rid,
            "ok": False,
            "e": self._response_epoch(),
            "err": serialize_exception(exc),
        })

    def _dispatch(self, conn: _Conn, req: dict) -> None:
        rid = req.get("i")
        method = req.get("m")
        if method == "repl_ack":
            # a standby echoing the replication stream's cumulative byte
            # count: pure accounting, no response frame (the subscriber
            # link is not a request/response channel), and exempt from
            # the fencing/standby gates below — acks must keep flowing
            # right up to the moment the link dies
            try:
                conn.repl_ack = max(conn.repl_ack, int(req.get("tb", 0)))
            except (TypeError, ValueError):
                pass
            if self._sync_q:
                # a fresh ack may release held semi-sync commits NOW —
                # the ack round-trip, not the next loop tick, is the
                # semi-sync latency floor
                self._sync_drain(time.monotonic())
            return
        if _FP_DISPATCH.armed:
            try:
                _FP_DISPATCH.fire(method=str(method))
            except ConnectionError:
                self._close(conn)  # the peer sees a reset mid-request
                return
        handler = getattr(self, "_op_" + str(method), None)
        # sentinel for unknown methods: the label value is client data,
        # and per-value counter series would let a fuzzing client grow
        # the registry without bound
        self._m_requests.inc(
            method=str(method) if handler is not None else "<unknown>"
        )
        if handler is None:
            self._send_error(
                conn, rid, EdlStoreError("unknown method %r" % method)
            )
            return
        # epoch fencing: a store that saw a higher epoch no longer speaks
        # for the cluster — only liveness/fence probes get through
        if self._fenced_by is not None and method not in _STANDBY_OK:
            self._send_error(conn, rid, EdlFencedError(
                "store fenced by epoch %d; a newer primary owns this "
                "cluster" % self._fenced_by
            ))
            return
        if self.role != "primary" and method not in _STANDBY_OK:
            refusal = self._standby_read_refusal(method, req)
            if refusal is not None:
                self._send_error(conn, rid, EdlNotPrimaryError(refusal))
                return
            self._standby_reads_n += 1
            self._m_standby_reads.inc()
        try:
            # per-method server-side latency + (when the caller stamped
            # a "tc" trace context into the frame) a handling span that
            # is a child of the caller's span
            with server_span(str(method), req.get(TC_FIELD), server=self.name):
                result, events = handler(conn, req)
        except Exception as exc:  # noqa: BLE001 — every fault maps to a wire error
            self._send_error(conn, rid, exc)
            return
        # journal + replicate BEFORE acking: a response implies the
        # mutation is durable AND streamed to every live standby — and
        # under semi-sync, standby-APPLIED (the commit below holds the
        # ack until the repl_ack covers it)
        entries: List[dict] = []
        if method == "lease_grant":
            entries.append(
                {"op": "grant", "id": result["lease"], "ttl": float(req["ttl"])}
            )
        elif method == "lease_revoke":
            entries.append({"op": "revoke", "id": req["lease"]})
        entries.extend({"op": "ev", **ev.to_wire()} for ev in events)
        resp = {"i": rid, "ok": True, "e": self._response_epoch()}
        resp.update(result)
        self._commit(conn, resp, list(events), entries)

    _NO_EVENTS: Tuple = ()

    # the read-only ops a standby may serve itself (applied == released
    # there: it holds no commit queues). unwatch rides along so a client
    # with a standby-registered watch can tear it down where it lives.
    _STANDBY_READS = ("get", "range", "watch", "unwatch")

    def _standby_read_refusal(self, method, req) -> Optional[str]:
        """None when this standby serves the read itself; otherwise the
        reason it must bounce to the primary. Every refusal maps to
        EdlNotPrimaryError on the wire — the exact error clients already
        redirect on, so old clients, lag fall-through and the
        read-your-writes floor all degrade the same way: a primary
        round-trip. Serving requires the client's explicit opt-in
        ("rm": "s"): a legacy client that dialed a standby by accident
        keeps getting the redirect, never silently-stale data."""
        if method not in self._STANDBY_READS or req.get("rm") != "s":
            return (
                "store at %s is a warm standby (epoch %d); retry against "
                "the primary" % (self._advertise, self._state.epoch)
            )
        if not self._has_state:
            return (
                "standby %s has no state yet (still bootstrapping)"
                % self._advertise
            )
        lag = self._repl_lag_entries()
        if lag > self._standby_max_lag:
            return (
                "standby %s lags the primary by %d revs (bound "
                "EDL_STORE_STANDBY_MAX_LAG=%d); retry against the primary"
                % (self._advertise, lag, self._standby_max_lag)
            )
        minr = req.get("minr")
        if minr is not None:
            try:
                floor = int(minr)
            except (TypeError, ValueError):
                floor = 0
            if self._state.revision < floor:
                return (
                    "standby %s applied rev %d < the session's write "
                    "floor %d (read-your-writes); retry against the "
                    "primary" % (self._advertise, self._state.revision, floor)
                )
        return None

    def _op_ping(self, conn, req):
        return {}, self._NO_EVENTS

    def _op_put(self, conn, req):
        ev = self._state.put(req["k"], req["v"], req.get("l", 0))
        return {"r": ev.rev}, [ev]

    def _op_put_absent(self, conn, req):
        created, ev, existing = self._state.put_if_absent(
            req["k"], req["v"], req.get("l", 0)
        )
        if created:
            return {"created": True, "r": ev.rev}, [ev]
        return {"created": False, "cur": existing}, self._NO_EVENTS

    def _op_cas(self, conn, req):
        ok, ev = self._state.cas(req["k"], req["er"], req["v"], req.get("l", 0))
        if ok:
            return {"swapped": True, "r": ev.rev}, [ev]
        return {"swapped": False}, self._NO_EVENTS

    def _read_rev(self, req) -> Optional[int]:
        """The revision this read answers AT: an explicit ``rev`` pin
        wins (snapshot-coherent range, MVCC history read); otherwise the
        last RELEASED revision when MVCC is on — a reader must not
        observe a commit whose semi-sync release is still held, it could
        die with this primary. None = the applied state (the fast path,
        and the whole story with EDL_STORE_MVCC=0)."""
        rev = req.get("rev")
        if rev is not None:
            return int(rev)
        if not self._mvcc:
            return None
        released = self._released_rev()
        # session floor: a standby leg may have answered at the standby's
        # applied revision a beat before OUR ack processing released it.
        # Anything the session already observed is applied+journaled on
        # the standby, so serving up to ``minr`` breaks no durability
        # promise — refusing to would make this session's history rewind.
        minr = req.get("minr")
        if minr:
            released = max(released, min(int(minr), self._state.revision))
        if released >= self._state.revision:
            return None  # nothing held: applied state IS released state
        return released

    def _op_get(self, conn, req):  # edl: protocol-ok(sent via client._read variable-method read path)
        rev = self._read_rev(req)
        try:
            got = self._state.get(req["k"], rev=rev)
        except ValueError as exc:
            raise EdlCompactedError(str(exc)) from exc
        asof = (
            self._state.revision if rev is None
            else min(rev, self._state.revision)
        )
        if got is None:
            return {"v": None, "r": asof}, self._NO_EVENTS
        value, mod_rev, lease = got
        return {"v": value, "mr": mod_rev, "l": lease, "r": asof}, self._NO_EVENTS

    def _op_range(self, conn, req):  # edl: protocol-ok(sent via client._read variable-method read path)
        try:
            items, rev = self._state.range(req["p"], rev=self._read_rev(req))
        except ValueError as exc:
            raise EdlCompactedError(str(exc)) from exc
        return {"kvs": [list(item) for item in items], "r": rev}, self._NO_EVENTS

    def _op_del(self, conn, req):
        ev = self._state.delete(req["k"])
        if ev is None:
            return {"deleted": 0}, self._NO_EVENTS
        return {"deleted": 1, "r": ev.rev}, [ev]

    def _op_del_range(self, conn, req):
        events = self._state.delete_range(req["p"])
        return {"deleted": len(events)}, events

    def _op_lease_grant(self, conn, req):
        lease = self._state.lease_grant(float(req["ttl"]))
        return {"lease": lease}, self._NO_EVENTS

    def _op_lease_keepalive(self, conn, req):
        alive = self._state.lease_keepalive(req["lease"])
        return {"alive": alive}, self._NO_EVENTS

    def _op_lease_renew_batch(self, conn, req):
        # the client-side renew coalescer's op: one RPC renews every
        # lease a connection owns this tick — at 10k pods the per-lease
        # keepalive stream was the control plane's dominant QPS
        return {
            "alive": [self._state.lease_keepalive(l) for l in req["ls"]]
        }, self._NO_EVENTS

    def _op_lease_revoke(self, conn, req):
        events = self._state.lease_revoke(req["lease"])
        return {"revoked": True}, events

    def _op_watch(self, conn, req):
        # The watch id is CLIENT-assigned (unique per connection) so the
        # client can register its handler before the first push can arrive —
        # no window where an event targets an unknown id. The backlog is
        # delivered as a push frame, written before the response and before
        # any later event, so the dispatcher sees strictly ordered history.
        wid = req["wid"]
        prefix = req["p"]
        released = self._released_rev()
        backlog = []
        if req.get("r") is not None:
            try:
                backlog = [
                    e.to_wire()
                    for e in self._state.history_since(req["r"], prefix)
                    if e.rev <= released
                ]
            except ValueError as exc:
                raise EdlCompactedError(str(exc)) from exc
        # high-water mark = the released revision: the backlog above
        # covers everything at-or-below it, the (held) fan-out covers
        # everything after — exactly once, and never before the
        # standby ack that makes the event durable beyond this primary.
        # A RESUME point past the released revision (the client's
        # range() already observed applied-but-held state) raises the
        # mark with it: re-delivering the held suffix on release would
        # double what the range reported.
        hwm = released
        if req.get("r") is not None:
            try:
                hwm = max(hwm, int(req["r"]))
            except (TypeError, ValueError):
                pass
        conn.watches[wid] = (prefix, hwm)
        if backlog:
            self._send(conn, {"w": wid, "ev": backlog})
        return {"r": released}, self._NO_EVENTS

    def _op_unwatch(self, conn, req):
        conn.watches.pop(req["wid"], None)
        return {}, self._NO_EVENTS

    def _op_state(self, conn, req):
        return {
            "rev": self._state.revision,
            "conns": len(self._conns),
            "role": self.role,
            "epoch": self._state.epoch,
        }, self._NO_EVENTS

    # -- replication control plane (see "replication" section above) -------

    def _op_repl_status(self, conn, req):
        return {
            "role": self.role,
            "e": self._state.epoch,
            "r": self._state.revision,
            "fenced": self._fenced_by is not None,
            "lag": int(self._repl_lag_entries()),
            # the per-shard health row edl-top renders: the open
            # semi-sync/async loss window and whether semi-sync is armed
            "unacked": int(self._repl_unacked_bytes()),
            "sync": self._repl_sync_timeout > 0,
            "subs": sum(
                1 for c in self._conns.values() if c.repl and not c.closed
            ),
            # read-serving posture (the edl-top STORE panel's read-mode /
            # standby-reads columns): which revision reads answer at, and
            # how many reads this member served as a standby
            "readmode": "released" if self._mvcc else "applied",
            "sreads": self._standby_reads_n,
        }, self._NO_EVENTS

    def _op_repl_sync(self, conn, req):
        """A standby bootstraps: register its endpoint in the membership
        keyspace, hand it a full snapshot, and subscribe its connection
        to the live journal stream. A sync request carrying a HIGHER
        epoch than ours is proof a newer primary exists — fence
        ourselves instead of feeding the caller stale state."""
        import msgpack

        req_epoch = int(req.get("e", 0))
        if req_epoch > self._state.epoch:
            self._fence_self(req_epoch, "repl_sync from a newer generation")
            raise EdlFencedError(
                "fenced by epoch %d carried on a sync request" % req_epoch
            )
        ep = req.get("ep")
        prio = int(req.get("prio", 1))
        if ep:
            # published (and journaled, and streamed) BEFORE the snapshot
            # is taken, so the snapshot below already carries it and the
            # new subscriber never sees its own registration twice. Two
            # standbys configured with the same priority must not
            # overwrite each other's membership row (clients and the
            # fence campaign would lose sight of one): take the first
            # slot at-or-after the requested one that is free or already
            # ours.
            slot = max(1, prio)
            while True:
                held = self._state.get(replica_mod.endpoint_key(slot))
                if held is None:
                    break
                try:
                    import json as _json

                    if _json.loads(held[0]).get("endpoint") == ep:
                        break
                except (ValueError, TypeError):
                    break  # malformed row: claim the slot
                slot += 1
            self._publish_endpoint(slot, ep, role="standby")
        blob = msgpack.packb(self._state.to_snapshot(), use_bin_type=True)
        conn.repl = True
        return {
            "snap": blob,
            "e": self._state.epoch,
            "r": self._state.revision,
        }, self._NO_EVENTS

    def _op_repl_fence(self, conn, req):
        """An epoch delivery from a promoted peer. Outcomes: we are older
        and serving → fence ourselves; we are older and standby → just
        update our horizon; we are NEWER → answer with our epoch so the
        CALLER learns it lost the race (it self-fences); EQUAL epochs
        with both sides primary (two standbys promoted concurrently) →
        tie-break on advertise endpoint, lexically larger loses — the
        same rule the caller applies, so exactly one survives."""
        epoch = int(req["e"])  # edl: protocol-ok(required field of the fence op itself, not the optional response stamp; a missing "e" maps to a wire error via the dispatch guard)
        sender = str(req.get("ep") or "")
        if epoch > self._state.epoch:
            if self.role == "primary":
                self._fence_self(epoch, "repl_fence from a promoted peer")
                return {
                    "fenced": True, "role": self.role,
                }, self._NO_EVENTS
            self._primary_epoch = max(self._primary_epoch, epoch)
            return {"fenced": False, "role": self.role}, self._NO_EVENTS
        if (
            epoch == self._state.epoch
            and self.role == "primary"
            and self._fenced_by is None
            and sender
            and sender != self._advertise
            and self._advertise > sender
        ):
            self._fence_self(epoch, "equal-epoch tie lost to %s" % sender)
            return {"fenced": True, "role": self.role}, self._NO_EVENTS
        return {"fenced": False, "role": self.role}, self._NO_EVENTS


def main() -> None:
    # invoked both as ``python -m edl_tpu.store.server`` and via edl_tpu.launch
    parser = argparse.ArgumentParser(description="edl_tpu coordination store")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=2379)
    parser.add_argument(
        "--data_dir",
        default=None,
        help="durable state dir (snapshot + wal); restarting on the same "
        "dir recovers every key, lease and revision",
    )
    parser.add_argument(
        "--replica_dir",
        default=None,
        help="shared-storage dir (ckpt volume / PVC) receiving a snapshot "
        "copy at every compaction: a replacement store on a FRESH host "
        "with an empty --data_dir seeds itself from here (store-host "
        "loss recovery; staleness bounded by EDL_STORE_REPLICA_INTERVAL)",
    )
    parser.add_argument(
        "--follow",
        default=None,
        help="run as a WARM STANDBY of this comma-separated primary "
        "endpoint list: bootstrap from a streamed snapshot, tail the "
        "journal live, and promote (with an epoch bump that fences the "
        "old primary) if the primary stays dead past the grace window",
    )
    parser.add_argument(
        "--priority", type=int, default=1,
        help="promotion order among standbys (1 = first in line; the "
        "grace window scales with it so lower priorities defer)",
    )
    parser.add_argument(
        "--failover_grace", type=float, default=2.0,
        help="seconds the replication link must stay dead before a "
        "standby considers promotion",
    )
    parser.add_argument(
        "--advertise", default=None,
        help="endpoint other members and clients should reach this store "
        "at (default: 127.0.0.1:<port> — set it on multi-host setups)",
    )
    parser.add_argument(
        "--repl_sync_timeout", type=float, default=None,
        help="semi-sync replication: hold each client ack until every "
        "live standby applied+journaled the write, degrading ONE commit "
        "to async (metered: edl_store_repl_sync_degraded_total) after "
        "this many seconds. <=0 disables semi-sync. Default: "
        "EDL_STORE_REPL_SYNC_TIMEOUT or 0.5",
    )
    parser.add_argument(
        "--name", default="store",
        help="server label on edl_rpc_server_seconds histograms (a "
        "sharded deployment names each shard store-0, store-1, ...)",
    )
    args = parser.parse_args()
    server = StoreServer(
        args.host, args.port, data_dir=args.data_dir,
        replica_dir=args.replica_dir, follow=args.follow,
        priority=args.priority, failover_grace=args.failover_grace,
        advertise=args.advertise, repl_sync_timeout=args.repl_sync_timeout,
        name=args.name,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
