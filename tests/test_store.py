"""Coordination store tests: pure state machine + live server/client.

Mirrors the reference's etcd test strategy (SURVEY §4 pattern 2): run a real
store daemon locally, exercise register/refresh/TTL-expiry/watch against it
(reference python/edl/tests/unittests/etcd_client_test.py) — here the
daemon is our own in-process StoreServer, and TTLs are sub-second so the
suite stays fast.
"""

import threading
import time

import pytest

from edl_tpu.store import Event, LeaseKeeper, StoreClient, StoreServer, StoreState
from edl_tpu.store.client import RESYNC
from edl_tpu.utils.exceptions import EdlStoreError


# ---------------------------------------------------------------------------
# StoreState (pure, no sockets)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_state_put_get_revisions():
    s = StoreState()
    ev1 = s.put("/a", b"1")
    ev2 = s.put("/a", b"2")
    assert (ev1.rev, ev2.rev) == (1, 2)
    value, mod_rev, lease = s.get("/a")
    assert value == b"2" and mod_rev == 2 and lease == 0
    assert s.get("/missing") is None


def test_state_put_if_absent_race():
    s = StoreState()
    created, ev, existing = s.put_if_absent("/rank/0", b"podA")
    assert created and ev is not None and existing is None
    created, ev, existing = s.put_if_absent("/rank/0", b"podB")
    assert not created and ev is None and existing == b"podA"


def test_state_cas():
    s = StoreState()
    ok, _ = s.cas("/k", 0, b"v1")
    assert ok
    _, mod_rev, _ = s.get("/k")
    ok, _ = s.cas("/k", mod_rev + 5, b"bad")
    assert not ok
    ok, _ = s.cas("/k", mod_rev, b"v2")
    assert ok and s.get("/k")[0] == b"v2"


def test_state_range_and_delete_range():
    s = StoreState()
    for i in range(3):
        s.put("/svc/n%d" % i, b"x")
    s.put("/other", b"y")
    items, rev = s.range("/svc/")
    assert [k for k, *_ in items] == ["/svc/n0", "/svc/n1", "/svc/n2"]
    assert rev == 4
    events = s.delete_range("/svc/")
    assert len(events) == 3 and all(e.type == "del" for e in events)
    assert s.range("/svc/")[0] == []


def test_state_lease_expiry_deletes_keys():
    clock = FakeClock()
    s = StoreState(clock=clock)
    lease = s.lease_grant(ttl=10.0)
    s.put("/hb/pod0", b"alive", lease=lease)
    s.put("/permanent", b"stay")
    clock.now += 5
    assert s.expire_leases() == []
    assert s.lease_keepalive(lease)
    clock.now += 9
    assert s.expire_leases() == []  # keepalive pushed the deadline
    clock.now += 2
    events = s.expire_leases()
    assert [e.key for e in events] == ["/hb/pod0"]
    assert s.get("/hb/pod0") is None and s.get("/permanent") is not None
    assert not s.lease_keepalive(lease)


def test_state_put_with_unknown_lease_rejected_cleanly():
    clock = FakeClock()
    s = StoreState(clock=clock)
    lease = s.lease_grant(5.0)
    s.put("/k", b"v", lease=lease)
    with pytest.raises(KeyError):
        s.put("/k", b"v2", lease=999)  # bogus lease must not orphan the key
    clock.now += 6
    events = s.expire_leases()
    assert [e.key for e in events] == ["/k"]  # still expires via its lease


def test_state_lease_detach_on_plain_put():
    clock = FakeClock()
    s = StoreState(clock=clock)
    lease = s.lease_grant(5.0)
    s.put("/k", b"leased", lease=lease)
    s.put("/k", b"permanent")  # no lease: key must survive expiry
    clock.now += 6
    s.expire_leases()
    assert s.get("/k")[0] == b"permanent"


def test_state_history_since():
    s = StoreState()
    s.put("/a/1", b"x")
    s.put("/b/1", b"y")
    s.put("/a/2", b"z")
    events = s.history_since(1, "/a/")
    assert [(e.key, e.rev) for e in events] == [("/a/2", 3)]
    with pytest.raises(ValueError):
        StoreState().history_since(-1, "/")  # below the retained floor


# ---------------------------------------------------------------------------
# Live server + client
# ---------------------------------------------------------------------------


@pytest.fixture()
def server():
    srv = StoreServer(host="127.0.0.1", port=0).start()
    yield srv
    srv.stop()


@pytest.fixture()
def client(server):
    c = StoreClient(server.endpoint, timeout=5)
    yield c
    c.close()


def test_client_put_get_range_delete(client):
    client.put("/job/x", b"1")
    client.put("/job/y", b"2")
    assert client.get("/job/x") == b"1"
    kvs, rev = client.range("/job/")
    assert [(k, v) for k, v, *_ in kvs] == [("/job/x", b"1"), ("/job/y", b"2")]
    assert rev >= 2
    assert client.delete("/job/x")
    assert client.get("/job/x") is None
    assert not client.delete("/job/x")


def test_client_rank_race_single_winner(server):
    """N clients race put_if_absent on the same rank key; exactly one wins.

    This is the primitive behind leader election (reference
    register.py:72-114 races rank 0 over etcd put-if-absent)."""
    clients = [StoreClient(server.endpoint) for _ in range(4)]
    results = []
    barrier = threading.Barrier(4)

    def race(c, i):
        barrier.wait()
        created, cur = c.put_if_absent("/rank/0", b"pod%d" % i)
        results.append(created)

    threads = [
        threading.Thread(target=race, args=(c, i)) for i, c in enumerate(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(results) == 1
    for c in clients:
        c.close()


def test_client_lease_expiry_and_watch_push(server, client):
    observer = StoreClient(server.endpoint)
    seen = []
    done = threading.Event()

    def on_events(events):
        seen.extend(events)
        if any(e.type == "del" for e in events):
            done.set()

    observer.watch("/live/", on_events)
    lease = client.lease_grant(ttl=0.4)
    client.put("/live/pod0", b"up", lease=lease)
    # no keepalive -> server must expire the lease and push the DELETE
    assert done.wait(3.0), "expected lease-expiry DELETE push, saw %s" % seen
    types = [(e.type, e.key) for e in seen]
    assert ("put", "/live/pod0") in types and ("del", "/live/pod0") in types
    observer.close()


def test_lease_keeper_keeps_alive(server, client):
    lease = client.lease_grant(ttl=0.5)
    client.put("/hb/k", b"v", lease=lease)
    keeper = LeaseKeeper(client, lease, ttl=0.5)
    time.sleep(1.5)  # several TTLs
    assert client.get("/hb/k") == b"v"
    keeper.stop(revoke=True)
    assert client.get("/hb/k") is None


_FROZEN_TOGETHER = """
import sys, time
sys.path.insert(0, %r)
from edl_tpu.store import LeaseKeeper, StoreClient, StoreServer
srv = StoreServer(host="127.0.0.1", port=0).start()
client = StoreClient(srv.endpoint, timeout=5.0)
lease = client.lease_grant(ttl=1.5)
client.put("/hb/k", b"v", lease=lease)
lost = []
keeper = LeaseKeeper(client, lease, ttl=1.5, on_lost=lambda: lost.append(1))
print("READY", flush=True)
sys.stdin.readline()            # the test froze and thawed us meanwhile
time.sleep(1.0)                 # let a late sweep / a late keepalive land
print("LOST" if lost or client.get("/hb/k") is None else "KEPT", flush=True)
"""


def test_frozen_process_does_not_expire_its_own_leases():
    """The launcher's shape: store server and lease owner in ONE process.
    Freeze the whole process past the TTL (a TPU runtime start does that to
    a whole VM): the serve loop must give the owners the lost time back
    instead of waking, sweeping, and expiring leases whose keepalive
    thread was exactly as frozen."""
    import os
    import signal
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _FROZEN_TOGETHER % root],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(0.6)  # mid-interval: the next keepalive is not yet due
        proc.send_signal(signal.SIGSTOP)
        time.sleep(3.0)  # two TTLs
        proc.send_signal(signal.SIGCONT)
        proc.stdin.write("go\n")
        proc.stdin.flush()
        assert proc.stdout.readline().strip() == "KEPT"
    finally:
        proc.kill()
        proc.wait()


def test_watch_backlog_replay(server, client):
    client.put("/w/a", b"1")
    client.put("/w/b", b"2")
    got = []
    saw_c = threading.Event()

    def cb(events):
        got.extend(events)
        if any(e.key == "/w/c" for e in events):
            saw_c.set()

    # start_rev=0 replays the full retained history before live events
    client.watch("/w/", cb, start_rev=0)
    client.put("/w/c", b"3")
    assert saw_c.wait(3.0)
    assert [e.key for e in got] == ["/w/a", "/w/b", "/w/c"]
    assert got[-1].value == b"3"


def test_watch_compacted_start_rev_delivers_resync(monkeypatch):
    monkeypatch.setattr(StoreState, "HISTORY_LIMIT", 4)
    srv = StoreServer(host="127.0.0.1", port=0).start()
    try:
        c = StoreClient(srv.endpoint, timeout=5)
        for i in range(10):  # blow past the 4-event history ring
            c.put("/c/k%d" % i, b"%d" % i)
        got = []
        arrived = threading.Event()

        def cb(events):
            got.extend(events)
            arrived.set()

        c.watch("/c/", cb, start_rev=0)
        assert arrived.wait(3.0)
        assert got[0].type == RESYNC and got[0].key == "/c/"
        # consumer contract: re-read current state after a resync
        kvs, _ = c.range("/c/")
        assert len(kvs) == 10
        c.close()
    finally:
        srv.stop()


def test_client_reconnect_resumes_watch(server):
    client = StoreClient(server.endpoint, timeout=5)
    got = []
    lock = threading.Lock()

    def cb(events):
        with lock:
            got.extend(events)

    client.watch("/r/", cb)
    client.put("/r/a", b"1")
    # sever the connection underneath the client
    import socket as _socket

    client._sock.shutdown(_socket.SHUT_RDWR)
    deadline = time.time() + 5
    while time.time() < deadline:
        try:
            client.put("/r/b", b"2")
            break
        except EdlStoreError:
            time.sleep(0.1)
    deadline = time.time() + 5
    while time.time() < deadline:
        with lock:
            keys = [e.key for e in got if e.type != RESYNC]
        if "/r/b" in keys:
            break
        time.sleep(0.05)
    assert "/r/a" in keys and "/r/b" in keys, got
    client.close()


class TestDurability:
    """Snapshot/WAL persistence (round-3): the reference's control plane
    survives because etcd is disk-persistent and restartable; the in-tree
    store earns the same property with the C++ master's Save/Load pattern."""

    def test_snapshot_roundtrip_preserves_revs_leases_keys(self):
        clock = FakeClock()
        st = StoreState(clock=clock)
        lease = st.lease_grant(5.0)
        st.put("/j/a", b"1", lease)
        st.put("/j/b", b"2")
        st.put("/j/b", b"3")  # mod_rev advances past create_rev
        st.delete("/j/gone") if st.get("/j/gone") else None
        snap = st.to_snapshot()

        st2 = StoreState(clock=clock)
        st2.load_snapshot(snap)
        assert st2.revision == st.revision
        assert st2.get("/j/a") == st.get("/j/a")
        assert st2.get("/j/b") == st.get("/j/b")
        # CAS against the pre-snapshot mod_rev still works
        _, mod_rev, _ = st2.get("/j/b")
        ok, _ = st2.cas("/j/b", mod_rev, b"4")
        assert ok
        # the restored lease still deletes its keys on expiry
        clock.now += 6.0
        evs = st2.expire_leases()
        assert [e.key for e in evs] == ["/j/a"]
        # pre-restore history is gone: resume must demand a resync
        with pytest.raises(ValueError):
            st2.history_since(1, "/j/")

    def test_journal_replay_reproduces_state_and_revisions(self):
        clock = FakeClock()
        src = StoreState(clock=clock)
        journal = []
        lease = src.lease_grant(3.0)
        journal.append({"op": "grant", "id": lease, "ttl": 3.0})
        journal.append({"op": "ev", **src.put("/k/held", b"x", lease).to_wire()})
        journal.append({"op": "ev", **src.put("/k/perm", b"y").to_wire()})
        clock.now += 4.0
        journal.extend({"op": "ev", **e.to_wire()} for e in src.expire_leases())
        journal.append({"op": "ev", **src.put("/k/perm", b"z").to_wire()})

        dst = StoreState(clock=clock)
        for entry in journal:
            dst.apply_journal(entry)
        assert dst.revision == src.revision
        assert dst.get("/k/held") is None  # expiry delete replayed
        assert dst.get("/k/perm") == src.get("/k/perm")
        # a fresh lease id never collides with a replayed one
        assert dst.lease_grant(1.0) == src.lease_grant(1.0)

    def test_server_restart_recovers_clean_stop(self, tmp_path):
        data = str(tmp_path / "d")
        srv = StoreServer(host="127.0.0.1", port=0, data_dir=data).start()
        c = StoreClient(srv.endpoint, timeout=5.0)
        lease = c.lease_grant(30.0)
        c.put("/j/leased", b"L", lease=lease)
        rev = c.put("/j/perm", b"P")
        c.close()
        srv.stop()

        srv2 = StoreServer(host="127.0.0.1", port=0, data_dir=data).start()
        try:
            c2 = StoreClient(srv2.endpoint, timeout=5.0)
            assert c2.get("/j/perm") == b"P"
            assert c2.get("/j/leased") == b"L"
            got, mod_rev = c2.get_with_rev("/j/perm")
            assert mod_rev == rev
            assert c2.lease_keepalive(lease)  # lease survived the restart
            assert c2.cas("/j/perm", mod_rev, b"P2")
            c2.close()
        finally:
            srv2.stop()

    def test_server_sigkill_recovery_via_wal(self, tmp_path):
        """Hard-kill the daemon (no clean-stop snapshot): every acked
        mutation must come back from the journal."""
        import os
        import signal
        import subprocess
        import sys

        from edl_tpu.utils.net import find_free_ports, wait_until_alive

        data = str(tmp_path / "d")
        port = find_free_ports(1)[0]
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cmd = [sys.executable, "-m", "edl_tpu.store.server",
               "--host", "127.0.0.1", "--port", str(port), "--data_dir", data]
        env = dict(os.environ, PYTHONPATH=repo)
        proc = subprocess.Popen(cmd, env=env)
        try:
            assert wait_until_alive("127.0.0.1:%d" % port, timeout=10.0)
            c = StoreClient("127.0.0.1:%d" % port, timeout=5.0)
            lease = c.lease_grant(30.0)
            c.put("/j/leased", b"L", lease=lease)
            rev = c.put("/j/perm", b"P")

            seen = []
            watch = c.watch("/j/", lambda evs: seen.extend(evs))

            proc.send_signal(signal.SIGKILL)
            proc.wait()
            proc = subprocess.Popen(cmd, env=env)
            assert wait_until_alive("127.0.0.1:%d" % port, timeout=10.0)

            # same client object rides the bounce (reference etcd parity)
            deadline = time.time() + 10.0
            while time.time() < deadline:
                try:
                    if c.get("/j/perm") == b"P":
                        break
                except Exception:
                    pass
                time.sleep(0.1)
            assert c.get("/j/perm") == b"P"
            assert c.get("/j/leased") == b"L"
            _, mod_rev = c.get_with_rev("/j/perm")
            assert mod_rev == rev
            assert c.lease_keepalive(lease)
            # the resumed watch still delivers post-restart events
            c.put("/j/after", b"A")
            deadline = time.time() + 5.0
            while time.time() < deadline and not any(
                e.key == "/j/after" for e in seen
            ):
                time.sleep(0.05)
            assert any(e.key == "/j/after" for e in seen)
            watch.cancel()
            c.close()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    def test_wal_compaction_threshold_and_recovery(self, tmp_path, monkeypatch):
        """Crossing _COMPACT_EVERY snapshots and truncates the journal;
        recovery from the compacted state plus the post-compaction tail
        still reproduces everything."""
        import os

        from edl_tpu.store import server as server_mod

        monkeypatch.setattr(server_mod, "_COMPACT_EVERY", 10)
        data = str(tmp_path / "d")
        srv = StoreServer(host="127.0.0.1", port=0, data_dir=data).start()
        c = StoreClient(srv.endpoint, timeout=5.0)
        for i in range(25):  # > 2 compactions
            c.put("/j/k%02d" % i, str(i).encode())
        wal_size = os.path.getsize(os.path.join(data, "wal.bin"))
        snap_size = os.path.getsize(os.path.join(data, "snapshot.bin"))
        assert snap_size > 0
        # journal was truncated at the last compaction: far smaller than
        # 25 entries' worth
        full_entry = len(b"x") + 60  # rough frame size floor
        assert wal_size < 25 * full_entry
        c.close()
        srv.stop()

        srv2 = StoreServer(host="127.0.0.1", port=0, data_dir=data).start()
        try:
            c2 = StoreClient(srv2.endpoint, timeout=5.0)
            for i in range(25):
                assert c2.get("/j/k%02d" % i) == str(i).encode()
            c2.close()
        finally:
            srv2.stop()


class TestReplicaRecovery:
    """Store-HOST loss (round-3 missing #4): snapshots replicate to a
    shared-storage dir at every compaction, and a replacement store on a
    FRESH host (empty data_dir) seeds itself from the replica."""

    def test_host_loss_recovers_from_replica(self, tmp_path):
        data_a = str(tmp_path / "host_a")
        replica = str(tmp_path / "shared")
        srv = StoreServer(
            host="127.0.0.1", port=0, data_dir=data_a, replica_dir=replica
        ).start()
        try:
            c = StoreClient(srv.endpoint, timeout=5.0)
            rev = c.put("/j/model", b"step-400")
            c.put("/j/cluster", b"world-4")
            srv._compact()  # deterministic stand-in for the timer trigger
            c.close()
        finally:
            srv.stop()
        # the HOST is gone: its local disk state with it
        import shutil

        shutil.rmtree(data_a)

        data_b = str(tmp_path / "host_b")  # brand-new host, empty disk
        srv2 = StoreServer(
            host="127.0.0.1", port=0, data_dir=data_b, replica_dir=replica
        ).start()
        try:
            c2 = StoreClient(srv2.endpoint, timeout=5.0)
            assert c2.get("/j/model") == b"step-400"
            assert c2.get("/j/cluster") == b"world-4"
            _, mod_rev = c2.get_with_rev("/j/model")
            assert mod_rev == rev  # revisions survive the host move
            assert c2.cas("/j/model", mod_rev, b"step-401")
            c2.close()
        finally:
            srv2.stop()

    def test_replica_faults_do_not_break_live_store(self, tmp_path):
        data = str(tmp_path / "d")
        bad_replica = str(tmp_path / "blocked")
        with open(bad_replica, "w") as f:
            f.write("a FILE where the replica dir should be")
        srv = StoreServer(
            host="127.0.0.1", port=0, data_dir=data, replica_dir=bad_replica
        ).start()
        try:
            c = StoreClient(srv.endpoint, timeout=5.0)
            c.put("/j/k", b"v")
            srv._compact()  # replica write fails; live store keeps serving
            assert c.get("/j/k") == b"v"
            c.close()
        finally:
            srv.stop()

    @pytest.mark.slow
    def test_job_resumes_after_store_host_move(self, tmp_path):
        """Full-stack: a launcher-driven job survives its store HOST
        dying — a replacement store (fresh dir, same replica) comes up on
        the same endpoint and the job completes."""
        import os
        import signal
        import subprocess
        import sys

        from edl_tpu.utils.net import find_free_ports, wait_until_alive

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        port = find_free_ports(1)[0]
        endpoint = "127.0.0.1:%d" % port
        replica = str(tmp_path / "shared")
        env = dict(
            os.environ, PYTHONPATH=repo,
            EDL_STORE_REPLICA_INTERVAL="0.2",  # tight staleness for the test
            TEST_OUT_DIR=str(tmp_path / "out"),
            TEST_EXIT_AFTER="25",
        )
        (tmp_path / "out").mkdir()

        def store_proc(data_dir):
            return subprocess.Popen(
                [sys.executable, "-m", "edl_tpu.store.server",
                 "--host", "127.0.0.1", "--port", str(port),
                 "--data_dir", data_dir, "--replica_dir", replica],
                env=env,
            )

        toy = os.path.join(repo, "tests", "toy_worker.py")
        store = store_proc(str(tmp_path / "host_a"))
        launcher = None
        try:
            assert wait_until_alive(endpoint, timeout=10.0)
            launcher = subprocess.Popen(
                [sys.executable, "-m", "edl_tpu.launch",
                 "--job_id", "movejob", "--store", endpoint,
                 "--nodes_range", "1:1", "--ttl", "2.0", toy],
                env=env, cwd=repo,
            )
            # let the job register + publish, then kill the store HOST
            deadline = time.time() + 20
            while time.time() < deadline and not any(
                n.startswith("run.") for n in os.listdir(tmp_path / "out")
            ):
                time.sleep(0.2)
            time.sleep(1.0)  # give the replica timer a compaction
            store.send_signal(signal.SIGKILL)
            store.wait()
            store = store_proc(str(tmp_path / "host_b"))  # fresh host
            assert wait_until_alive(endpoint, timeout=10.0)
            assert launcher.wait(timeout=90) == 0
        finally:
            for p in (launcher, store):
                if p is not None and p.poll() is None:
                    p.kill()
                    p.wait()


class TestWarmStandby:
    """Control-plane HA: live snapshot+WAL replication to a warm standby,
    epoch-fenced promotion on primary death, stale-primary fencing, and
    client failover through the ordered endpoint list (DESIGN.md
    "Control-plane HA")."""

    @staticmethod
    def _pair(tmp_path, grace=0.8):
        primary = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "p")
        ).start()
        standby = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "s"),
            follow=primary.endpoint, priority=1, failover_grace=grace,
        ).start()
        deadline = time.time() + 15
        while time.time() < deadline and not standby._has_state:
            time.sleep(0.02)
        assert standby._has_state, "standby never bootstrapped"
        return primary, standby

    @staticmethod
    def _wait_promoted(standby, timeout=15.0):
        deadline = time.time() + timeout
        while time.time() < deadline and standby.role != "primary":
            time.sleep(0.02)
        assert standby.role == "primary", "standby never promoted"

    def test_replicates_live_and_rejects_clients_while_standby(self, tmp_path):
        from edl_tpu.rpc.wire import request_once

        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(primary.endpoint, timeout=5.0)
            rev = c.put("/r/k", b"v")
            deadline = time.time() + 10
            while time.time() < deadline and standby._state.get("/r/k") is None:
                time.sleep(0.02)
            got = standby._state.get("/r/k")
            assert got is not None and got[0] == b"v" and got[1] == rev
            # a standby replicates; it does not serve (the wire error
            # names the reason so clients advance their endpoint ring)
            resp = request_once(
                standby.endpoint,
                {"i": 1, "m": "put", "k": "/r/x", "v": b"y", "l": 0},
                timeout=2.0,
            )
            assert resp["ok"] is False
            assert resp["err"]["etype"] == "EdlNotPrimaryError"
            # liveness probes still answer, reporting the standby role
            status = request_once(
                standby.endpoint, {"i": 2, "m": "repl_status"}, timeout=2.0
            )
            assert status["ok"] and status["role"] == "standby"
            c.close()
        finally:
            standby.stop()
            primary.stop()

    def test_promotion_bumps_epoch_and_client_fails_over(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        old_epoch = primary._state.epoch
        try:
            c = StoreClient(
                "%s,%s" % (primary.endpoint, standby.endpoint), timeout=5.0
            )
            rev = c.put("/f/acked", b"pre-kill")
            time.sleep(0.3)  # let the tail drain
            primary.kill()  # crash, not clean stop
            self._wait_promoted(standby)
            assert standby._state.epoch == old_epoch + 1
            # the same client object rides the failover: the acked write
            # is there with its original mod_rev, and a CAS against it
            # still lands (revision continuity across the failover)
            resp = c.retrying("get", k="/f/acked")
            assert resp["v"] == b"pre-kill" and resp["mr"] == rev
            assert c.cas("/f/acked", rev, b"post-failover")
            c.close()
        finally:
            standby.stop()

    def test_watch_resumes_exactly_once_across_failover(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(
                "%s,%s" % (primary.endpoint, standby.endpoint), timeout=5.0
            )
            events = []
            c.watch("/w/", lambda evs: events.extend(evs))
            for i in range(3):
                c.put("/w/k%d" % i, b"%d" % i)
            time.sleep(0.4)  # replication tail + watch delivery
            primary.kill()
            c.retrying("put", k="/w/after", v=b"x", l=0)
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                e.key == "/w/after" for e in events
            ):
                time.sleep(0.05)
            keys = [(e.type, e.key) for e in events]
            # the promoted standby's replicated history covered the
            # client's resume revision: no resync, no gap, no duplicate
            assert keys == [
                ("put", "/w/k0"), ("put", "/w/k1"), ("put", "/w/k2"),
                ("put", "/w/after"),
            ], keys
            c.close()
        finally:
            standby.stop()

    def test_resurrected_stale_primary_is_fenced(self, tmp_path):
        from edl_tpu.utils.exceptions import EdlStoreError

        primary, standby = self._pair(tmp_path)
        pport = primary.port
        try:
            c = StoreClient(
                "%s,%s" % (primary.endpoint, standby.endpoint), timeout=5.0
            )
            c.put("/s/k", b"v")
            time.sleep(0.3)
            primary.kill()
            self._wait_promoted(standby)
            # the old primary comes back on its stale state at the same
            # endpoint; the promoted primary's fence campaign must shut
            # it out before a fresh client can write to it
            old = StoreServer(
                host="127.0.0.1", port=pport, data_dir=str(tmp_path / "p")
            ).start()
            try:
                deadline = time.time() + 15
                while time.time() < deadline and old._fenced_by is None:
                    time.sleep(0.05)
                assert old._fenced_by == standby._state.epoch
                probe = StoreClient(old.endpoint, timeout=3.0, reconnect=False)
                with pytest.raises(EdlStoreError):
                    probe.request("put", k="/s/intruder", v=b"x", l=0)
                probe.close()
            finally:
                old.stop()
            c.close()
        finally:
            standby.stop()

    def test_stale_primary_restarts_fenced_before_its_first_request(
        self, tmp_path
    ):
        """A primary restarted on stale state beside a standby that
        promoted meanwhile is fenced when its constructor returns, not a
        fence-campaign pass later: it asks the members its own state
        names for their epoch before it serves."""
        primary, standby = self._pair(tmp_path)
        pport = primary.port
        try:
            c = StoreClient(
                "%s,%s" % (primary.endpoint, standby.endpoint), timeout=5.0
            )
            c.put("/s/k", b"v")
            time.sleep(0.3)
            c.close()
            primary.kill()
            self._wait_promoted(standby)
            old = StoreServer(
                host="127.0.0.1", port=pport, data_dir=str(tmp_path / "p")
            )
            try:
                assert old._fenced_by == standby._state.epoch
            finally:
                old.start().stop()
        finally:
            standby.stop()

    def test_equal_epoch_fence_tie_breaks_deterministically(self, tmp_path):
        """Two standbys promoted concurrently land on the SAME epoch;
        strictly-greater comparisons can't resolve that, so the fence
        protocol tie-breaks on advertise endpoint (lexically larger
        loses, applied identically on both sides) — exactly one
        survives."""
        from edl_tpu.store import replica

        a = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "a")
        ).start()
        b = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "b")
        ).start()
        try:
            for srv in (a, b):
                srv._state.set_epoch(1)  # the concurrent-promotion state
            winner, loser = sorted((a, b), key=lambda s: s._advertise)
            # the winner's campaign reaches the loser: it self-fences
            resp = replica.send_fence(
                loser._advertise, 1, sender=winner._advertise, timeout=2.0
            )
            assert resp is not None and resp["fenced"] is True
            assert loser._fenced_by == 1
            # the loser's campaign reaching the winner leaves it serving;
            # the reply (equal epoch, primary, not fenced) is what makes
            # the caller apply the same rule and stand down
            resp = replica.send_fence(
                winner._advertise, 1, sender=loser._advertise, timeout=2.0
            )
            assert resp is not None and resp["fenced"] is False
            assert resp["role"] == "primary" and resp["e"] == 1
            assert winner._fenced_by is None
        finally:
            a.stop()
            b.stop()

    def test_standby_promotes_despite_standby_peers_in_follow_list(self, tmp_path):
        """A follow list naming fellow standbys (the natural full member
        list) must not wedge promotion: contacting a standby (sync
        rejected) is not contact with a primary and must not reset the
        grace clock."""
        primary = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "p")
        ).start()
        # a peer standby that will never promote itself (huge grace)
        peer = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "peer"),
            follow=primary.endpoint, priority=9, failover_grace=60.0,
        ).start()
        candidate = None
        try:
            deadline = time.time() + 15
            while time.time() < deadline and not peer._has_state:
                time.sleep(0.02)
            candidate = StoreServer(
                host="127.0.0.1", port=0, data_dir=str(tmp_path / "c"),
                follow="%s,%s" % (primary.endpoint, peer.endpoint),
                priority=1, failover_grace=0.8,
            ).start()
            deadline = time.time() + 15
            while time.time() < deadline and not candidate._has_state:
                time.sleep(0.02)
            assert candidate._has_state
            primary.kill()
            self._wait_promoted(candidate)
            assert candidate._state.epoch >= 1
        finally:
            if candidate is not None:
                candidate.stop()
            peer.stop()

    def test_demoted_primary_resyncs_as_standby(self, tmp_path):
        """The 'demote/resync' path: the dead ex-primary rejoins AS A
        STANDBY of the new primary and discards its diverged state for
        a full re-sync of the newer generation."""
        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(
                "%s,%s" % (primary.endpoint, standby.endpoint), timeout=5.0
            )
            c.put("/d/k", b"old")
            time.sleep(0.3)
            primary.kill()
            self._wait_promoted(standby)
            c.retrying("put", k="/d/k", v=b"new", l=0)
            rejoined = StoreServer(
                host="127.0.0.1", port=0, data_dir=str(tmp_path / "p"),
                follow=standby.endpoint, priority=2, failover_grace=5.0,
            ).start()
            try:
                deadline = time.time() + 15
                while time.time() < deadline and (
                    rejoined._state.get("/d/k") is None
                    or rejoined._state.get("/d/k")[0] != b"new"
                ):
                    time.sleep(0.05)
                assert rejoined.role == "standby"
                assert rejoined._state.get("/d/k")[0] == b"new"
                assert rejoined._state.epoch == standby._state.epoch
            finally:
                rejoined.stop()
            c.close()
        finally:
            standby.stop()


class TestEpochState:
    def test_epoch_survives_snapshot_roundtrip(self):
        st = StoreState()
        st.set_epoch(3)
        st.put("/k", b"v")
        st2 = StoreState()
        st2.load_snapshot(st.to_snapshot())
        assert st2.epoch == 3

    def test_epoch_journal_op_and_monotonicity(self):
        st = StoreState()
        st.apply_journal({"op": "epoch", "e": 5})
        assert st.epoch == 5
        st.apply_journal({"op": "epoch", "e": 2})  # never rolls back
        assert st.epoch == 5

    def test_reset_lease_deadlines_counts_and_extends(self):
        clock = FakeClock()
        st = StoreState(clock=clock)
        l1 = st.lease_grant(5.0)
        st.lease_grant(7.0)
        clock.now += 4.9  # one tick from expiry
        assert st.reset_lease_deadlines() == 2
        clock.now += 4.9  # past the ORIGINAL deadline, inside the fresh one
        assert st.expire_leases() == []
        assert st.lease_keepalive(l1)


def test_extend_lease_deadlines_moves_every_lease():
    clock = FakeClock()
    st = StoreState(clock=clock)
    lease = st.lease_grant(5.0)
    clock.now += 4.9
    assert st.extend_lease_deadlines(7.0) == 1
    clock.now += 7.0  # past the original deadline, inside the extended one
    assert st.expire_leases() == []
    clock.now += 0.2
    assert st.expire_leases_with_ids()[1] == [lease]


def test_salvage_wal_any_truncation_yields_valid_prefix():
    """Satellite: truncate a recorded WAL at EVERY byte offset; the
    salvaged entries must always be an exact, in-order prefix of what was
    journaled — no exception, no skipped entry, no trailing garbage."""
    from edl_tpu.rpc.wire import pack_frame

    entries = [
        {"op": "grant", "id": 1, "ttl": 2.5},
        {"op": "ev", "t": "put", "k": "/w/a", "v": b"1", "r": 1, "l": 1},
        {"op": "ev", "t": "put", "k": "/w/b", "v": b"x" * 100, "r": 2, "l": 0},
        {"op": "revoke", "id": 1},
        {"op": "ev", "t": "del", "k": "/w/a", "v": None, "r": 3, "l": 0},
    ]
    frames = [pack_frame(e, fault=False) for e in entries]
    wal = b"".join(frames)
    boundaries = []
    offset = 0
    for frame in frames:
        offset += len(frame)
        boundaries.append(offset)
    for cut in range(len(wal) + 1):
        salvaged = list(StoreServer._salvage_wal(wal[:cut]))
        want = sum(1 for b in boundaries if b <= cut)
        assert len(salvaged) == want, "cut=%d" % cut
        assert salvaged == entries[:want], "cut=%d" % cut
        revs = [e["r"] for e in salvaged if e.get("op") == "ev"]
        assert revs == sorted(revs), "cut=%d: revisions not monotonic" % cut


def test_corrupt_snapshot_degrades_to_journal_recovery(tmp_path):
    """A torn snapshot (non-atomic replica fs caught mid-replace) must not
    crash-loop the store: it is set aside and recovery continues from the
    WAL alone."""
    import os

    data = str(tmp_path / "d")
    os.makedirs(data)
    with open(os.path.join(data, "snapshot.bin"), "wb") as f:
        f.write(b"\x93torn-msgpack-garbage")
    srv = StoreServer(host="127.0.0.1", port=0, data_dir=data).start()
    try:
        c = StoreClient(srv.endpoint, timeout=5.0)
        c.put("/j/after-corruption", b"ok")
        assert c.get("/j/after-corruption") == b"ok"
        c.close()
    finally:
        srv.stop()
    assert os.path.exists(os.path.join(data, "snapshot.bin.corrupt"))


# ---------------------------------------------------------------------------
# Semi-sync replication ack + group commit (DESIGN.md "Sharded control plane")
# ---------------------------------------------------------------------------


class TestSemiSync:
    """The PR-3 replication stream made semi-synchronous: a mutation's
    ack is held until every live standby has applied+journaled it — the
    `edl_store_repl_unacked_bytes` window is DRAINED TO ZERO before the
    client hears ok, deleting the known store-failover acked-write-loss
    flake at its root. A bounded escape hatch degrades to async,
    metered."""

    def _pair(self, tmp_path, **primary_kw):
        primary = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "p"),
            **primary_kw,
        ).start()
        standby = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "s"),
            follow=primary.endpoint, failover_grace=30.0,
        ).start()
        deadline = time.time() + 15
        while time.time() < deadline and not standby._has_state:
            time.sleep(0.02)
        assert standby._has_state, "standby never bootstrapped"
        return primary, standby

    def test_ack_held_until_standby_applied_and_window_drained(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        client = StoreClient(primary.endpoint, timeout=5)
        try:
            for i in range(10):
                client.put("/j/svc/k%d" % i, b"v%d" % i)
                # the moment the ack lands, the write is already ON the
                # standby (applied, not just kernel-buffered)...
                got = standby._state.get("/j/svc/k%d" % i)
                assert got is not None and got[0] == b"v%d" % i
                # ...and the loss-window gauge reads zero: nothing acked
                # is in flight
                assert primary._repl_unacked_bytes() == 0.0
        finally:
            client.close()
            primary.stop()
            standby.stop()

    def test_wedged_standby_degrades_within_timeout_and_is_metered(
        self, tmp_path
    ):
        primary, standby = self._pair(tmp_path, repl_sync_timeout=0.4)
        # wedge the standby's apply path: frames arrive, acks never come
        standby._repl_apply = lambda frame: None
        client = StoreClient(primary.endpoint, timeout=5)
        try:
            before = primary._m_sync_degraded.value(cause="timeout")
            t0 = time.monotonic()
            client.put("/j/svc/slow", b"x")
            held = time.monotonic() - t0
            # held for ~the escape-hatch timeout, not forever
            assert 0.2 <= held < 3.0, held
            assert primary._m_sync_degraded.value(cause="timeout") > before
            # the window is OPEN now — exactly what the gauge + the
            # repl-sync-degraded monitor rule surface
            assert primary._repl_unacked_bytes() > 0
        finally:
            client.close()
            primary.stop()
            standby.stop()

    def test_dead_standby_falls_back_to_async(self, tmp_path):
        primary, standby = self._pair(tmp_path, repl_sync_timeout=0.5)
        standby.kill()
        time.sleep(0.2)  # let the primary reap the dead subscriber conn
        client = StoreClient(primary.endpoint, timeout=5)
        try:
            t0 = time.monotonic()
            client.put("/j/svc/after-death", b"x")
            # no live subscriber -> nothing to wait for (MySQL-semisync
            # fallback semantics); the commit must not eat the timeout
            assert time.monotonic() - t0 < 0.4
        finally:
            client.close()
            primary.stop()
            standby.stop()

    def test_semi_sync_off_acks_without_standby_ack(self, tmp_path):
        primary, standby = self._pair(tmp_path, repl_sync_timeout=0.0)
        standby._repl_apply = lambda frame: None  # acks never come
        client = StoreClient(primary.endpoint, timeout=5)
        try:
            t0 = time.monotonic()
            client.put("/j/svc/async", b"x")
            assert time.monotonic() - t0 < 0.3  # pre-shard async behavior
        finally:
            client.close()
            primary.stop()
            standby.stop()

    def test_watch_exactly_once_in_revision_order_under_held_commits(
        self, tmp_path
    ):
        """Writers hammer a semi-sync pair while a watch is live: every
        event arrives exactly once, in revision order — the FIFO
        release queue and the registration high-water mark under test."""
        primary, standby = self._pair(tmp_path)
        client = StoreClient(primary.endpoint, timeout=5)
        seen = []
        try:
            rows, rev = client.range("/j/w/")
            client.watch("/j/w/", lambda evs: seen.extend(evs), start_rev=rev)

            def writer(tag):
                c = StoreClient(primary.endpoint, timeout=5)
                try:
                    for i in range(20):
                        c.put("/j/w/%s%d" % (tag, i), b"x")
                finally:
                    c.close()

            threads = [
                threading.Thread(target=writer, args=(t,)) for t in "ab"
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            deadline = time.time() + 10
            while time.time() < deadline and len(seen) < 40:
                time.sleep(0.05)
            assert len(seen) == 40, len(seen)
            revs = [e.rev for e in seen]
            assert revs == sorted(revs), "events out of revision order"
            assert len({e.key for e in seen}) == 40, "duplicate delivery"
        finally:
            client.close()
            primary.stop()
            standby.stop()


def test_lease_renew_batch_op(server, client):
    l1 = client.lease_grant(2.0)
    l2 = client.lease_grant(2.0)
    assert client.lease_keepalive_batch([l1, 9999, l2]) == [True, False, True]


def test_lease_keepers_coalesce_into_batched_renews(server):
    """10 keepers on one client issue ONE batched renew RPC per tick,
    not 10 keepalive streams — the client-side control-plane QPS cut."""
    client = StoreClient(server.endpoint, timeout=5)
    batch_calls = []
    real_batch = client.lease_keepalive_batch
    client.lease_keepalive_batch = lambda ls: (
        batch_calls.append(len(ls)) or real_batch(ls)
    )
    try:
        keepers = []
        for i in range(10):
            lease = client.lease_grant(0.9)
            client.put("/j/coal/k%d" % i, b"x", lease=lease)
            keepers.append(LeaseKeeper(client, lease, 0.9))
        time.sleep(1.2)  # ~4 renew intervals
        for i in range(10):
            assert client.get("/j/coal/k%d" % i) == b"x"
        assert batch_calls, "renew coalescer never ran"
        # coalesced: a handful of batch RPCs, most covering all 10 leases
        assert len(batch_calls) <= 8, batch_calls
        assert max(batch_calls) == 10, batch_calls
        for k in keepers:
            k.stop()
    finally:
        client.close()


def test_lease_renewer_falls_back_when_batch_unsupported(server):
    """Against a server that predates lease_renew_batch (the native C++
    twin), the renewer degrades to per-lease keepalives."""
    client = StoreClient(server.endpoint, timeout=5)

    def no_batch(ls):
        raise EdlStoreError("unknown method 'lease_renew_batch'")

    client.lease_keepalive_batch = no_batch
    try:
        lease = client.lease_grant(0.6)
        client.put("/j/fb/k", b"x", lease=lease)
        keeper = LeaseKeeper(client, lease, 0.6)
        time.sleep(1.0)
        assert client.get("/j/fb/k") == b"x", "fallback keepalive failed"
        keeper.stop()
    finally:
        client.close()


def test_watch_fanout_batches_one_frame_per_connection(server):
    """Two watches on ONE connection whose prefixes both match an event
    get a single batched `wb` frame, and both callbacks fire."""
    import socket as _socket

    from edl_tpu.rpc.wire import FrameReader, pack_frame
    from edl_tpu.utils.net import split_endpoint

    sock = _socket.create_connection(split_endpoint(server.endpoint), 5)
    reader = FrameReader(fault=False)

    def req(payload):
        sock.sendall(pack_frame(payload, fault=False))
        while True:
            for frame in reader.feed(sock.recv(65536)):
                return frame

    assert req({"i": 1, "m": "watch", "p": "/a/", "wid": 11})["ok"]
    assert req({"i": 2, "m": "watch", "p": "/a/b/", "wid": 12})["ok"]
    writer = StoreClient(server.endpoint, timeout=5)
    try:
        writer.put("/a/b/x", b"1")  # matches BOTH watches
        deadline = time.time() + 5
        frames = []
        sock.settimeout(1.0)
        while time.time() < deadline and not frames:
            try:
                frames.extend(reader.feed(sock.recv(65536)))
            except _socket.timeout:
                pass
        assert frames, "no fan-out frame arrived"
        (frame,) = frames
        assert "wb" in frame, frame  # batched, not two w-frames
        assert sorted(wid for wid, _evs in frame["wb"]) == [11, 12]
        for _wid, evs in frame["wb"]:
            assert evs[0]["k"] == "/a/b/x"
    finally:
        writer.close()
        sock.close()


# ---------------------------------------------------------------------------
# Sharded store client (consistent-hash keyspace partitioning)
# ---------------------------------------------------------------------------


class TestSharded:
    """ShardedStoreClient routes by the first-two-component token on
    the consistent-hash ring, fans watches/ranges out where the prefix
    spans shards, virtualizes leases per shard, and discovers the
    topology from the replicated /store/shards/ map via connect_store."""

    @pytest.fixture()
    def fleet(self):
        from edl_tpu.store import shard as shard_mod

        servers = [
            StoreServer(host="127.0.0.1", port=0, name="store-%d" % i).start()
            for i in range(3)
        ]
        boot = StoreClient(servers[0].endpoint, timeout=5)
        shard_mod.publish_shard_map(boot, [[s.endpoint] for s in servers])
        boot.close()
        yield servers
        for s in servers:
            s.stop()

    @pytest.fixture()
    def sharded(self, fleet):
        from edl_tpu.store import ShardedStoreClient, connect_store

        client = connect_store(fleet[0].endpoint, timeout=5)
        assert isinstance(client, ShardedStoreClient)
        assert client.num_shards == 3
        yield client
        client.close()

    def test_connect_store_returns_plain_client_unsharded(self, server):
        from edl_tpu.store import connect_store

        client = connect_store(server.endpoint, timeout=5)
        assert isinstance(client, StoreClient)
        client.close()

    def test_token_coherence_and_spread(self, sharded):
        from edl_tpu.store import shard as shard_mod

        keys = [
            "/job%02d/%s/p%d" % (j, svc, i)
            for j in range(12)
            for svc in ("heartbeat", "pods")
            for i in range(3)
        ]
        owners = {}
        for key in keys:
            token = shard_mod.route_token(key)
            shard = sharded.shard_of(key)
            assert owners.setdefault(token, shard) == shard, (
                "one token split across shards"
            )
        assert len(set(owners.values())) > 1, "ring never spread tokens"
        # system keys pin to the meta shard
        assert sharded.shard_of("/store/shards/000") == sharded._meta_name

    def test_crud_and_tokened_range(self, sharded):
        for i in range(6):
            sharded.put("/jobA/svc/k%d" % i, b"v%d" % i)
        assert sharded.get("/jobA/svc/k3") == b"v3"
        rows, rev = sharded.range("/jobA/svc/")
        assert [r[0] for r in rows] == ["/jobA/svc/k%d" % i for i in range(6)]
        assert rev > 0
        assert sharded.delete("/jobA/svc/k0")
        assert sharded.get("/jobA/svc/k0") is None
        assert sharded.delete_range("/jobA/svc/") == 5

    def test_fanout_range_merges_sorted(self, sharded):
        keys = ["/j%02d/m/x" % i for i in range(10)]
        for key in keys:
            sharded.put(key, b"1")
        rows, _rev = sharded.range("/j")
        got = [r[0] for r in rows]
        assert got == sorted(keys)

    def test_read_then_watch_on_tokened_prefix(self, sharded):
        sharded.put("/jobW/svc/a", b"1")
        rows, rev = sharded.range("/jobW/svc/")
        seen = []
        watch = sharded.watch(
            "/jobW/svc/", lambda evs: seen.extend(evs), start_rev=rev
        )
        sharded.put("/jobW/svc/b", b"2")
        deadline = time.time() + 5
        while time.time() < deadline and not seen:
            time.sleep(0.02)
        assert [e.key for e in seen] == ["/jobW/svc/b"]
        watch.cancel()

    def test_fanout_watch_spans_shards_and_rejects_start_rev(self, sharded):
        seen = []
        watch = sharded.watch("/", lambda evs: seen.extend(evs))
        sharded.put("/jobX/a/1", b"1")
        sharded.put("/jobY/b/2", b"2")
        deadline = time.time() + 5
        while time.time() < deadline and len(seen) < 2:
            time.sleep(0.02)
        assert sorted(e.key for e in seen) == ["/jobX/a/1", "/jobY/b/2"]
        watch.cancel()
        with pytest.raises(ValueError):
            sharded.watch("/", lambda evs: None, start_rev=7)

    def test_virtual_lease_spans_shards(self, sharded):
        lease = sharded.lease_grant(1.0)
        # pick two keys on DIFFERENT shards
        keys, shards_hit = [], set()
        i = 0
        while len(shards_hit) < 2 and i < 64:
            key = "/vjob%d/lease/k" % i
            if sharded.shard_of(key) not in shards_hit:
                shards_hit.add(sharded.shard_of(key))
                keys.append(key)
            i += 1
        for key in keys:
            sharded.put(key, b"leased", lease=lease)
        assert sharded.lease_keepalive(lease)
        assert sharded.lease_keepalive_batch([lease, 424242]) == [True, False]
        sharded.lease_revoke(lease)
        for key in keys:
            assert sharded.get(key) is None, "revoke missed a shard"

    def test_lease_expiry_is_shard_local(self, sharded):
        lease = sharded.lease_grant(0.5)
        sharded.put("/exp0/a/k", b"x", lease=lease)  # realizes ONE shard
        sharded.put("/exp0/a/k2", b"y", lease=lease)
        assert sharded.get("/exp0/a/k") == b"x"
        time.sleep(1.2)  # no keepalive: the shard-local lease expires
        assert sharded.get("/exp0/a/k") is None
        assert sharded.get("/exp0/a/k2") is None

    def test_retrying_routes_like_request(self, sharded):
        sharded.put("/jobR/svc/k", b"v")
        resp = sharded.retrying("get", k="/jobR/svc/k")
        assert resp["v"] == b"v"

    def test_registry_rides_sharded_client(self, sharded):
        """The whole discovery layer (register/watch/rank-race) works
        unchanged over the sharded client — the service prefix IS the
        routing token."""
        from edl_tpu.discovery.registry import Registry

        registry = Registry(sharded, "shardjob")
        events = []
        watch = registry.watch_service(
            "trainer",
            on_add=lambda m: events.append(("add", m.name)),
            on_remove=lambda m: events.append(("rm", m.name)),
        )
        reg = registry.register("trainer", "w0", b"addr", ttl=0.8)
        deadline = time.time() + 5
        while time.time() < deadline and ("add", "w0") not in events:
            time.sleep(0.02)
        assert ("add", "w0") in events
        won, _ = registry.register_if_absent("rank", "0", b"me", ttl=0.8)
        assert won is not None
        lost, holder = registry.register_if_absent("rank", "0", b"other", ttl=0.8)
        assert lost is None and holder == b"me"
        reg.stop()
        deadline = time.time() + 5
        while time.time() < deadline and ("rm", "w0") not in events:
            time.sleep(0.02)
        assert ("rm", "w0") in events
        won.stop()
        watch.cancel()

    def test_per_shard_failover_with_zero_acked_loss(self, tmp_path):
        """Two semi-sync shards, both primaries killed: each standby
        promotes with its own epoch; an acked write on EACH shard
        survives with its original revision — strict, not best-effort."""
        from edl_tpu.store import ShardedStoreClient, connect_store
        from edl_tpu.store import shard as shard_mod

        groups = []
        for i in range(2):
            primary = StoreServer(
                host="127.0.0.1", port=0,
                data_dir=str(tmp_path / ("p%d" % i)), name="store-%d" % i,
            ).start()
            standby = StoreServer(
                host="127.0.0.1", port=0,
                data_dir=str(tmp_path / ("s%d" % i)),
                follow=primary.endpoint, failover_grace=0.5,
                name="store-%d" % i,
            ).start()
            groups.append((primary, standby))
        deadline = time.time() + 15
        for _p, s in groups:
            while time.time() < deadline and not s._has_state:
                time.sleep(0.02)
            assert s._has_state
        boot = StoreClient(groups[0][0].endpoint, timeout=5)
        shard_mod.publish_shard_map(boot, [
            [p.endpoint, s.endpoint] for p, s in groups
        ])
        boot.close()
        client = connect_store(groups[0][0].endpoint, timeout=5)
        assert isinstance(client, ShardedStoreClient)
        try:
            acked = {}
            i = 0
            while len(acked) < 2 and i < 64:
                key = "/fj%d/svc/acked" % i
                shard = client.shard_of(key)
                if shard not in acked:
                    acked[shard] = (key, client.put(key, b"survive-me"))
                i += 1
            assert len(acked) == 2
            for primary, _s in groups:
                primary.kill()
            deadline = time.time() + 20
            for _p, standby in groups:
                while time.time() < deadline and standby.role != "primary":
                    time.sleep(0.05)
                assert standby.role == "primary", "shard never promoted"
                assert standby._state.epoch >= 1
            for shard, (key, rev) in acked.items():
                resp = client.retrying("get", k=key)
                assert resp["v"] == b"survive-me", "ACKED WRITE LOST"
                assert resp["mr"] == rev, "acked revision rewritten"
        finally:
            client.close()
            for primary, standby in groups:
                primary.stop()
                standby.stop()


# ---------------------------------------------------------------------------
# MVCC version chains + released-revision reads
# ---------------------------------------------------------------------------


class TestMVCC:
    """Bounded multi-version keyspace: reads pin to past revisions, the
    chain compacts past the retention horizon, and the server answers
    `rev=`-pinned gets/ranges with snapshot coherence (DESIGN.md
    "Consistency model")."""

    def test_state_versioned_get_and_range(self):
        s = StoreState()
        r1 = s.put("/m/a", b"a1").rev
        s.put("/m/b", b"b1")
        r3 = s.put("/m/a", b"a2").rev
        s.delete("/m/b")
        # pinned get: each revision sees the value live at that moment
        assert s.get("/m/a", rev=r1) == (b"a1", r1, 0)
        assert s.get("/m/a", rev=r3) == (b"a2", r3, 0)
        assert s.get("/m/b", rev=r3) == (b"b1", 2, 0)
        assert s.get("/m/b", rev=s.revision) is None  # tombstoned
        assert s.get("/m/b") is None
        # key that did not exist yet at the pinned revision
        assert s.get("/m/b", rev=0) is None
        # pinned range is a coherent snapshot: no torn read across keys
        items, asof = s.range("/m/", rev=r3)
        assert asof == r3
        assert [(k, v) for k, v, *_ in items] == [
            ("/m/a", b"a2"), ("/m/b", b"b1"),
        ]
        items, _ = s.range("/m/", rev=s.revision)
        assert [(k, v) for k, v, *_ in items] == [("/m/a", b"a2")]

    def test_state_compaction_drops_history_keeps_live(self):
        s = StoreState()
        for i in range(10):
            s.put("/c/k", b"%d" % i)
        s.put("/c/dead", b"x")
        s.delete("/c/dead")
        before = s.version_count
        dropped = s.compact(s.revision - 2)
        assert dropped > 0 and s.version_count < before
        assert s.compact_rev == s.revision - 2
        # live value still readable at and after the horizon
        assert s.get("/c/k")[0] == b"9"
        assert s.get("/c/k", rev=s.revision - 2)[0] is not None
        # pinned reads below the horizon are refused, not silently wrong
        with pytest.raises(ValueError):
            s.get("/c/k", rev=1)
        with pytest.raises(ValueError):
            s.range("/c/", rev=1)
        # tombstone chains past the horizon disappear entirely
        dropped2 = s.compact(s.revision)
        assert s.get("/c/dead") is None
        assert dropped2 >= 1
        # compaction is monotonic: lower horizon is a no-op
        assert s.compact(1) == 0

    def test_state_chains_rebuild_via_journal_apply(self):
        src = StoreState()
        src.put("/j/a", b"1")
        src.put("/j/a", b"2")
        dst = StoreState()
        for ev in src.history_since(0, "/"):
            dst.apply_journal({"op": "ev", **ev.to_wire()})
        assert dst.get("/j/a", rev=1) == (b"1", 1, 0)
        assert dst.get("/j/a", rev=2) == (b"2", 2, 0)

    def test_server_pinned_reads_and_compacted_error(self, server, client):
        r1 = client.put("/mv/k", b"old")
        client.put("/mv/k", b"new")
        assert client.get("/mv/k", rev=r1) == b"old"
        assert client.get("/mv/k") == b"new"
        items, asof = client.range("/mv/", rev=r1)
        assert asof == r1 and [(k, v) for k, v, *_ in items] == [
            ("/mv/k", b"old")
        ]
        # compact past r1 server-side; the pinned read now fails loudly
        server._state.compact(server._state.revision)
        from edl_tpu.utils.exceptions import EdlCompactedError

        with pytest.raises(EdlCompactedError):
            client.get("/mv/k", rev=r1)

    def test_mvcc_disabled_reads_applied_state(self, tmp_path, monkeypatch):
        monkeypatch.setenv("EDL_STORE_MVCC", "0")
        srv = StoreServer(host="127.0.0.1", port=0).start()
        try:
            assert srv._mvcc is False
            c = StoreClient(srv.endpoint, timeout=5)
            c.put("/off/k", b"v")
            assert c.get("/off/k") == b"v"
            c.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Standby read serving
# ---------------------------------------------------------------------------


class TestStandbyReads:
    """Standbys serve versioned reads at their applied released revision
    when the client opts in (read_mode="standby"); staleness is bounded
    by the lag guard and the session's read-your-writes floor, and every
    refusal degrades to a primary round-trip."""

    _pair = staticmethod(TestWarmStandby._pair)

    @staticmethod
    def _settle(primary, standby, timeout=10.0):
        deadline = time.time() + timeout
        while (
            time.time() < deadline
            and standby._state.revision < primary._state.revision
        ):
            time.sleep(0.02)

    def test_standby_serves_get_range_watch(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(primary.endpoint, read_mode="standby", timeout=5)
            for i in range(3):
                c.put("/sr/k%d" % i, b"%d" % i)
            self._settle(primary, standby)
            assert c.get("/sr/k1") == b"1"
            items, rev = c.range("/sr/")
            assert len(items) == 3 and rev >= 3
            events = []
            c.watch("/sr/", lambda evs: events.extend(evs))
            c.put("/sr/new", b"x")
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                e.key == "/sr/new" for e in events
            ):
                time.sleep(0.05)
            assert any(e.key == "/sr/new" for e in events)
            # the reads (and the watch) were served by the STANDBY. Early
            # reads may legitimately fall through (lag / read-your-writes
            # floor while the tail drains), so poll until the standby has
            # demonstrably served.
            deadline = time.time() + 10
            while time.time() < deadline and standby._standby_reads_n < 3:
                c.get("/sr/k1")
                time.sleep(0.05)
            assert standby._standby_reads_n >= 3
            assert c._standby_leg_client is not None
            assert c._standby_leg_client._endpoint == standby.endpoint
            c.close()
        finally:
            standby.stop()
            primary.stop()

    def test_leader_mode_never_touches_standby(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(primary.endpoint, timeout=5)  # default: leader
            c.put("/lm/k", b"v")
            assert c.get("/lm/k") == b"v"
            assert standby._standby_reads_n == 0
            assert c._standby_leg_client is None
            c.close()
        finally:
            standby.stop()
            primary.stop()

    def test_read_your_writes_floor(self, tmp_path):
        """A write acked at rev N is never invisible to the same session:
        the client sends its floor, a behind standby refuses, and the
        read falls through to the primary."""
        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(primary.endpoint, read_mode="standby", timeout=5)
            for i in range(50):
                rev = c.put("/ryw/k", b"%d" % i)
                assert c._min_rev >= rev
                got = c.get("/ryw/k")
                assert got == b"%d" % i, (
                    "stale read: wrote %d at rev %d, got %r" % (i, rev, got)
                )
            c.close()
        finally:
            standby.stop()
            primary.stop()

    def test_refusal_matrix(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        try:
            # writes and un-opted reads always bounce
            assert standby._standby_read_refusal("put", {}) is not None
            assert standby._standby_read_refusal("get", {}) is not None
            # opted-in read with no floor: served
            assert standby._standby_read_refusal("get", {"rm": "s"}) is None
            # floor above the applied revision: bounce (read-your-writes)
            req = {"rm": "s", "minr": standby._state.revision + 10}
            assert "write" in standby._standby_read_refusal("get", req)
            # lag beyond the bound: bounce
            standby._standby_max_lag = 0
            orig = standby._repl_lag_entries
            standby._repl_lag_entries = lambda: 5
            try:
                r = standby._standby_read_refusal("get", {"rm": "s"})
                assert r is not None and "lags" in r
            finally:
                standby._repl_lag_entries = orig
        finally:
            standby.stop()
            primary.stop()

    def test_fall_through_when_standby_dies(self, tmp_path):
        primary, standby = self._pair(tmp_path)
        try:
            c = StoreClient(primary.endpoint, read_mode="standby", timeout=5)
            c.put("/ft/k", b"v")
            self._settle(primary, standby)
            assert c.get("/ft/k") == b"v"
            standby.stop()
            # reads keep working: the dead leg falls through to primary
            for _ in range(3):
                assert c.get("/ft/k") == b"v"
            c.close()
        finally:
            primary.stop()

    def test_sharded_client_standby_mode(self, tmp_path):
        from edl_tpu.store.client import connect_store

        primary, standby = self._pair(tmp_path)
        try:
            c = connect_store(primary.endpoint, read_mode="standby")
            c.put("/sh/k", b"v")
            self._settle(primary, standby)
            assert c.get("/sh/k") == b"v"
            c.close()
        finally:
            standby.stop()
            primary.stop()


class TestNativeTwinCompat:
    """Wire-protocol parity with servers that predate this plane: the
    native C++ twin (and any one-PR-older python peer) knows none of
    ``rev``/``rm``/``minr`` and has no ``lease_renew_batch`` dispatch.
    These tests emulate such a server at the DISPATCH level — an
    instance attribute shadowing the handler makes ``getattr`` return
    None, which is exactly the unknown-method path an old twin takes —
    and assert the client degrades instead of erroring."""

    _pair = staticmethod(TestWarmStandby._pair)
    _settle = staticmethod(TestStandbyReads._settle)

    def test_lease_keeper_survives_server_without_batch_op(self, server):
        # shadow the handler: dispatch getattr()s the instance first, so
        # None here IS the legacy twin's "unknown method" refusal
        server._op_lease_renew_batch = None
        client = StoreClient(server.endpoint, timeout=5)
        try:
            lease = client.lease_grant(0.6)
            client.put("/twin/fb", b"x", lease=lease)
            keeper = LeaseKeeper(client, lease, 0.6)
            time.sleep(1.4)  # > 2 TTLs: only live renewals keep the key
            assert client.get("/twin/fb") == b"x", (
                "per-lease fallback never renewed against legacy server"
            )
            assert client._renewer is not None
            assert client._renewer._batch_ok is False, (
                "renewer should remember the twin lacks the batch op"
            )
            keeper.stop()
        finally:
            client.close()

    def test_standby_mode_degrades_against_legacy_standby(self, tmp_path):
        """A standby that predates the read plane bounces EVERY read
        with EdlNotPrimaryError no matter what ``rm``/``minr`` say; a
        read_mode="standby" client must degrade to primary round-trips
        with correct results and no surfaced errors."""
        primary, standby = self._pair(tmp_path)
        # legacy emulation: unconditional refusal, rm/minr ignored
        standby._standby_read_refusal = lambda method, req: (
            "not primary (role=standby)"
        )
        try:
            c = StoreClient(primary.endpoint, read_mode="standby", timeout=5)
            for i in range(5):
                c.put("/twin/sr/k%d" % i, b"%d" % i)
            self._settle(primary, standby)
            for i in range(5):
                assert c.get("/twin/sr/k%d" % i) == b"%d" % i
            items, rev = c.range("/twin/sr/")
            assert len(items) == 5 and rev >= 5
            assert standby._standby_reads_n == 0, (
                "legacy standby must never count a served read"
            )
            c.close()
        finally:
            standby.stop()
            primary.stop()
