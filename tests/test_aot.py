"""AOT resize ladder + compile-cache exchange (edl_tpu/train/aot.py).

Covers the rung enumeration and claim dedupe, the manifest/digest
machinery, the exchange end-to-end on real sockets + a real store, the
chaos drill (a corrupted or dropped cache-entry pull degrades to a
normal compile, never a wedged worker), and the acceptance e2e: a pod
joining with an EMPTY cache dir pulls entries a peer already compiled
and provably first-jits from them — zero real compiles, nonzero rx
bytes.
"""

import hashlib
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from edl_tpu.chaos.plane import configure as chaos_configure
from edl_tpu.store.client import StoreClient
from edl_tpu.train import aot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env_stub(**over):
    base = dict(
        world_size=2, nproc_per_node=1, min_nodes=1, max_nodes=3,
        global_rank=0, pod_id="podA", job_id="aotjob", store_endpoint="",
    )
    base.update(over)
    return SimpleNamespace(**base)


def _wait_until(pred, timeout=10.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


# -- rung enumeration ---------------------------------------------------------

class TestNeighborWorlds:
    def test_nearest_first_shrink_before_grow(self):
        # 4 pods in a 1..6 window: ±1 then ±2, shrink first at equal
        # distance — the shrink is what this process can compile itself
        assert aot.neighbor_worlds(4, 1, 1, 6) == [3, 5, 2, 6]

    def test_window_clamps(self):
        assert aot.neighbor_worlds(1, 1, 1, 3) == [2, 3]
        assert aot.neighbor_worlds(3, 1, 1, 3) == [2, 1]
        # a window pinned to the current world: nothing to speculate
        assert aot.neighbor_worlds(4, 1, 4, 4) == []

    def test_nproc_scales_worlds(self):
        # 2 procs/node, 2 pods, window 1..4 -> pod targets 1,3,4 as worlds
        assert aot.neighbor_worlds(4, 2, 1, 4) == [2, 6, 8]

    def test_non_divisible_world_is_a_noop(self):
        assert aot.neighbor_worlds(5, 2, 1, 4) == []


# -- manifest / digest machinery ----------------------------------------------

class TestManifest:
    def test_scan_digests_entries_and_skips_sidecars(self, tmp_path):
        (tmp_path / "key1-cache").write_bytes(b"exec one")
        (tmp_path / "key2-cache").write_bytes(b"exec two")
        (tmp_path / "key1-cache-atime").write_bytes(b"12345678")
        (tmp_path / ".hidden").write_bytes(b"x")
        (tmp_path / ("key3" + aot._TMP_MARK + ".99")).write_bytes(b"torn")
        m = aot.scan_manifest(str(tmp_path))
        assert sorted(m) == ["key1-cache", "key2-cache"]
        assert m["key1-cache"]["sha"] == hashlib.sha256(b"exec one").hexdigest()
        assert m["key2-cache"]["size"] == len(b"exec two")

    def test_scan_missing_dir_is_empty(self, tmp_path):
        assert aot.scan_manifest(str(tmp_path / "nope")) == {}


# -- portable keys + cache-event seam -----------------------------------------

class TestJitSeamPatches:
    def test_portable_keys_enable_and_idempotent(self, monkeypatch):
        monkeypatch.delenv("EDL_CACHE_PORTABLE_KEYS", raising=False)
        assert aot.enable_portable_cache_keys() is True
        from jax._src import cache_key as ck

        assert getattr(ck._hash_accelerator_config, "_edl_portable", False)
        assert aot.enable_portable_cache_keys() is True  # no double-wrap

    def test_portable_keys_opt_out(self, monkeypatch):
        monkeypatch.setenv("EDL_CACHE_PORTABLE_KEYS", "0")
        assert aot.enable_portable_cache_keys() is False

    def test_instrumentation_idempotent_and_counts_shape(self, monkeypatch):
        monkeypatch.delenv("EDL_CACHE_EVENTS", raising=False)
        assert aot.instrument_compilation_cache() is True
        assert aot.instrument_compilation_cache() is True
        counts = aot.cache_event_counts()
        assert sorted(counts) == ["hit", "miss", "write"]
        assert all(v >= 0 for v in counts.values())


# -- the ladder ---------------------------------------------------------------

class TestAotLadder:
    @pytest.fixture(autouse=True)
    def _one_device_per_proc(self, monkeypatch):
        # these rigs model 1-device processes (the CPU resize rig pins
        # the same); without the pin devices_per_process() derives
        # 8-virtual-devices / world_size from the host mesh
        monkeypatch.setenv("EDL_DEVICES_PER_PROC", "1")

    def test_multi_device_processes_scale_rungs(self, monkeypatch):
        # TPU shape: world counts PROCESSES but meshes are devices — a
        # 2-process stage over the 8-device host mesh owns 4 devices
        # per process, so world 1 compiles a 4-device mesh and world 3
        # (12 devices) is a grow this process cannot see
        monkeypatch.delenv("EDL_DEVICES_PER_PROC", raising=False)
        compiled = []
        before = aot._M_AOT.value(outcome="skipped_grow")
        ladder = aot.AotLadder(
            _env_stub(), compiled.append, delay=0.0
        ).start()
        assert _wait_until(
            lambda: aot._M_AOT.value(outcome="skipped_grow") == before + 1
        )
        ladder.close()
        assert compiled == [1]
        assert aot.devices_per_process(_env_stub()) == 4

    def test_compiles_neighbor_worlds_in_order(self):
        compiled = []
        ladder = aot.AotLadder(
            _env_stub(), compiled.append, delay=0.0
        ).start()
        assert _wait_until(lambda: len(ladder.compiled) == 2)
        ladder.close()
        # world 2 in a 1..3 pod window -> worlds [1, 3]; the 8-device
        # virtual CPU mesh makes both compilable in-process
        assert compiled == [1, 3]
        assert ladder.compiled == [1, 3]

    def test_nonzero_rank_without_store_defers(self):
        compiled = []
        ladder = aot.AotLadder(
            _env_stub(global_rank=1), compiled.append, delay=0.0
        ).start()
        time.sleep(0.3)
        ladder.close()
        assert compiled == []

    def test_failed_compile_is_counted_never_raised(self):
        def boom(world):
            raise RuntimeError("xla says no")

        before = aot._M_AOT.value(outcome="failed")
        ladder = aot.AotLadder(_env_stub(), boom, delay=0.0).start()
        assert _wait_until(
            lambda: aot._M_AOT.value(outcome="failed") >= before + 2
        )
        ladder.close()
        assert ladder.compiled == []

    def test_indivisible_rung_is_skipped_not_failed(self):
        # a sharded dim that doesn't divide over the neighbor mesh is a
        # permanent model/window property: its own outcome, never noise
        # in the failed counter
        def indivisible(world):
            raise aot.RungUnavailable("dim 0 (5) not divisible over dp=2")

        before_f = aot._M_AOT.value(outcome="failed")
        before_s = aot._M_AOT.value(outcome="skipped_indivisible")
        ladder = aot.AotLadder(_env_stub(), indivisible, delay=0.0).start()
        assert _wait_until(
            lambda: aot._M_AOT.value(outcome="skipped_indivisible")
            >= before_s + 2
        )
        ladder.close()
        assert aot._M_AOT.value(outcome="failed") == before_f
        assert ladder.compiled == []

    def test_store_claim_dedupes_across_pods(self, store):
        client = StoreClient(store.endpoint)
        a_worlds, b_worlds = [], []
        env_a = _env_stub(store_endpoint=store.endpoint)
        env_b = _env_stub(
            store_endpoint=store.endpoint, pod_id="podB", global_rank=0
        )
        try:
            ladder_a = aot.AotLadder(
                env_a, a_worlds.append, client=client, delay=0.0
            ).start()
            assert _wait_until(lambda: len(ladder_a.compiled) == 2)
            ladder_a.close()
            before = aot._M_AOT.value(outcome="skipped_claimed")
            ladder_b = aot.AotLadder(
                env_b, b_worlds.append, client=client, delay=0.0
            ).start()
            assert _wait_until(
                lambda: aot._M_AOT.value(outcome="skipped_claimed")
                >= before + 2
            )
            ladder_b.close()
        finally:
            client.close()
        assert a_worlds == [1, 3]
        assert b_worlds == []  # every rung already done: by podA

    def test_peer_failure_releases_rung_to_deferred_retry(
        self, store, monkeypatch
    ):
        # a rung claimed by a peer whose compile then FAILS (lease
        # deleted, no done marker) must not be stranded: the deferred
        # re-pass picks it up
        monkeypatch.setattr(aot.AotLadder, "_RETRY_DELAY", 0.3)
        from edl_tpu.discovery.registry import Registry

        client = StoreClient(store.endpoint)
        try:
            regs = [
                Registry(client, "aotjob").register_if_absent(
                    "aot", str(w), b"podA.0", ttl=60.0
                )[0]
                for w in (1, 3)
            ]
            before = aot._M_AOT.value(outcome="skipped_claimed")
            compiled = []
            ladder = aot.AotLadder(
                _env_stub(pod_id="podB", store_endpoint=store.endpoint),
                compiled.append, client=client, delay=0.0,
            ).start()
            assert _wait_until(
                lambda: aot._M_AOT.value(outcome="skipped_claimed")
                >= before + 2
            )
            for reg in regs:  # the peer's compiles fail: claims released
                reg.stop(delete=True)
            assert _wait_until(lambda: len(ladder.compiled) == 2)
            ladder.close()
            assert compiled == [1, 3]
        finally:
            client.close()

    def test_grow_beyond_visible_devices_is_skipped(self):
        # 8 virtual devices: a 12-device rung cannot compile here
        compiled = []
        before = aot._M_AOT.value(outcome="skipped_grow")
        ladder = aot.AotLadder(
            _env_stub(), compiled.append, worlds=[12], delay=0.0
        ).start()
        assert _wait_until(
            lambda: aot._M_AOT.value(outcome="skipped_grow") == before + 1
        )
        ladder.close()
        assert compiled == []

    def test_ladder_seconds_land_in_aot_compile_state(self):
        from edl_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.default_registry()
        counter = reg.counter("edl_goodput_seconds_total")
        before = counter.value(state="aot_compile", cause="w1")
        ladder = aot.AotLadder(
            _env_stub(), lambda w: time.sleep(0.05), delay=0.0
        ).start()
        assert _wait_until(lambda: len(ladder.compiled) == 2)
        ladder.close()
        assert counter.value(state="aot_compile", cause="w1") > before

    def test_chaos_drop_on_compile_point_is_a_counted_failure(self):
        chaos_configure(
            {"rules": [{"point": "train.aot.compile", "action": "drop",
                        "times": 0}]},
            who="pytest",
        )
        try:
            compiled = []
            before = aot._M_AOT.value(outcome="failed")
            ladder = aot.AotLadder(
                _env_stub(), compiled.append, delay=0.0
            ).start()
            assert _wait_until(
                lambda: aot._M_AOT.value(outcome="failed") >= before + 2
            )
            ladder.close()
            assert compiled == []  # every rung dropped, nobody crashed
        finally:
            chaos_configure({"rules": []}, who="pytest")


# -- the exchange -------------------------------------------------------------

@pytest.fixture()
def exchange_rig(store, tmp_path):
    """A served pod-A cache dir + an empty pod-B dir on a real store."""
    client = StoreClient(store.endpoint)
    dir_a = tmp_path / "cache_a"
    dir_b = tmp_path / "cache_b"
    dir_a.mkdir()
    dir_b.mkdir()
    entries = {
        "k1-cache": b"executable one" * 100,
        "k2-cache": b"executable two" * 100,
        "k3-cache": b"\x00\x01binary\xff" * 64,
    }
    for name, data in entries.items():
        (dir_a / name).write_bytes(data)
    (dir_a / "k1-cache-atime").write_bytes(b"01234567")  # never shipped
    xchg = aot.CacheExchange(
        str(dir_a), client, "xjob", "podA", host="127.0.0.1"
    ).start()
    # publication rides the exchange's scan thread; land it before the
    # tests look (peers in production simply pull on their next look)
    assert _wait_until(lambda: "podA" in aot.read_manifests(client, "xjob"))
    yield SimpleNamespace(
        store=store, client=client, dir_a=dir_a, dir_b=dir_b,
        entries=entries, xchg=xchg,
    )
    xchg.stop()
    client.close()


class TestCacheExchange:
    def test_manifest_published_and_readable(self, exchange_rig):
        r = exchange_rig
        manifests = aot.read_manifests(r.client, "xjob")
        assert set(manifests) == {"podA"}
        m = manifests["podA"]
        assert sorted(m["entries"]) == sorted(r.entries)
        assert m["endpoint"].endswith(":%d" % r.xchg.port)
        assert "k1-cache-atime" not in m["entries"]

    def test_empty_pod_pulls_everything_byte_identical(self, exchange_rig):
        r = exchange_rig
        rx_before = aot._M_XCHG_BYTES.value(dir="rx")
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB"
        )
        assert stats["pulled"] == len(r.entries)
        assert stats["skipped_bad"] == 0
        assert stats["peers"] == 1
        for name, data in r.entries.items():
            assert (r.dir_b / name).read_bytes() == data
        assert aot._M_XCHG_BYTES.value(dir="rx") == rx_before + stats["bytes"]
        assert stats["bytes"] == sum(len(d) for d in r.entries.values())
        # second pull: nothing missing anymore
        again = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB"
        )
        assert again["pulled"] == 0

    def test_own_manifest_is_never_pulled(self, exchange_rig):
        r = exchange_rig
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podA"
        )
        assert stats == {"pulled": 0, "bytes": 0, "skipped_bad": 0, "peers": 0}

    def test_unchanged_refresh_does_not_republish(self, exchange_rig):
        # the manifest put is journal traffic on the control plane (and
        # rides HA replication streams): an unchanged cache dir must not
        # republish — the embedded ts may not defeat the change check
        r = exchange_rig
        key = "/xjob/%s/podA" % aot.MANIFEST_SERVICE
        _, rev_before = r.client.get_with_rev(key)
        r.xchg.refresh(force=True)
        r.xchg.refresh(force=True)
        _, rev_after = r.client.get_with_rev(key)
        assert rev_after == rev_before

    def test_refresh_republishes_new_entries(self, exchange_rig):
        r = exchange_rig
        (r.dir_a / "k4-cache").write_bytes(b"late entry" * 50)
        r.xchg.refresh(force=True)
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB"
        )
        assert stats["pulled"] == len(r.entries) + 1
        assert (r.dir_b / "k4-cache").read_bytes() == b"late entry" * 50

    def test_server_refuses_path_shaped_names(self, exchange_rig, tmp_path):
        from edl_tpu.rpc.wire import request_once

        secret = tmp_path / "secret.txt"
        secret.write_bytes(b"not a cache entry")
        resp = request_once(
            exchange_rig.xchg.endpoint,
            {"i": 1, "m": "cache_pull",
             "names": ["../secret.txt", ".hidden", "a/b", "k1-cache"]},
            timeout=5.0,
        )
        assert resp["ok"]
        assert set(resp["entries"]) == {"k1-cache"}

    def test_tampered_entry_is_skipped_not_landed(self, exchange_rig):
        # peer's file changes AFTER the manifest was published (a torn
        # write at the peer in miniature): digest mismatch -> skipped
        r = exchange_rig
        (r.dir_a / "k1-cache").write_bytes(b"tampered!")
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB"
        )
        assert stats["skipped_bad"] == 1
        assert stats["pulled"] == len(r.entries) - 1
        assert not (r.dir_b / "k1-cache").exists()
        assert not any(
            aot._TMP_MARK in p.name for p in r.dir_b.iterdir()
        ), "a skipped entry must not leave temp litter"

    def test_pull_without_store_is_a_noop(self, tmp_path):
        stats = aot.pull_missing(str(tmp_path), endpoint="", job_id="j")
        assert stats["pulled"] == 0

    def test_pull_survives_dead_peer_endpoint(self, exchange_rig):
        # a manifest pointing at a gone peer: the pull skips it inside
        # its budget instead of raising
        r = exchange_rig
        r.client.put(
            "/xjob/compile_cache/podGone",
            json.dumps({
                "endpoint": "127.0.0.1:1",  # nothing listens there
                "entries": {"kX-cache": "0" * 64},
                "ts": 0,
            }).encode(),
        )
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB",
            deadline=5.0,
        )
        assert stats["pulled"] == len(r.entries)  # podA still served
        assert not (r.dir_b / "kX-cache").exists()

    def test_hostile_manifest_name_never_dialed_or_landed(self, exchange_rig):
        # the WRITE direction of the path-refusal rule: a manifest naming
        # "../escape" must not choose where pulled bytes land — the name
        # is dropped before the peer is even dialed
        r = exchange_rig
        evil = {"../escape": "0" * 64, ".dotted": "1" * 64, "a/b": "2" * 64}
        r.client.put(
            "/xjob/compile_cache/podEvil",
            json.dumps({
                "endpoint": r.xchg.endpoint,  # a live server, deliberately
                "entries": evil, "ts": 0,
            }).encode(),
        )
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB",
        )
        assert stats["pulled"] == len(r.entries)  # podA's real entries only
        assert stats["peers"] == 1  # podEvil had nothing pullable
        assert not (r.dir_b.parent / "escape").exists()
        assert sorted(p.name for p in r.dir_b.iterdir()) == sorted(r.entries)

    def test_byte_capped_response_splits_and_completes(
        self, exchange_rig, monkeypatch
    ):
        # entries are ~1400/1400/768 bytes; a 2000-byte cap forces the
        # server to truncate every chunk and the puller to re-request the
        # pushed-out names — everything still lands, byte-identical
        monkeypatch.setenv("EDL_CACHE_PULL_MAX_BYTES", "2000")
        r = exchange_rig
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB",
        )
        assert stats["pulled"] == len(r.entries)
        for name, data in r.entries.items():
            assert (r.dir_b / name).read_bytes() == data

    def test_oversize_single_entry_still_ships(self, exchange_rig, monkeypatch):
        # one entry alone over the cap: the server must still serve it
        # (a cap that starves is worse than a fat frame) rather than
        # truncate forever
        monkeypatch.setenv("EDL_CACHE_PULL_MAX_BYTES", "10")
        r = exchange_rig
        stats = aot.pull_missing(
            str(r.dir_b), client=r.client, job_id="xjob", own_pod="podB",
        )
        assert stats["pulled"] == len(r.entries)

    def test_scan_thread_republishes_without_caller(self, store, tmp_path):
        # the recurring digest scan is the exchange's own thread — new
        # entries must surface in the manifest with nobody calling
        # refresh() (the launcher loop doesn't anymore)
        client = StoreClient(store.endpoint)
        try:
            d = tmp_path / "cache_t"
            d.mkdir()
            xchg = aot.CacheExchange(
                str(d), client, "xjob3", "podT", host="127.0.0.1"
            )
            xchg._REFRESH_EVERY = 0.2
            xchg.start()
            try:
                (d / "kN-cache").write_bytes(b"fresh entry")
                assert _wait_until(
                    lambda: "kN-cache" in (
                        aot.read_manifests(client, "xjob3")
                        .get("podT", {}).get("entries") or {}
                    ),
                    timeout=5.0,
                )
            finally:
                xchg.stop()
        finally:
            client.close()

    def test_stop_retracts_manifest(self, store, tmp_path):
        client = StoreClient(store.endpoint)
        try:
            d = tmp_path / "cache_c"
            d.mkdir()
            (d / "kZ-cache").write_bytes(b"entry")
            xchg = aot.CacheExchange(
                str(d), client, "xjob2", "podC", host="127.0.0.1"
            ).start()
            assert _wait_until(
                lambda: "podC" in aot.read_manifests(client, "xjob2")
            )
            xchg.stop()
            # a departed pod must not leave a manifest for later pulls to
            # burn budget on (SIGKILL still can; the per-peer dial cap is
            # the backstop there)
            assert "podC" not in aot.read_manifests(client, "xjob2")
        finally:
            client.close()


class TestChaosDrill:
    """Satellite drill: a corrupted/dropped cache-entry pull degrades to
    a normal compile — entries are skipped, nothing lands poisoned,
    nothing wedges or crashes."""

    def test_corrupt_pull_skips_every_entry(self, exchange_rig):
        chaos_configure(
            {"rules": [{"point": "store.cache.exchange",
                        "action": "corrupt", "times": 0}]},
            who="pytest",
        )
        try:
            stats = aot.pull_missing(
                str(exchange_rig.dir_b), client=exchange_rig.client,
                job_id="xjob", own_pod="podB",
            )
        finally:
            chaos_configure({"rules": []}, who="pytest")
        assert stats["pulled"] == 0
        assert stats["skipped_bad"] == len(exchange_rig.entries)
        assert list(exchange_rig.dir_b.iterdir()) == []

    def test_dropped_pull_is_contained_and_bounded(self, exchange_rig):
        chaos_configure(
            {"rules": [{"point": "store.cache.exchange",
                        "action": "drop", "times": 0}]},
            who="pytest",
        )
        t0 = time.monotonic()
        try:
            stats = aot.pull_missing(
                str(exchange_rig.dir_b), client=exchange_rig.client,
                job_id="xjob", own_pod="podB", deadline=10.0,
            )
        finally:
            chaos_configure({"rules": []}, who="pytest")
        assert time.monotonic() - t0 < 10.0
        assert stats["pulled"] == 0
        assert stats["skipped_bad"] == len(exchange_rig.entries)
        assert list(exchange_rig.dir_b.iterdir()) == []


# -- acceptance e2e: join with an empty cache, first-jit from pulled entries --

# the worker both pods run: edl init (arms the cache + portable keys +
# event counters and, pod B, pulls from peers), one jitted step, then a
# JSON proof of what the persistent cache did
WORKER = """
import json, os, sys
sys.path.insert(0, %(repo)r)
from edl_tpu.chaos import plane as chaos_plane
chaos_plane.arm_from_env("worker")
from edl_tpu.train import init
from edl_tpu.train import aot
init()
import jax, jax.numpy as jnp
f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
print(float(f(jnp.ones((96, 96)))), file=sys.stderr)
print(json.dumps({
    "counts": aot.cache_event_counts(),
    "rx": aot._M_XCHG_BYTES.value(dir="rx"),
}))
"""


def _run_worker(cache_dir, pod, store, extra=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "EDL_JOB_ID": "e2ejob",
        "EDL_POD_ID": pod,
        "EDL_STORE_ENDPOINT": store.endpoint,
        "EDL_COMPILE_CACHE_DIR": str(cache_dir),
        "EDL_AOT": "0",  # the pull is what's under test, not the ladder
    })
    env.update(extra or {})
    out = subprocess.run(
        [sys.executable, "-c", WORKER % {"repo": REPO}],
        env=env, capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestJoinFromPeerCache:
    def test_empty_pod_first_jits_from_pulled_entries(self, store, tmp_path):
        dir_a = tmp_path / "a"
        dir_b = tmp_path / "b"
        dir_a.mkdir()
        dir_b.mkdir()
        # pod A pays the real compile
        a = _run_worker(dir_a, "podA", store)
        assert a["counts"]["miss"] >= 1 and a["counts"]["write"] >= 1
        assert any(
            not n.endswith("-atime") for n in os.listdir(dir_a)
        ), "pod A must leave cache entries"
        # ... and serves its cache (the launcher's role, in miniature)
        client = StoreClient(store.endpoint)
        xchg = aot.CacheExchange(
            str(dir_a), client, "e2ejob", "podA", host="127.0.0.1"
        ).start()
        try:
            # pod B joins with an EMPTY dir: init() pulls, the first jit
            # is a cache LOAD — zero real compiles, nonzero rx bytes
            b = _run_worker(dir_b, "podB", store)
        finally:
            xchg.stop()
            client.close()
        assert b["rx"] > 0, b
        assert b["counts"]["hit"] >= 1, b
        assert b["counts"]["miss"] == 0, (
            "pod B paid a real compile despite a peer's warm cache: %r" % b
        )

    def test_corrupted_pull_degrades_to_a_normal_compile(
        self, store, tmp_path
    ):
        """The chaos drill end-to-end: every pulled entry corrupted in
        flight — pod B must simply compile (miss+write), finish its
        step, and exit clean."""
        dir_a = tmp_path / "a"
        dir_b = tmp_path / "b"
        dir_a.mkdir()
        dir_b.mkdir()
        _run_worker(dir_a, "podA", store)
        client = StoreClient(store.endpoint)
        xchg = aot.CacheExchange(
            str(dir_a), client, "e2ejob", "podA", host="127.0.0.1"
        ).start()
        try:
            b = _run_worker(
                dir_b, "podB", store,
                extra={"EDL_CHAOS": json.dumps({
                    "rules": [{"point": "store.cache.exchange",
                               "action": "corrupt", "times": 0}],
                })},
            )
        finally:
            xchg.stop()
            client.close()
        assert b["rx"] == 0, b
        assert b["counts"]["miss"] >= 1 and b["counts"]["write"] >= 1, b


# -- every rank writes the persistent cache ------------------------------------

class TestAllRankCacheWrites:
    def test_patch_applies_and_is_idempotent(self, tmp_path, monkeypatch):
        monkeypatch.setenv("EDL_CACHE_ALL_RANKS", "1")
        from edl_tpu.train.context import enable_compilation_cache

        enable_compilation_cache(str(tmp_path / "c"))
        from jax._src import compiler as _compiler

        assert getattr(_compiler._cache_write, "_edl_all_ranks", False)
        before = _compiler._cache_write
        enable_compilation_cache(str(tmp_path / "c"))
        assert _compiler._cache_write is before  # no double-wrap

    def test_patched_write_ignores_process_id(self, tmp_path, monkeypatch):
        """The wrapped _cache_write must not take the rank-0-only early
        return: with a fake nonzero process_id it should proceed into the
        write path (observed via the compilation_cache call)."""
        monkeypatch.setenv("EDL_CACHE_ALL_RANKS", "1")
        from edl_tpu.train.context import enable_compilation_cache

        enable_compilation_cache(str(tmp_path / "c"))
        from jax._src import compiler as _compiler
        from jax._src import compilation_cache as _cc

        calls = []
        monkeypatch.setattr(
            _cc, "put_executable_and_time",
            lambda *a, **kw: calls.append(a),
        )
        real_gs = _compiler.distributed.global_state
        monkeypatch.setattr(real_gs, "process_id", 3, raising=False)
        try:
            _compiler._cache_write(
                "k", 1.0, "jit_x", object(), object(), []
            )
        except Exception:
            pass  # fake executable may explode later in the write path
        assert calls, "write path never reached despite process_id=3"
