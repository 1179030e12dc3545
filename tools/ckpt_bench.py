"""ckpt_bench: restore latency of the checkpoint tier ladder.

Measures, on one machine with a real store + a real replica holder over
TCP loopback, what a restoring pod pays per tier:

- **peer tier**: manifests read from the store, shards fetched from the
  holder over the wire (digest-verified, atomically assembled), then a
  normal Orbax restore — the shared-FS-free recovery path;
- **durable tier**: newest version copied from the durable directory
  into the local tier, then the same Orbax restore — the classic path.

On a single host both tiers move bytes at local-disk/loopback speed, so
the RAW numbers mainly price the replication plane's own overhead
(manifest read, chunked fetch RPCs, sha256 verification) against a
directory copy. The production gap comes from the durable tier being a
REMOTE filesystem: ``--durable-latency S`` adds a modeled per-file
round-trip (NFS/GCS/HDFS metadata+read RTT) to the durable figure,
reported separately and clearly labeled as modeled, never mixed into
the raw measurement.

Usage::

    python tools/ckpt_bench.py --mb 64 --trials 3 --json
    python tools/ckpt_bench.py --mb 64 --durable-latency 0.05 \
        --out bench_results/ckpt_bench_cpu_rNN.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _median(xs: List[float]) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _state(mb: int):
    import numpy as np

    # several arrays so the step dir has a realistic multi-file shape
    per = max(1, mb // 4)
    return {
        "layer%d" % i: np.random.RandomState(i).rand(
            per * (1 << 20) // 8
        ).astype("float64")
        for i in range(4)
    }


def run_bench(
    mb: int, trials: int, durable_latency: float, workdir: str
) -> Dict:
    from edl_tpu.checkpoint import replicate as repl
    from edl_tpu.checkpoint.manager import CheckpointManager, TrainStatus
    from edl_tpu.discovery.registry import Registry
    from edl_tpu.store.client import StoreClient
    from edl_tpu.store.server import StoreServer

    job = "ckpt-bench"
    srv = StoreServer(host="127.0.0.1", port=0).start()
    client = StoreClient(srv.endpoint, timeout=10.0)
    os.environ.update({
        "EDL_STORE_ENDPOINT": srv.endpoint,
        "EDL_JOB_ID": job,
        "EDL_CKPT_REPLICAS": "1",
    })
    durable = os.path.join(workdir, "durable")
    holder = repl.ReplicaServer(
        os.path.join(workdir, "holder.replicas"), client, job, "holder"
    ).start()
    reg = Registry(client, job).register(
        repl.PEERS_SERVICE, "holder", holder.endpoint.encode(), ttl=60.0
    )
    out: Dict = {
        "bench": "ckpt_bench",
        "mb": mb,
        "trials": trials,
        "platform": os.environ.get("JAX_PLATFORMS", ""),
    }
    try:
        # -- the saver: one checkpoint in the local tier, pushed + mirrored
        os.environ["EDL_POD_ID"] = "saver"
        state = _state(mb)
        mngr = CheckpointManager(
            durable, local_dir=os.path.join(workdir, "local-saver")
        )
        t0 = time.monotonic()
        mngr.save(state, TrainStatus(epoch=1, step=8, world_size=1))
        mngr.wait()
        out["save_s"] = round(time.monotonic() - t0, 4)
        t0 = time.monotonic()
        assert mngr._replicator is not None, "replication plane not armed"
        assert mngr._replicator.flush(120.0), "peer push failed"
        out["push_s"] = round(time.monotonic() - t0, 4)
        # the durable mirror runs on the background thread; wait for it
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and not os.path.isdir(
            os.path.join(durable, "8")
        ):
            time.sleep(0.05)
        assert os.path.isdir(os.path.join(durable, "8")), "no durable mirror"
        step_dir = os.path.join(workdir, "local-saver", "8")
        n_files = sum(len(fs) for _, _, fs in os.walk(step_dir))
        n_bytes = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(step_dir) for f in fs
        )
        out["files"] = n_files
        out["bytes"] = n_bytes
        mngr.close()

        import jax.numpy as jnp  # noqa: F401 — template trees are numpy

        template = _state(mb)

        def timed_restore(pod: str, replicas: str) -> float:
            os.environ["EDL_POD_ID"] = pod
            os.environ["EDL_CKPT_REPLICAS"] = replicas
            local = os.path.join(workdir, "local-" + pod)
            shutil.rmtree(local, ignore_errors=True)
            m = CheckpointManager(durable, local_dir=local)
            t0 = time.monotonic()
            _restored, status = m.restore(template)
            dt = time.monotonic() - t0
            assert status is not None and status.step == 8, (
                "restore missed the checkpoint (pod %s)" % pod
            )
            m.close()
            return dt

        peer, durable_raw = [], []
        for i in range(trials):
            peer.append(timed_restore("peer-%d" % i, "1"))
            # EDL_CKPT_REPLICAS=0 disables the peer tier: the ladder
            # walks local (empty) -> durable, the classic path
            durable_raw.append(timed_restore("durable-%d" % i, "0"))
        out["peer_restore_s"] = round(_median(peer), 4)
        out["durable_restore_s_raw"] = round(_median(durable_raw), 4)
        out["peer_restore_all_s"] = [round(x, 4) for x in peer]
        out["durable_restore_all_s"] = [round(x, 4) for x in durable_raw]
        if durable_latency > 0:
            out["durable_latency_per_file_s"] = durable_latency
            out["durable_restore_s_modeled"] = round(
                _median(durable_raw) + durable_latency * n_files, 4
            )
        out["note"] = (
            "single-host rig: both tiers move bytes at local-disk/loopback "
            "speed, so raw numbers price the replication plane's overhead "
            "(manifest read + chunked fetch + sha256) against a directory "
            "copy; the modeled figure adds the per-file RTT a REMOTE "
            "durable tier (NFS/GCS/HDFS) pays and the peer tier does not"
        )
    finally:
        reg.stop(delete=True)
        holder.stop()
        client.close()
        srv.stop()
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="ckpt_bench",
        description="restore latency: peer tier vs durable tier",
    )
    parser.add_argument("--mb", type=int, default=64,
                        help="checkpoint size in MB (default 64)")
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument(
        "--durable-latency", type=float, default=0.0,
        help="modeled per-file RTT of a remote durable FS (seconds); "
        "reported separately as durable_restore_s_modeled",
    )
    parser.add_argument("--out", default=None, help="write JSON here")
    parser.add_argument("--json", action="store_true")
    parser.add_argument("--workdir", default=None)
    args = parser.parse_args(argv)

    workdir = args.workdir or tempfile.mkdtemp(prefix="edl-ckpt-bench-")
    try:
        result = run_bench(
            args.mb, max(1, args.trials), args.durable_latency, workdir
        )
    finally:
        if args.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)
    result["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    # run archive (EDL_RUN_ARCHIVE): peer/durable restore timings become
    # indexed rollups so tier-ladder regressions gate via edl_report;
    # the emitted doc carries its bundle name
    from edl_tpu.obs import archive as run_archive

    bundle = run_archive.maybe_archive_bench("ckpt_bench", result, backend="cpu")
    if bundle:
        result["bundle"] = os.path.basename(bundle)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print("wrote %s" % args.out, file=sys.stderr)
    if args.json or not args.out:
        print(json.dumps(result, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
