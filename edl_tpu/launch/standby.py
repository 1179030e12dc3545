"""Hot-standby worker shells: pre-paid process start for elastic restages.

The measured anatomy of a stop-resume restage on real TPU
(bench_results/resize_tpu_r4b.json: 26.8 s drain → first step) is almost
entirely worker COLD START: python interpreter + jax/flax/optax imports +
backend init + compile-cache load. The reference pays none of this (its workers re-exec into a warm
Paddle runtime in seconds, /root/reference/python/edl/collective/
launch.py:200-244, because Paddle program build was cheap); a TPU-native
framework must engineer the cost away instead.

A :class:`StandbyPool` keeps ``nproc`` *standby shells* per pod: fully
spawned worker processes (own session, PDEATHSIG armed) that have already
paid the interpreter start and the heavy imports, and then BLOCK on stdin
waiting for an activation message. When the launcher adopts a stage it
activates a standby instead of cold-spawning: one json line carries the
complete worker env, script path, args, and log path; the shell replaces
its environment, redirects stdout/stderr to the worker log, and
``runpy``-executes the training script in-process. The imports overlap
the control-plane convergence window (lease expiry of the dead pod →
drain → re-publish), which is exactly the window a fresh machine joining
a real elastic job would otherwise waste.

Eager backend init: when the elastic window pins the world to ONE worker
(``max_nodes * nproc_per_node == 1`` — the single-chip restart drill, or
any single-host job), the first standby also initializes the jax backend
at spawn, claiming the chip while the control plane converges — and is
the very process that becomes the worker, so the chip keeps one owner.
The launcher spawns the pool only after its device probe has exited.
Multi-worker windows never do this: ``jax.distributed.initialize``
is required to run before backend init, and the coordinator address only
exists after publish. Replacement standbys (spawned while a live stage
owns the chip) never eager-init: everything else a shell pre-pays is
imports, which touch no device.

The standby is a strict fallback chain: a dead/unusable standby (or a
jax-env mismatch between spawn and activation) degrades to the normal
cold spawn in ``start_local_workers`` — activation can never be worse
than not having a pool.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Sequence

from edl_tpu.utils.log import get_logger

logger = get_logger("launch.standby")

# jax reads these at import time; an activation that disagrees with the
# spawn env would run the worker under the wrong platform/flags
_IMPORT_TIME_VARS = (
    "JAX_PLATFORMS", "XLA_FLAGS", "JAX_ENABLE_X64", "LIBTPU_INIT_ARGS",
)


def standby_enabled(cli_flag: bool = False) -> bool:
    env = os.environ.get("EDL_STANDBY", "")
    if env in ("0", "off"):
        return False
    return cli_flag or env == "1"


class StandbyPool:
    """Per-pod pool of pre-imported worker shells.

    ``spawn_env`` is the complete base env for the shells (the launcher's
    env after proxy stripping, plus the job's extra worker env) —
    activation replaces it wholesale with the stage's worker env, but the
    import-time jax variables must already be right at spawn.
    """

    def __init__(
        self,
        spawn_env: Dict[str, str],
        count: int = 1,
        eager: bool = False,
    ) -> None:
        self.spawn_env = dict(spawn_env)
        self.count = max(1, count)
        self._eager_budget = self.count if eager else 0
        self._mu = threading.Lock()
        self._idle: List[subprocess.Popen] = []
        self._stopped = False
        self._respawn_timer: Optional[threading.Timer] = None
        # replacements wait out the fresh workers' own startup (measured:
        # an immediate respawn's jax import contends with the worker's
        # first compile and ADDS downtime), and run niced for the same
        # reason — the initial pool races the first publish un-niced
        # because there is no live worker to protect yet
        self.respawn_delay = float(
            os.environ.get("EDL_STANDBY_RESPAWN_DELAY", "30")
        )
        self.ensure()

    # -- spawning ----------------------------------------------------------

    def _spawn_one(self, nice: bool = False) -> Optional[subprocess.Popen]:
        env = dict(self.spawn_env)
        if self._eager_budget > 0:
            env["EDL_STANDBY_EAGER"] = "1"
            self._eager_budget -= 1
        else:
            env.pop("EDL_STANDBY_EAGER", None)
        cmd = [sys.executable, "-u", "-m", "edl_tpu.launch.standby"]
        if nice:
            cmd = ["nice", "-n", "10"] + cmd
        try:
            proc = subprocess.Popen(  # edl: blocking-ok(fork+exec is ms-scale and top-ups are restage-rare; take() waits at most one pool refill — same budget as launch/process.py)
                cmd,
                env=env,
                stdin=subprocess.PIPE,
                start_new_session=True,
            )
        except OSError as exc:
            logger.warning("standby spawn failed: %s", exc)
            return None
        logger.info(
            "standby shell pid=%d spawned%s%s",
            proc.pid,
            " (eager backend init)" if env.get("EDL_STANDBY_EAGER") else "",
            " (niced replacement)" if nice else "",
        )
        return proc

    def ensure(self, nice: bool = False) -> None:
        """Top the pool back up to ``count`` live shells."""
        with self._mu:
            if self._stopped:
                return
            self._idle = [p for p in self._idle if p.poll() is None]
            while len(self._idle) < self.count:
                proc = self._spawn_one(nice=nice)
                if proc is None:
                    break
                self._idle.append(proc)

    def ensure_later(self) -> None:
        """Schedule a (niced) top-up after ``respawn_delay`` seconds —
        called right after activation, when an immediate respawn would
        contend with the just-activated workers' startup."""
        with self._mu:
            if self._stopped:
                return
            if self._respawn_timer is not None:
                self._respawn_timer.cancel()
            self._respawn_timer = threading.Timer(
                self.respawn_delay, self.ensure, kwargs={"nice": True}
            )
            self._respawn_timer.daemon = True
            self._respawn_timer.start()

    # -- activation --------------------------------------------------------

    def _env_compatible(self, env: Dict[str, str]) -> bool:
        for var in _IMPORT_TIME_VARS:
            if self.spawn_env.get(var, "") != env.get(var, ""):
                logger.info(
                    "standby declined: %s changed between spawn (%r) and "
                    "activation (%r)",
                    var, self.spawn_env.get(var, ""), env.get(var, ""),
                )
                return False
        return True

    def activate(
        self,
        env: Dict[str, str],
        training_script: str,
        training_args: Sequence[str],
        log_path: str = "",
    ) -> Optional[subprocess.Popen]:
        """Turn one standby shell into THE worker; None = use a cold spawn.

        The returned Popen is the worker process (same pid, same session,
        PDEATHSIG already armed); its exit code is the training script's.
        """
        if not self._env_compatible(env):
            return None
        with self._mu:
            while self._idle:
                proc = self._idle.pop(0)
                if proc.poll() is not None:
                    continue
                msg = json.dumps({
                    "env": dict(env),
                    "script": training_script,
                    "args": list(training_args),
                    "log_path": log_path,
                })
                try:
                    proc.stdin.write(msg.encode() + b"\n")
                    proc.stdin.flush()
                    proc.stdin.close()
                except (OSError, ValueError):
                    logger.warning(
                        "standby pid=%d unusable at activation; trying next",
                        proc.pid,
                    )
                    try:
                        proc.kill()
                    except OSError:
                        pass
                    continue
                logger.info(
                    "standby pid=%d activated as worker rank=%s",
                    proc.pid, env.get("EDL_WORKER_RANK", "?"),
                )
                return proc
        return None

    def stop(self) -> None:
        with self._mu:
            self._stopped = True
            if self._respawn_timer is not None:
                self._respawn_timer.cancel()
                self._respawn_timer = None
            procs, self._idle = self._idle, []
        for proc in procs:
            try:
                proc.kill()
            except OSError:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=5.0)
            except (subprocess.TimeoutExpired, OSError):
                pass


# -- the shell child (python -m edl_tpu.launch.standby) ---------------------


def _child_main() -> None:
    # PDEATHSIG first: the shell must die with its launcher exactly like a
    # cold-spawned worker (worker_command's bootstrap arms the same flag)
    try:
        import ctypes
        import signal as _signal

        ctypes.CDLL("libc.so.6", use_errno=True).prctl(
            1, int(_signal.SIGKILL), 0, 0, 0
        )
    except Exception:
        pass  # non-glibc: orphan cleanup degrades to lease TTL

    # the pre-payment: heavy imports now, while the control plane converges.
    # NO device/backend access here unless eager (a live stage may own the
    # chip); model/train modules are import-only.
    at_spawn = dict(os.environ)
    import numpy  # noqa: F401

    try:
        import flax  # noqa: F401
        import jax
        import optax  # noqa: F401

        import edl_tpu.models  # noqa: F401
        import edl_tpu.parallel  # noqa: F401
        import edl_tpu.train.loop  # noqa: F401

        if os.environ.get("EDL_STANDBY_EAGER") == "1":
            # single-worker window: claim the freed chip before the stage
            # publishes (see module docstring for why this is gated)
            try:
                dev = jax.devices()[0]
                logger.info("standby eager backend init: %s", dev.device_kind)
            except Exception as exc:
                logger.warning("standby eager init failed: %s", exc)
    except ImportError as exc:
        logger.warning("standby pre-import incomplete: %s", exc)

    line = sys.stdin.buffer.readline()
    if not line.strip():
        sys.exit(0)  # launcher closed the pipe without activating: retire
    spec = json.loads(line)

    # what the imports themselves exported stays: on a TPU host ``import
    # jax`` appends to LIBTPU_INIT_ARGS, which the compile-cache key hashes
    # — a cold-started worker has it, and a shell that wiped it never hit
    # an entry any other worker wrote (found on the chip: a standby restart
    # recompiled everything)
    imported = {
        k: v for k, v in os.environ.items() if at_spawn.get(k) != v
    }
    env = spec.get("env", {})
    os.environ.clear()
    os.environ.update(env)
    os.environ.update(imported)
    log_path = spec.get("log_path", "")
    if log_path:
        fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        sys.stdout.flush()
        sys.stderr.flush()
        os.dup2(fd, 1)
        os.dup2(fd, 2)
        os.close(fd)

    # cache exchange (train/aot.py): pull compile-cache entries peers
    # already compiled, HERE — the activation window overlaps the control
    # plane's own convergence (lease expiry -> drain -> publish), so the
    # transfer is free wall-clock. EDL_CACHE_PULLED tells train.init()
    # not to pull a second time. Best-effort: any failure degrades to
    # init()'s own bounded pull / a normal compile.
    if (
        os.environ.get("EDL_COMPILE_CACHE_DIR")
        and os.environ.get("EDL_STORE_ENDPOINT")
        and os.environ.get("EDL_CACHE_EXCHANGE", "1") != "0"
    ):
        try:
            from edl_tpu.train.aot import pull_missing

            stats = pull_missing(
                os.environ["EDL_COMPILE_CACHE_DIR"],
                endpoint=os.environ["EDL_STORE_ENDPOINT"],
                job_id=os.environ.get("EDL_JOB_ID", ""),
                own_pod=os.environ.get("EDL_POD_ID", ""),
            )
            # dedupe init()'s pull only when this one actually reached a
            # peer: activating before any manifest exists (or through a
            # store hiccup) returns peers=0, and suppressing the later
            # bounded pull would forfeit entries published moments later
            if stats.get("peers") or stats.get("pulled"):
                os.environ["EDL_CACHE_PULLED"] = "1"
        except Exception as exc:  # noqa: BLE001
            logger.warning("standby cache pull failed: %s", exc)

    import runpy

    script = spec["script"]
    sys.argv = [script] + list(spec.get("args", []))
    # `python script.py` puts the script's directory at sys.path[0];
    # run_path does not — match it, or script-local imports would work
    # cold-spawned but break through the standby fast path
    sys.path.insert(0, os.path.dirname(os.path.abspath(script)))
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    _child_main()
