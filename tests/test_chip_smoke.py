"""chip_smoke.py and what it stands on, rehearsed without a chip.

The script's phases run here at tiny sizes on the CPU (and on four virtual
devices) through the same launcher, trainer and cache code the chip run
takes — the platform each phase insists on is steered HERE, through the
config the phase functions receive, never through an option of the
program. Beside them: the control plane stays off jax, the compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says (or to one fixed path), and
the three private jax seams the cache plane patches still have the
signatures of the pinned jax 0.9.0 — their drift guards are gone, so a jax
that moves them fails here, loudly, instead of degrading at run time.
"""

import inspect
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
TOY_WORKER = os.path.join(REPO, "tests", "toy_worker.py")

import chip_smoke  # the parent half: stdlib + numpy, no jax

TINY_VISION = {"model": "resnet_tiny", "batch": 8, "image": 32, "classes": 10}
TINY = {
    "platform": "cpu",  # the steer: chip_smoke.main() always says "tpu"
    "seed": 0,
    "train": dict(TINY_VISION, steps=2, epochs=1),
    "teacher": dict(TINY_VISION, batch=4, calls=3),
    "mesh": dict(TINY_VISION, steps=2, axes={"dp": 2, "fsdp": 2}),
}


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for name in ("EDL_DEVICES_PER_PROC", "JAX_COMPILATION_CACHE_DIR",
                 "EDL_COMPILE_CACHE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    env.update(extra)
    return env


def _python(code, **extra):
    return subprocess.run(
        [sys.executable, "-c", code], env=_env(**extra), cwd=REPO,
        capture_output=True, text=True, timeout=180,
    )


def _json_lines(text):
    return [json.loads(l) for l in text.splitlines() if l.startswith("{")]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """One placed cache for the rehearsals. A phase writes a handful of
    programs (the key's two, ``jit_init_state``, ``jit_step``, no longer
    one an init operation): the resumed launch loads the cold one's, and
    the mesh phase, on its own mesh, shares only the key's."""
    return str(tmp_path_factory.mktemp("xla"))


@pytest.fixture()
def rehearsal(tmp_path, monkeypatch, cache_dir):
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "logs"))
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    monkeypatch.delenv("EDL_DEVICES_PER_PROC", raising=False)
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # conftest's 8 devices
    return tmp_path


# -- the script itself --------------------------------------------------------


def test_without_a_chip_the_smoke_fails_and_says_so():
    out = subprocess.run(
        [sys.executable, SMOKE], env=_env(), cwd=REPO,
        capture_output=True, text=True, timeout=180,
    )
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False  # no fallback to the CPU
    assert last["device"]["platform"] == "cpu"
    # the parent asserts "jax" not in sys.modules before that last line:
    # reaching it means the assertion held
    assert "AssertionError" not in out.stderr


def test_smoke_parent_imports_no_jax():
    out = _python(
        "import sys, chip_smoke; "
        "from edl_tpu.distill import PredictClient; "
        "sys.exit('jax' in sys.modules)"
    )
    assert out.returncode == 0, out.stderr[-800:]


def test_alone_in_a_directory_the_smoke_prints_no_result(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    env = _env()
    env.pop("PYTHONPATH")
    out = subprocess.run(
        [sys.executable, str(alone)], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode != 0
    assert not _json_lines(out.stdout)


def test_train_phase_resumes_on_a_cache_hit(rehearsal, capsys):
    """Cold launch, then a new job id on the same checkpoint and cache —
    through the real launcher, with no EDL_DEVICES_PER_PROC."""
    ok, device = chip_smoke.run_phases(TINY, [chip_smoke.phase_train])
    lines = _json_lines(capsys.readouterr().out)
    assert ok, lines
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    cold, resumed = lines
    assert cold["launch"] == "cold" and cold["cache"]["miss"] > 0
    assert "jit_step" in cold["missed_modules"]  # the cache_load spans name them
    assert resumed["launch"] == "resumed"
    assert resumed["cache"]["hit"] > 0 and resumed["cache"]["miss"] == 0
    assert resumed["missed_modules"] == {}
    assert resumed["step"] == 2 * cold["step"] == 4
    assert resumed["first_step_seconds"] > 0
    # both jobs cached where the variable says, and nowhere else
    assert resumed["cache_dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]


def test_mesh_phase_on_four_virtual_devices(rehearsal, capsys, monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    ok, device = chip_smoke.run_phases(TINY, [chip_smoke.phase_mesh])
    lines = _json_lines(capsys.readouterr().out)
    assert ok, lines
    assert device["count"] == 4
    (mesh,) = lines
    assert mesh["leaf_device_span"] == [4]
    assert mesh["leaves_sharded"] > 0
    assert mesh["collectives"]["all-reduce"] > 0
    assert len(mesh["mesh_losses"]) == len(mesh["one_device_losses"]) == 2


def test_teacher_phase_answers_through_the_client(rehearsal, capsys):
    ok, _ = chip_smoke.run_phases(TINY, [chip_smoke.phase_teacher])
    lines = _json_lines(capsys.readouterr().out)
    assert ok, lines
    assert lines[0]["shape"] == [4, 10]


def test_a_phase_on_the_wrong_platform_fails(rehearsal, capsys):
    wrong = dict(TINY, platform="tpu")  # what main() asks for, on a CPU
    ok, _ = chip_smoke.run_phases(wrong, [chip_smoke.phase_teacher])
    lines = _json_lines(capsys.readouterr().out)
    assert not ok and lines[-1]["ok"] is False


@pytest.mark.parametrize("line", [
    "elastic-trainer: health monitor unavailable (boom); continuing "
    "without graceful drain",
    "elastic-trainer: memory plane unavailable (boom); continuing without it",
    "elastic-trainer: capture plane unavailable (boom); continuing without it",
    "elastic-trainer: aot ladder unavailable (boom); resizes will compile "
    "on arrival",
    "cache pull failed (boom); continuing uncached",
])
def test_a_degraded_trainer_line_fails_the_phase(line):
    with pytest.raises(chip_smoke.PhaseFailed):
        chip_smoke._check_worker_log("epoch 0 loss 1.0\n%s\n" % line)


def test_missed_modules_are_named(monkeypatch):
    """What a worker reports as ``missed_modules``: the foreground's
    ``cache_load`` spans that found nothing, not the ladder's."""
    import time

    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import aot

    tracer = obs_trace.SpanTracer("test")
    for module, hit, ladder in (("jit_step", False, False), ("jit_mul", True, False),
                                ("jit_step", False, True), ("jit_add", False, False)):
        tracer.record("cache_load", time.monotonic(), 0.1, module=module,
                      hit=hit, ladder=ladder)
    monkeypatch.setattr(obs_trace, "get_tracer", lambda *a: tracer)
    assert aot.missed_modules() == ["jit_step", "jit_add"]
    assert chip_smoke._check_worker_log("epoch 0 loss 1.0\n") is None


# -- one process per chip: the control plane stays off jax --------------------


def test_launcher_without_device_pin_never_imports_jax(tmp_path):
    """README path, default compile cache armed (so the cache exchange
    starts), no EDL_DEVICES_PER_PROC: the device count comes from a child."""
    code = (
        "import sys\n"
        "from edl_tpu.launch.launcher import main\n"
        "rc = main(['--job_id', 'nojax', '--store', '127.0.0.1:%d', "
        "'--embed_store', '--nodes_range', '1:1', '--nproc_per_node', '1', "
        "'--compile_cache_dir', %r, %r])\n"
        "assert rc == 0, rc\n"
        "sys.exit('jax' in sys.modules)\n"
    ) % (chip_smoke.find_free_ports(1)[0], str(tmp_path / "xla"), TOY_WORKER)
    out = _python(code, TEST_OUT_DIR=str(tmp_path), TEST_EXIT_AFTER="0.1")
    assert out.returncode == 0, out.stderr[-1500:]
    assert any(n.startswith("run.") for n in os.listdir(tmp_path))


def test_probe_asks_a_child_and_the_pin_asks_nobody(monkeypatch):
    from edl_tpu.cluster import job_env

    assert job_env.probe_devices(_env()) == (1, "cpu", "cpu")
    monkeypatch.setattr(
        job_env.subprocess, "run",
        lambda *a, **k: pytest.fail("the pin must not spawn a probe"),
    )
    pinned = job_env.probe_devices(_env(EDL_DEVICES_PER_PROC="3"))
    assert (pinned.count, pinned.platform) == (3, "cpu")


def test_probe_that_finds_no_device_is_an_error():
    from edl_tpu.cluster.job_env import probe_devices

    with pytest.raises(RuntimeError, match="device probe failed"):
        probe_devices(_env(JAX_PLATFORMS="no_such_platform"))


def test_launcher_refuses_a_second_chip_owner_on_tpu(monkeypatch):
    from edl_tpu.cluster.job_env import JobEnv, LocalDevices
    from edl_tpu.launch import launcher

    monkeypatch.setattr(
        launcher, "probe_devices",
        lambda env: LocalDevices(4, "tpu", "TPU v5 lite"),
    )
    job = JobEnv(
        job_id="refuse", store_endpoint="127.0.0.1:1", nodes_range="1:2",
        nproc_per_node=2,
    )
    with pytest.raises(ValueError, match="nproc_per_node 2 on a TPU host"):
        launcher.ElasticLauncher(job, TOY_WORKER)


# -- the compile cache is placeable -------------------------------------------

_CACHED_JIT = (
    "import os, sys, json\n"
    "from edl_tpu.train import init, aot\n"
    "env = init()\n"
    "import jax, jax.numpy as jnp\n"
    "jax.jit(lambda x: jnp.tanh(x @ x.T).sum())(jnp.ones((32, 32)))"
    ".block_until_ready()\n"
    "print(json.dumps({'dir': jax.config.jax_compilation_cache_dir, "
    "'env_dir': env.compile_cache_dir, 'counts': aot.cache_event_counts()}))\n"
)


def test_placed_cache_wins_over_every_other_setting(tmp_path):
    placed, other = tmp_path / "placed", tmp_path / "other"
    out = _python(
        _CACHED_JIT, JAX_COMPILATION_CACHE_DIR=str(placed),
        EDL_COMPILE_CACHE_DIR=str(other), EDL_JOB_ID="j",
    )
    assert out.returncode == 0, out.stderr[-1500:]
    doc = _json_lines(out.stdout)[-1]
    assert doc["dir"] == doc["env_dir"] == str(placed)
    assert doc["counts"]["write"] > 0
    assert any(n.endswith("-cache") for n in os.listdir(placed))
    assert not other.exists()


def test_job_env_follows_the_placed_cache(monkeypatch, tmp_path):
    from edl_tpu.cluster.job_env import JobEnv, WorkerEnv

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("EDL_COMPILE_CACHE_DIR", "/somewhere/else")
    for flag in (None, "/flag/dir", "none"):
        job = JobEnv(job_id="j", compile_cache_dir=flag)
        assert job.compile_cache_dir == str(tmp_path)
    assert WorkerEnv().compile_cache_dir == str(tmp_path)


def test_two_job_ids_share_one_fixed_default(monkeypatch):
    from edl_tpu.cluster.job_env import JobEnv, default_compile_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("EDL_COMPILE_CACHE_DIR", raising=False)
    a, b = JobEnv(job_id="first"), JobEnv(job_id="second")
    assert a.compile_cache_dir == b.compile_cache_dir
    assert a.compile_cache_dir == default_compile_cache_dir()
    assert a.compile_cache_dir == os.path.join(REPO, ".cache", "xla")
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".cache/" in ignored


def test_group_writable_handed_in_dir_is_used_not_skipped(tmp_path):
    """The old check left such a run silently uncached."""
    placed = tmp_path / "shared"
    placed.mkdir()
    os.chmod(placed, 0o777)
    out = _python(_CACHED_JIT, JAX_COMPILATION_CACHE_DIR=str(placed),
                  EDL_JOB_ID="j")
    assert out.returncode == 0, out.stderr[-1500:]
    assert _json_lines(out.stdout)[-1]["counts"]["write"] > 0
    assert "uncached" not in out.stderr


def test_unusable_cache_dir_is_an_error_not_an_uncached_run(tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    out = _python(_CACHED_JIT, EDL_COMPILE_CACHE_DIR=str(blocker / "xla"),
                  EDL_JOB_ID="j")
    assert out.returncode != 0
    assert "is unusable" in out.stderr


def test_a_variable_set_after_jax_import_is_refused(monkeypatch, tmp_path):
    import jax  # noqa: F401 — already imported: the variable comes too late

    from edl_tpu.train.context import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "late"))
    with pytest.raises(RuntimeError, match="after jax was imported"):
        enable_compilation_cache(str(tmp_path / "x"))


def test_no_code_assigns_a_cache_dir_when_the_variable_is_set():
    """``grep -rn jax_compilation_cache_dir --include=*.py``: one update,
    and it sits under ``if not placed``."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in ("tests", "build")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            text = open(path, errors="replace").read()
            for m in re.finditer(
                r'jax\.config\.update\(\s*"jax_compilation_cache_dir"', text
            ):
                hits.append((os.path.relpath(path, REPO), text[:m.start()]))
    assert [h[0] for h in hits] == ["edl_tpu/train/context.py"]
    assert hits[0][1].rstrip().endswith("if not placed:")


# -- the private jax seams of the cache plane (jax 0.9.0) ---------------------


@pytest.mark.parametrize("module,name,params", [
    ("jax._src.compiler", "_cache_write",
     ["cache_key", "compile_time_secs", "module_name", "backend",
      "executable", "host_callbacks"]),
    ("jax._src.cache_key", "_hash_accelerator_config",
     ["hash_obj", "accelerators"]),
    ("jax._src.cache_key", "_hash_devices", ["hash_obj", "devices"]),
    ("jax._src.compilation_cache", "get_executable_and_time",
     ["cache_key", "compile_options", "backend", "executable_devices"]),
    ("jax._src.compilation_cache", "put_executable_and_time",
     ["cache_key", "module_name", "executable", "backend", "compile_time"]),
])
def test_private_seam_has_the_pinned_signature(module, name, params):
    """No drift guard keeps another jax alive: the patches are written
    against exactly these signatures (the seed's were not, and on jax 0.9.0
    one crashed every cached compile and one turned every read into a
    miss)."""
    import importlib

    fn = getattr(importlib.import_module(module), name)
    fn = inspect.unwrap(fn)
    got = [p for p in inspect.signature(fn).parameters if not p.startswith("_")]
    assert got == params


def test_all_three_patches_apply_and_count(tmp_path):
    code = (
        "import json\n"
        "from edl_tpu.train.context import enable_compilation_cache\n"
        "from edl_tpu.train import aot\n"
        "enable_compilation_cache(%r)\n"
        "import jax, jax.numpy as jnp\n"
        "from jax._src import cache_key, compilation_cache, compiler\n"
        "f = jax.jit(lambda x: x * 2 + 1)\n"
        "f(jnp.ones(4)).block_until_ready()\n"
        "print(json.dumps({'all_ranks': compiler._cache_write._edl_all_ranks,"
        " 'portable': cache_key._hash_accelerator_config._edl_portable,"
        " 'events': compilation_cache.get_executable_and_time._edl_events,"
        " 'xla_caches': jax.config.jax_persistent_cache_enable_xla_caches,"
        " 'counts': aot.cache_event_counts()}))\n"
    ) % str(tmp_path / "xla")
    first = _json_lines(_python(code).stdout)[-1]
    assert first["all_ranks"] and first["portable"] and first["events"]
    assert first["xla_caches"] == "none"
    assert first["counts"]["miss"] == first["counts"]["write"] > 0
    second = _json_lines(_python(code).stdout)[-1]
    assert second["counts"]["hit"] == first["counts"]["miss"]
    assert second["counts"]["miss"] == 0


def test_standby_shell_keeps_what_jax_import_exported(tmp_path):
    """Found on the chip: ``import jax`` on a TPU host appends to
    LIBTPU_INIT_ARGS (part of every compile-cache key); activation wiped
    the environment, so a standby worker missed every cache entry."""
    from edl_tpu.launch.standby import StandbyPool

    script = tmp_path / "env.py"
    script.write_text(
        "import json, os\n"
        "json.dump({k: os.environ.get(k) for k in "
        "('LIBTPU_INIT_ARGS', 'SPAWN_ONLY', 'EDL_WORKER_RANK')}, "
        "open(os.environ['ENV_OUT'], 'w'))\n"
    )
    out = tmp_path / "env.json"
    # JAX_FORCE_TPU_INIT: jax's import then exports what it would on a TPU VM
    spawn = _env(JAX_FORCE_TPU_INIT="1", SPAWN_ONLY="stale")
    pool = StandbyPool(spawn, count=1)
    try:
        activation = _env(
            JAX_FORCE_TPU_INIT="1", ENV_OUT=str(out), EDL_WORKER_RANK="0"
        )
        proc = pool.activate(activation, str(script), [])
        assert proc is not None and proc.wait(timeout=120) == 0
    finally:
        pool.stop()
    seen = json.loads(out.read_text())
    assert "--xla_tpu_use_enhanced_launch_barrier" in seen["LIBTPU_INIT_ARGS"]
    assert seen["SPAWN_ONLY"] is None  # the wholesale replacement stands
    assert seen["EDL_WORKER_RANK"] == "0"
