"""Installed-package smoke test (VERDICT r4 #7).

Everything else in the suite runs from the checkout via PYTHONPATH; this
file is the one place the package is actually BUILT and INSTALLED — a
fresh venv, ``pip install .``, then the console scripts and the
Dockerfile's CMD module driven end-to-end from the installed copy with
the checkout deliberately off sys.path. Catches what structure-only
checks cannot: a module missing from packages.find, package-data (the
attention dispatch calibration) dropped from the wheel, a console script
pointing at a function that doesn't exist, or a dependency pin no
environment can satisfy (``pip check`` validates Requires-Dist against
the installed world).

Zero-egress constraints shape the mechanics: the venv shares the host's
site-packages (numpy/psutil/jax come from there — pip cannot download),
and the install runs ``--no-deps --no-build-isolation``; ``pip check``
then still verifies the declared pins against what is present.
"""

import json
import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def venv(tmp_path_factory):
    """A venv with edl-tpu pip-installed; yields its bin dir."""
    import sysconfig

    root = tmp_path_factory.mktemp("venv")
    subprocess.run(
        [sys.executable, "-m", "venv", str(root)], check=True,
    )
    # the dev environment is ITSELF a venv, so --system-site-packages
    # would expose the wrong prefix; a .pth makes the host environment's
    # packages (numpy/psutil/jax AND setuptools for the build) visible
    host_purelib = sysconfig.get_paths()["purelib"]
    venv_purelib = (
        root / "lib" / ("python%d.%d" % sys.version_info[:2])
        / "site-packages"
    )
    (venv_purelib / "_host_env.pth").write_text(host_purelib + "\n")
    bin_dir = root / "bin"
    pip = str(bin_dir / "pip")
    out = subprocess.run(
        [pip, "install", "--no-deps", "--no-build-isolation",
         "--no-index", REPO],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, "pip install . failed:\n" + out.stderr[-2000:]
    return bin_dir


def _run(cmd, timeout=60, env_extra=None, cwd=None):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)  # the checkout must NOT rescue imports
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [str(c) for c in cmd], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=cwd or "/tmp",
    )


def test_pip_check_validates_pins(venv):
    # The venv sees the host's site-packages through the .pth, so pip
    # check also re-reports the host's own conflicts (e.g. google-cloud
    # pins protobuf<6 while the host ships 6.x).  Those predate the
    # install and are not ours to fix: baseline them from the host
    # interpreter and fail only on NEW lines, which can only come from
    # edl-tpu's Requires-Dist.
    baseline = _run([sys.executable, "-m", "pip", "check"], timeout=120)
    preexisting = set(baseline.stdout.splitlines())
    out = _run([venv / "pip", "check"], timeout=120)
    new = [l for l in out.stdout.splitlines()
           if l.strip() and l not in preexisting]
    assert not new, "edl-tpu introduced dependency conflicts:\n" + "\n".join(new)


def test_console_scripts_exist_and_answer_help(venv):
    for script in (
        "edl-store", "edl-launch", "edl-register",
        "edl-discovery-server", "edl-resize", "edl-status",
    ):
        path = venv / script
        assert path.exists(), "console script %s not installed" % script
        out = _run([path, "--help"], timeout=60)
        assert out.returncode == 0, "%s --help failed:\n%s" % (
            script, out.stderr[-800:],
        )


def test_dockerfile_cmd_module_serves(venv, tmp_path):
    """The image's CMD (python -m edl_tpu.store.server) must run from the
    installed package and actually serve."""
    with open(os.path.join(REPO, "docker", "Dockerfile")) as f:
        cmd_lines = [l for l in f if l.strip().startswith("CMD")]
    assert cmd_lines, "Dockerfile has no CMD"
    argv = json.loads(cmd_lines[-1].strip()[len("CMD"):].strip())
    assert argv[:2] == ["python", "-m"], argv
    # port 0 instead of the image's fixed port: the host may be busy
    module_argv = [venv / "python", "-m", argv[2], "--port", "0"]
    proc = subprocess.Popen(
        [str(c) for c in module_argv],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
        cwd="/tmp",
    )
    try:
        deadline = time.time() + 30
        line = ""
        while time.time() < deadline:
            line = proc.stdout.readline()
            if "serving" in line:
                break
        assert "serving" in line, "store never announced serving: %r" % line
    finally:
        proc.kill()
        proc.wait()


def test_launch_toy_job_from_installed_package(venv, tmp_path):
    """Full control-plane drill from the installed copy: edl-launch with
    an embedded store runs a worker to completion, edl-status reads the
    job back. The worker script lives OUTSIDE the repo and imports
    nothing from it."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "with open(os.environ['OUT'], 'w') as f:\n"
        "    f.write(os.environ['EDL_STAGE'])\n"
    )
    marker = tmp_path / "ran"
    out = _run(
        [venv / "edl-launch", "--job_id", "inst1",
         "--store", "127.0.0.1:29641", "--embed_store",
         "--nodes_range", "1:1", "--ttl", "1.0", str(script)],
        timeout=120, env_extra={"OUT": str(marker)}, cwd=str(tmp_path),
    )
    assert out.returncode == 0, (
        "edl-launch failed rc=%d:\n%s" % (out.returncode, out.stderr[-1500:])
    )
    assert marker.exists() and marker.read_text(), "worker never ran"
