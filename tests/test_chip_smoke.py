"""chip_smoke.py rehearsed on the CPU at a tiny size (ISSUE 21).

The script is what the driver runs on the chip; here the same path (a
real agent, the C2M replay, two bursts over HTTP, the host-side checks)
runs in a subprocess with an explicit ``JAX_PLATFORMS=cpu`` and a
cluster of a few hundred nodes. The second case pins that a wave
program which raises ends the run with its error instead of being
served by another program.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
TINY = ["--nodes", "300", "--allocs", "3000", "--jobs", "12"]


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # conftest's 8 virtual CPU devices are for the in-process mesh
    # tests; the smoke on one host device adopts no mesh
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _line(stdout: str, key: str) -> str:
    return next(ln.split(": ", 1)[1] for ln in stdout.splitlines()
                if ln.startswith(key + ": "))


def test_rehearsal_serves_both_bursts(tmp_path):
    proc = subprocess.run(
        [sys.executable, SMOKE, *TINY, "--out", str(tmp_path)],
        env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    assert _line(out, "platform") == "cpu"
    assert "served by joint;" in _line(out, "lean_burst")
    assert "served by joint;" in _line(out, "mixed_burst")
    assert json.loads(out.splitlines()[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # no cache directory named from outside: the fixed path in the
    # checkout, whatever the working directory
    assert _line(out, "compile_cache_dir") == os.path.join(
        REPO, ".jax_cache")


def test_raising_wave_program_fails_the_run(tmp_path):
    """launch_wave no longer catches what a program it chose raises:
    the error reaches the worker, and the smoke exits non-zero with
    it. (At the parent commit the composite served the wave and the
    run looked green.)"""
    cache = str(tmp_path / "cache")
    code = (
        "import runpy, sys\n"
        "from nomad_tpu.parallel import coalesce\n"
        "def boom(*a, **k):\n"
        "    raise RuntimeError('injected wave program failure')\n"
        "coalesce.place_taskgroups_joint_jit = boom\n"
        f"sys.argv = {[SMOKE, *TINY, '--out', str(tmp_path)]!r}\n"
        f"runpy.run_path({SMOKE!r}, run_name='__main__')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env=_env(JAX_COMPILATION_CACHE_DIR=cache),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "injected wave program failure" in proc.stderr
    assert '"ok"' not in proc.stdout
    # a cache directory named from outside is the one in use: the
    # program set none of its own
    assert _line(proc.stdout, "compile_cache_dir") == cache


def test_no_accelerator_is_a_failure(tmp_path):
    """Without a size of the caller's own the run is not a rehearsal:
    it names the devices it found and prints no result."""
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path)],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU: jax.devices() returned [CpuDevice(id=0)]" in proc.stderr
    assert proc.stdout == ""
