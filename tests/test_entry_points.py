"""Entry-point contracts of the bring-up: no CPU stand-in for the chip,
and a persistent compile cache placed from outside.

- ``perfbench/run.py`` (every cell of ``BENCHMARK.json``) and
  ``chip_smoke.py`` measure or prove the chip: without a TPU they exit
  non-zero and print no result line (no rate, no ok).
- ``utils.compile_cache.enable_compile_cache`` (called by the
  program's entry points): ``JAX_COMPILATION_CACHE_DIR`` wins and the
  code sets nothing; otherwise the cache lives at the fixed
  ``<repo>/.jax_cache/``.

Every case runs in a subprocess: the CPU pin and cache settings of the
test process itself must not leak into what is being checked.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args, *, cwd=REPO, env=None, timeout=300):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.update(env or {})
    return subprocess.run([sys.executable, *args], cwd=str(cwd),
                          env=full, capture_output=True, text=True,
                          timeout=timeout)


def _json_lines(stdout: str):
    return [ln for ln in stdout.splitlines() if ln.strip().startswith("{")]


@pytest.mark.parametrize("cell", [
    w["name"] for w in
    json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]])
def test_benchmark_refuses_cpu(cell):
    proc = _run([str(REPO / "perfbench" / "run.py"), "--workload", cell,
                 "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout), proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_chip_smoke_refuses_cpu():
    proc = _run([str(REPO / "chip_smoke.py")])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied out of the repo, the script has nothing to drive."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)


_CACHE_PROBE = (
    "import jax\n"
    "from dmclock_tpu.utils.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "jax.jit(lambda x: x * 3 + 1)(2).block_until_ready()\n")


def _repo_cache_entries():
    d = REPO / ".jax_cache"
    return sorted(p.name for p in d.iterdir()) if d.is_dir() else None


def test_cache_dir_from_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there, and the repo
    cache is untouched."""
    before = _repo_cache_entries()
    cache = tmp_path / "cache"
    proc = _run(["-c", _CACHE_PROBE], env={
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_ENABLE_COMPILATION_CACHE": "true",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.splitlines()[:2] == [str(cache)] * 2
    assert any(cache.iterdir())
    assert _repo_cache_entries() == before


def test_cache_dir_defaults_to_repo():
    """Unset: the fixed <repo>/.jax_cache path, never a tmp name.
    (Only the setting is checked; nothing is compiled into the repo.)"""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import jax\n"
            "from dmclock_tpu.utils.compile_cache import "
            "enable_compile_cache\n"
            "enable_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(REPO / ".jax_cache")
    # and the git-ignored path is what the helper uses
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
