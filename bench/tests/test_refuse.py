"""The entry refuses a device that is not a TPU: non-zero exit and no
result line."""
import os
import subprocess
import sys

from conftest import ROOT


def _run(env_extra, cwd=ROOT, entry=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, entry or str(ROOT / "bench" / "run.py"),
         "--workload", "wan13b-17f-lp2", "--seed", str(2 ** 31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_refuses_cpu():
    p = _run({})
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A tree that holds only BENCHMARK.json and bench/ cannot run."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run({}, cwd=tmp_path, entry=str(tmp_path / "bench" / "run.py"))
    assert p.returncode != 0
    assert _no_result(p.stdout)
