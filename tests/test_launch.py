"""Entry points: ``serve`` / ``loadtest`` CLIs on the reduced model, the
``--latent`` grammar, and where the compilation cache lives."""
import argparse
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import compile_cache
from repro.launch.serve import build_parser, parse_latent

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def no_cache_dir(monkeypatch, tmp_path):
    """Entry points enable the compile cache; under test, point it at a
    throwaway directory through the variable JAX reads (so the helper
    sets nothing and this process's JAX config is left as it was)."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))


def test_parse_latent():
    assert parse_latent("5x60x104") == (5, 60, 104)
    assert parse_latent("21X60X104") == (21, 60, 104)
    for bad in ("5x60", "5x60x0", "ax60x104", ""):
        with pytest.raises(argparse.ArgumentTypeError):
            parse_latent(bad)


def test_serve_defaults_are_published_shapes():
    args = build_parser().parse_args([])
    assert args.latent == (5, 60, 104) and not args.reduced
    assert args.max_batch == 4
    args = build_parser().parse_args(["--latent", "21x60x104",
                                      "--max-batch", "1", "--reduced"])
    assert args.latent == (21, 60, 104) and args.max_batch == 1
    assert args.reduced


def test_serve_reduced_end_to_end(capsys, no_cache_dir):
    from repro.launch import serve

    serve.main(["--reduced", "--latent", "4x8x12", "--requests", "3",
                "--steps", "2", "--partitions", "2", "--max-batch", "2"])
    out = capsys.readouterr().out
    assert "config: wan21-dit-1.3b-reduced blocks=2 d=128" in out
    lines = [l for l in out.splitlines() if l.startswith("request ")]
    assert len(lines) == 3
    assert all("latent (1, 4, 8, 12, 4)" in l for l in lines)
    # --max-batch 2: two requests ride the first batch, one the second
    assert sorted(l.split("batch=")[1].split()[0] for l in lines) == \
        ["1", "2", "2"]


def test_serve_reduced_hunyuan_video_end_to_end(capsys, no_cache_dir):
    """``--arch`` picks the denoiser by configuration name: HunyuanVideo's
    MMDiT, with pytree conditioning and embedded guidance, through the
    same engine."""
    from repro.launch import serve

    serve.main(["--arch", "hunyuanvideo-dit", "--reduced", "--latent",
                "4x8x12", "--requests", "2", "--steps", "2",
                "--partitions", "2", "--max-batch", "2"])
    out = capsys.readouterr().out
    assert "config: hunyuanvideo-dit-reduced blocks=3 d=128" in out
    lines = [l for l in out.splitlines() if l.startswith("request ")]
    assert len(lines) == 2
    assert all("latent (1, 4, 8, 12, 4)" in l and "batch=2" in l
               for l in lines)


def test_loadtest_reduced_end_to_end(no_cache_dir):
    from repro.launch import loadtest

    report = loadtest.main([
        "--reduced", "--rate", "50", "--requests", "3", "--steps", "2",
        "--partitions", "2", "--max-batch", "1",
        "--mix", "a,shape=4x8x12,priority=interactive"])
    assert report["source"] == "live" and report["warmed"]
    assert report["workload"]["requests"] == 3


CACHE_SCRIPT = textwrap.dedent(
    """
    import sys
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    from pathlib import Path
    compile_cache.DEFAULT_DIR = Path(sys.argv[1])   # stands in for
    # <checkout>/.jax_cache so the test never writes into the checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print("DIR", compile_cache.enable_compile_cache())
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
    """
)


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_goes_to_one_place(tmp_path, env_set):
    default, env_dir = tmp_path / "default", tmp_path / "env"
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if env_set:
        env[compile_cache.ENV_VAR] = str(env_dir)
    res = subprocess.run([sys.executable, "-c", CACHE_SCRIPT, str(default)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert res.returncode == 0, res.stderr
    used, unused = (env_dir, default) if env_set else (default, env_dir)
    assert f"DIR {used}" in res.stdout
    assert used.is_dir() and any(used.iterdir())
    assert not unused.exists()


def test_default_cache_dir_is_fixed_and_ignored():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
