"""chip_smoke.py and the compile cache its entry points share.

The smoke script's checks run here at a tiny width on CPU (kernels in
interpret mode); the script itself must refuse to run off the TPU and
must fail when it stands alone, without the repository.  Scripts run in
child processes held to the CPU, so this process's JAX config is
untouched.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_config
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src"),
                **extra})
    return env


def _run(args, cwd, **extra):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=_env(**extra),
                          capture_output=True, text=True, timeout=300)


@pytest.mark.slow
def test_smoke_checks_pass_at_tiny_width():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    lines = []
    cfg = get_config("llama-3.2-1b").reduced(n_layers=2, d_model=64,
                                             vocab=256)
    failures = smoke.smoke(cfg, dict(n_clients=2, local_steps=1,
                                     batch_size=2, prompt_len=4, max_new=4),
                           log=lines.append)
    assert failures == []
    assert any("[per-round] audit ok=True" in l for l in lines)
    assert any("[fused] audit ok=True" in l for l in lines)
    assert any("native quantize/dequantize vs ref.py" in l for l in lines)


def test_smoke_refuses_cpu(tmp_path):
    r = _run([str(SMOKE)], ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / SMOKE.name)
    r = _run([SMOKE.name], tmp_path, PYTHONPATH="",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_cache_goes_where_the_environment_says(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    r = _run(["-c", code], tmp_path, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_cache_defaults_to_the_checkout():
    code = ("import jax\n"
            "from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = _run(["-c", code], ROOT)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(ROOT / ".jax_cache")] * 2
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
