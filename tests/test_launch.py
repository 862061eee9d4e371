"""Launcher plumbing: the persistent compile cache's location, and the chip
smoke's refusal to report a result without a TPU. Each case runs in a fresh
interpreter, since JAX reads its platform and cache settings once."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, env_extra=None, drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins when set, and compiled programs land
    there; otherwise the fixed in-checkout directory is used."""
    code = ("import jax, jax.numpy as jnp, json\n"
            "from repro.launch import compile_cache\n"
            "path = compile_cache.enable()\n"
            + ("jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
               if from_env else "")
            + "print(json.dumps([path, jax.config.jax_compilation_cache_dir]))")
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else {}
    out = _run(["-c", code], REPO, env,
               drop=() if from_env else ("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    path, configured = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache")
    assert path == configured == want
    if from_env:
        assert any(tmp_path.iterdir()), "no compiled program was cached"


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    """On the CPU, or copied out of the checkout, the smoke exits non-zero
    and prints no result line."""
    if alone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = tmp_path
    else:
        cwd = REPO
    out = _run(["chip_smoke.py"], cwd, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
