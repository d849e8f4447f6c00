"""Where the entry points keep JAX's persistent compilation cache, and that
the loadgen client stays off JAX.  Each case runs in a fresh interpreter:
the cache directory is process-wide JAX configuration."""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, **env) -> str:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu", **env)
    out = subprocess.run([sys.executable, "-c", code], env=full,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_from_environment_receives_programs(tmp_path):
    last = _run(
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "where = use_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
        "print(where)",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert last == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    code = ("import jax\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "where = use_compile_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == where\n"
            "print(where)")
    first, second = _run(code), _run(code)
    assert first == second == str(ROOT / ".jax_compile_cache")


def test_loadgen_client_imports_no_jax():
    # a client process on a chip host must not take the chip
    assert _run("import sys, repro.launch.loadgen\n"
                "print('jax' in sys.modules)") == "False"
