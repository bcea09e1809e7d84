"""The persistent compilation cache lands in one fixed directory.

Each case runs in a subprocess: the cache is process state that JAX
initializes once, at the first compile."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SNIPPET = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print(enable_compile_cache(sys.argv[1]))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(8.0)).block_until_ready()
""")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compiled_entries_land_in_the_cache_dir(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    want = checkout / ".jax_cache"
    if from_env:
        want = tmp_path / "from-env"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", _SNIPPET, str(checkout)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == [str(want)]
    assert want.is_dir() and any(want.iterdir())
    if from_env:   # no other directory was set
        assert not (checkout / ".jax_cache").exists()
