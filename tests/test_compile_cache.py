"""The entry points' persistent compile cache lands where the docs say:
``$JAX_COMPILATION_CACHE_DIR`` when it is set (and nowhere else), otherwise
the fixed ``<checkout>/.jax_cache``."""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp
    from repro.launch.cache import configure_compile_cache
    print(configure_compile_cache())
    jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0)).block_until_ready()
""")


def _checkout_copy(root):
    """A checkout holding only ``repro/launch/cache.py``: the cache path is
    resolved from that file's location, so the probe never writes into the
    real checkout."""
    launch = root / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    for pkg in (launch.parent, launch):
        (pkg / "__init__.py").write_text("")
    shutil.copy(os.path.join(REPO, "src", "repro", "launch", "cache.py"), launch)
    return root


def _entries(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(tmp_path, from_env):
    checkout = _checkout_copy(tmp_path / "checkout")
    env_dir = tmp_path / "env_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", PROBE, str(checkout / "src")], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    default_dir = checkout / ".jax_cache"
    want, other = (env_dir, default_dir) if from_env else (default_dir, env_dir)
    assert proc.stdout.split()[-1] == str(want)
    assert _entries(want), f"no compiled entries in {want}"
    assert not _entries(other)
