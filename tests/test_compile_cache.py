"""The entry points' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
when it is set, else the fixed <repo>/.jax_cache — and entries land only
in the directory chosen."""
import os
import subprocess
import sys
import textwrap
import uuid
from pathlib import Path

import pytest

from repro.launch.compile_cache import REPO_CACHE

REPO = Path(__file__).resolve().parents[1]

PROBE = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = use_compile_cache()
    assert use_compile_cache() == path          # stable across calls
    assert jax.config.jax_compilation_cache_dir == path
    def {name}(x):
        return jnp.sin(x) * {salt}
    jax.jit({name})(jnp.ones(8)).block_until_ready()
    print(path)
""")


def entries(d: Path, name: str) -> list:
    return sorted(p.name for p in d.glob(f"*{name}*")) if d.is_dir() else []


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_entries_land_only_in_the_chosen_dir(tmp_path, from_env):
    name = f"cache_probe_{uuid.uuid4().hex[:12]}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = PROBE.format(name=name, salt=int(from_env) + 2)
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    chosen = tmp_path / "cc" if from_env else REPO_CACHE
    assert out.stdout.strip().splitlines()[-1] == str(chosen)
    assert REPO_CACHE == REPO / ".jax_cache"
    assert entries(chosen, name), f"no cache entry for {name} in {chosen}"
    other = REPO_CACHE if from_env else tmp_path / "cc"
    assert not entries(other, name), f"{name} was also cached in {other}"
