"""``repro.compile_cache``: where the persistent compilation cache goes.

Each case runs a fresh interpreter, because the cache directory is read
once, when JAX first compiles.
"""

import json
import os
import subprocess
import sys

from repro.compile_cache import DEFAULT_CACHE_DIR

ROOT = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = """
import json
import jax, jax.numpy as jnp
import jax.monitoring as mon
hits = []
mon.register_event_listener(
    lambda e, **_: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
from repro.compile_cache import enable_compile_cache
where = enable_compile_cache()
# cache even a program this small
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"dir": where, "hits": len(hits)}))
"""


def _run(env_dir=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_stays_where_the_environment_says(tmp_path):
    out = _run(env_dir=tmp_path)
    assert out["dir"] == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_compile_cache_default_is_in_the_checkout_and_hits():
    assert str(DEFAULT_CACHE_DIR) == os.path.join(ROOT, ".jax_compile_cache")
    first = _run()
    assert first["dir"] == str(DEFAULT_CACHE_DIR)
    second = _run()
    assert second["hits"] >= 1
