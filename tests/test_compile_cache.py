"""Persistent compilation cache across cold processes.

A fleet of spawned workers (fleet/process.py) builds N identical
engines in N fresh JAX runtimes; without the on-disk cache each pays
the full XLA compile of the same sweep program. The contract under
test: with ``JAX_COMPILATION_CACHE_DIR`` set, the FIRST cold process
populates the cache, a SECOND cold process loads instead of compiling
(counted via the persistent-cache hit log line), and the cached run's
results are bitwise identical to the fresh run's. Unset, the cache
goes to the fixed ``<checkout>/.jax_cache`` (parallel/compile_cache.py,
the one rule).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, logging, os, sys
import numpy as np

records = []

class _Cap(logging.Handler):
    def emit(self, record):
        records.append(record.getMessage())

import madsim_tpu
import jax

assert jax.config.jax_compilation_cache_dir == \
    os.environ["JAX_COMPILATION_CACHE_DIR"]

# The persistent-cache layer logs hits/misses under jax's logger tree.
h = _Cap(level=logging.DEBUG)
for name in ("jax", "jax._src.compiler",
             "jax._src.compilation_cache"):
    lg = logging.getLogger(name)
    lg.setLevel(logging.DEBUG)
    lg.addHandler(h)

from madsim_tpu.engine import (DeviceEngine, EngineConfig, RaftActor,
                               RaftDeviceConfig)
from madsim_tpu.parallel.sweep import sweep

cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                   t_limit_us=1_500_000, stop_on_bug=True)
eng = DeviceEngine(RaftActor(RaftDeviceConfig(n=3, buggy_double_vote=True)),
                   cfg)
res = sweep(None, cfg, np.arange(32), engine=eng, chunk_steps=64,
            max_steps=4_000)
hits = sum("persistent compilation cache hit" in m.lower()
           for m in records)
json.dump({"hits": hits,
           "failing": sorted(res.failing_seeds),
           "steps": {k: np.asarray(v).tolist()
                     for k, v in res.observations.items()
                     if k in ("steps", "bug_found", "t_us")}},
          sys.stdout)
"""


def _run_child(cache_dir):
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout)


def test_second_cold_process_reuses_cache(tmp_path):
    cache = tmp_path / "xla_cache"
    fresh = _run_child(cache)
    entries = {p.name for p in cache.iterdir()}
    assert entries, "first process wrote nothing to the cache"
    cached = _run_child(cache)
    # The second cold runtime LOADED the sweep programs it would
    # otherwise compile...
    assert cached["hits"] >= 1, (fresh["hits"], cached["hits"])
    # ...and added no new entries: the program set was fully covered.
    assert {p.name for p in cache.iterdir()} == entries
    # Cached-vs-fresh bitwise: a cache hit must be the SAME executable.
    assert cached["failing"] == fresh["failing"]
    for k in fresh["steps"]:
        np.testing.assert_array_equal(fresh["steps"][k],
                                      cached["steps"][k], err_msg=k)


def test_rule_points_jax_at_the_dir(tmp_path, monkeypatch):
    """With jax already imported, the rule updates jax's config: the
    env var's directory when set, else the fixed checkout path."""
    import jax

    from madsim_tpu.parallel import compile_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        assert cc.apply() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
        target = str(tmp_path / "xla_cache")
        monkeypatch.setenv(cc.ENV_VAR, target)
        assert cc.apply() == target
        assert jax.config.jax_compilation_cache_dir == target
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_package_import_applies_the_rule_before_jax(tmp_path, env_dir):
    """A fresh process that imports madsim_tpu before jax stays jax-free
    on import, and the jax it imports next caches where the rule says —
    and only there."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(REPO, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import sys, madsim_tpu\n"
            "assert 'jax' not in sys.modules\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == want
    if env_dir:
        assert any(os.scandir(want)), "no cache entry written to the dir"
