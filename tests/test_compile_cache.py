"""Persistent compile cache: the placement rule (jax's own
JAX_COMPILATION_CACHE_DIR when set, else ``.jax_cache`` in the checkout)
and the cross-process warm-start guarantee.

conftest.py turns jax's cache master switch off for the session, so the
in-process tests here only look at configuration; the processes below
switch it back on for themselves."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from paddle_tpu.observability import compilecache

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def config_updates(monkeypatch):
    """Record what enable_persistent_cache() asks jax.config to change,
    without changing it (nothing to restore, nothing bleeds)."""
    import jax
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    compilecache.reset_for_tests()
    yield calls
    monkeypatch.undo()
    compilecache.reset_for_tests()


def test_env_branch_sets_no_directory(tmp_path, monkeypatch, config_updates):
    """With JAX_COMPILATION_CACHE_DIR set, jax's own handling places the
    cache: enabling must not touch ``jax_compilation_cache_dir``."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    placed_by_jax = jax.config.jax_compilation_cache_dir
    assert compilecache.enable_persistent_cache() == placed_by_jax
    assert "jax_compilation_cache_dir" not in config_updates
    # the floors are zeroed on both branches, and the call is idempotent
    assert config_updates == {
        "jax_persistent_cache_min_compile_time_secs": 0,
        "jax_persistent_cache_min_entry_size_bytes": -1}
    config_updates.clear()
    assert compilecache.enable_persistent_cache() == placed_by_jax
    assert not config_updates


def test_unset_branch_is_a_fixed_directory_in_the_checkout(
        monkeypatch, config_updates):
    """No variable: one fixed path beside the package — never a temp
    name, pid or time (the path is part of what makes a cache hit)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compilecache.enable_persistent_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want
    assert compilecache.persistent_cache_dir() == want


_WORKLOAD = r"""
import os, sys, json
import jax, jax.numpy as jnp
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.compilecache import enable_persistent_cache
assert enable_persistent_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]

@jax.jit
def f(x, y):
    return jnp.tanh(x @ y) + x.sum()

@jax.jit
def g(x):
    return jnp.sort(x * 3.0)[::-1]

x = jnp.ones((16, 16)); v = jnp.arange(32.0)
f(x, x).block_until_ready()
g(v).block_until_ready()
reg = get_registry()
print(json.dumps({
    "hits": reg.counter("compile.persistent_cache_hits").value,
    "requests": reg.counter("compile.persistent_cache_requests").value,
}))
"""


@pytest.mark.slow
def test_warm_start_compiles_nothing(tmp_path):
    """A second process with the same program shapes loads every
    executable from disk — persistent hits equal the cacheable compile
    requests and no XLA compilation runs fresh."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_ENABLE_COMPILATION_CACHE="1", JAX_PLATFORMS="cpu")
    env.pop("PTPU_METRICS_DIR", None)

    def run():
        out = subprocess.run([sys.executable, "-c", _WORKLOAD],
                             cwd=str(REPO), capture_output=True, text=True,
                             timeout=300, env=env)
        assert out.returncode == 0, out.stderr
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["hits"] == 0            # nothing cached yet
    assert cold["requests"] >= 2        # both functions went to the cache
    assert os.listdir(str(tmp_path / "cc"))  # executables persisted
    warm = run()
    assert warm["requests"] >= 2
    assert warm["hits"] == warm["requests"]  # 0 fresh compiles
