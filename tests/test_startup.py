"""Start-up at real size: where random weights are built, and where the
compiled programs are kept."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.runtime import config as rc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tp4_mesh():
    return Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("dp", "tp"))


@pytest.mark.parametrize("quantization", [None, "int8"])
def test_init_params_builds_every_leaf_on_its_own_devices(tp4_mesh,
                                                          quantization):
    """Under a mesh no leaf is built on (or left on) the first device: each
    one comes out of its own jit already spread over all four, tp-sharded
    leaves hold a quarter per device, and the values are those of the
    unsharded init bit for bit (same seed, any mesh)."""
    cfg = dataclasses.replace(ModelConfig.tiny(), dtype="bfloat16")
    key = jax.random.key(5)
    sharded = M.init_params(cfg, key, mesh=tp4_mesh,
                            quantization=quantization)
    plain = M.init_params(cfg, key, quantization=quantization)
    flat = jax.tree_util.tree_leaves_with_path(sharded)
    assert len(flat) == len(jax.tree.leaves(plain))
    for (path, x), y in zip(flat, jax.tree.leaves(plain)):
        assert len(x.sharding.device_set) == 4, path
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(y.astype(jnp.float32)))
    # head-major projections [L, heads, width, D] shard whole heads: a
    # quarter of the 4 q heads a device, the 2 KV heads on every device
    wq, wk = (sharded["layers"][k]["q"] if quantization
              else sharded["layers"][k] for k in ("wq", "wk"))
    assert wq.addressable_shards[0].data.shape[1] * 4 == wq.shape[1]
    assert wk.addressable_shards[0].data.shape == wk.shape
    wo = sharded["layers"]["wo"]
    wo = wo["q"] if quantization else wo
    assert wo.addressable_shards[0].data.shape[-2] * 4 == wo.shape[-2]


def test_compile_cache_env_set_means_code_sets_nothing(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert rc.place_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_unset_is_one_fixed_dir_in_the_checkout(monkeypatch):
    """Unset: a fixed, git-ignored directory inside the checkout — the same
    across two calls and in another process (the path is part of the
    cache key; a temp name, pid or time would never hit)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = rc.place_compile_cache(), rc.place_compile_cache()
        assert first == second == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == os.path.join(REPO, ".jax_compile_cache")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("from dynamo_tpu.runtime.config import place_compile_cache;"
            "print(place_compile_cache())")
    other = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, check=True)
    assert other.stdout.strip() == first
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_compile_cache/" in ignored
