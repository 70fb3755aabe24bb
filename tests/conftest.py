"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see exactly
one CPU device (the 512-device override belongs to launch/dryrun.py only;
multi-device tests spawn subprocesses)."""
import jax
import numpy as np
import pytest

from repro.launch.compile_cache import enable_compile_cache

jax.config.update("jax_enable_x64", False)

# Persistent XLA compilation cache: tier-1 runtime is compile-dominated
# (smoke models are tiny), so repeat runs drop most of their wall time.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def assert_tree_finite(tree):
    import jax.numpy as jnp
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        assert jnp.all(jnp.isfinite(leaf.astype(jnp.float32))), path


TOL = {"float32": dict(rtol=2e-4, atol=2e-4),
       "bfloat16": dict(rtol=3e-2, atol=3e-2)}
