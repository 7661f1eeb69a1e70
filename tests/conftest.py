"""Test harness configuration.

All tests run on CPU JAX with 8 virtual devices — the standard way to test
pjit/mesh/collective code without real TPU chips (SURVEY.md §4). Must run
before jax initializes, hence the env mutation at import time.

Statistical-test convention (the `statistical` pytest marker): tests that
check an EMPIRICAL distribution (rejection-sampling speculation vs vanilla
sampling, tests/test_speculative.py) must be deterministic and non-flaky
in tier-1, so they follow three rules:

1. **Fixed seeds everywhere.** Every random draw derives from a literal
   seed in the test (jax.random.key(N) / fold_in chains); reruns are
   bit-identical, so a passing test stays passing — the tolerance
   documents observed-vs-expected distance, it does not absorb run-to-run
   noise.
2. **Explicit tolerance with a stated basis.** Chi-square against the
   closed-form distribution where one exists (threshold = a named
   percentile of the chi-square at the test's degrees of freedom, e.g.
   the 99.99th). Where only sampling can estimate both sides, bound the
   total-variation distance by a NULL BASELINE: the same statistic
   computed between two vanilla runs at disjoint fixed seeds and equal
   sample count, plus a stated margin — never a bare magic constant.
3. **Sample counts sized to the tolerance.** Pick N so the null
   statistic sits well under the bound (binomial noise ~ sqrt(p/N));
   if a test needs N large enough to be slow, it carries
   `@pytest.mark.slow` too and a fast-lane sibling covers the same
   property at reduced N.
"""

import os

# Hold JAX to the CPU and give it 8 virtual devices. Both are read when JAX
# is imported / its backend comes up, hence before the import below.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from llm_based_apache_spark_optimization_tpu.utils.jaxenv import (  # noqa: E402
    place_compile_cache,
)

# Persistent XLA compilation cache: scheduler instances build fresh
# @jax.jit closures, so every ContinuousBatchingScheduler construction
# would otherwise recompile byte-identical programs (the cache keys on
# the lowered module hash, not function identity). Tier-1 builds
# dozens of schedulers from a handful of configs; deduping the
# compiles is the difference between the suite fitting its wall-clock
# budget and not. Placed like every entry point's (utils/jaxenv.py:
# JAX_COMPILATION_CACHE_DIR, or the fixed in-checkout directory); the
# programs here are tiny, so nothing is too quick to be worth keeping.
place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny_model():
    """(cfg, params) for the tiny test config, f32 for CPU exactness."""
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    params = init_params(TINY, jax.random.key(0), dtype=jnp.float32)
    return TINY, params


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
