"""Test harness configuration.

Multi-"device" SPMD tests run on a virtual 8-device CPU mesh in-process —
strictly better than the reference's subprocess-localhost harness
(test_dist_base.py:743), per SURVEY.md §4 note 5.

XLA_FLAGS and JAX_PLATFORMS must be set before jax is imported: jax reads
both once, at import and at backend initialization.
"""
import os

# the caller's platform choice BEFORE the harness forces cpu below: the
# tier-1 command sets JAX_PLATFORMS=cpu explicitly, and the slow-test
# budget guard keys off that declared intent, not the forced value
_CALLER_PLATFORMS = os.environ.get("JAX_PLATFORMS")

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# persistent XLA compilation cache, shared by every test process
# (subprocess tests inherit the env var): the suite is dominated by
# compile time, and a warm cache cuts repeat runs well under the tier-1
# wall-clock budget.  Entries are keyed by program hash + compile
# options, so the multi-device/launch children can share the directory
# safely; the dir is repo-local and untracked.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.05")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Tier-1 time-budget guard: the CPU suite runs ~630s warm-cache
    (~1040s cold) against ROADMAP.md's 1260s tier-1 timeout, so
    sweep-sized serving tests must not sneak in even when the
    ``-m 'not slow'`` filter is forgotten.  Slow-marked
    tests in test_serving.py are SKIPPED on the CPU tier unless
    RUN_SLOW=1 (other modules' slow tests keep their usual opt-in
    semantics: subprocess/launcher suites run under ``-m slow``).
    Budget-hunting tip: ``pytest --durations=15`` names the slowest
    tests; anything >5s belongs behind the ``slow`` marker."""
    if _CALLER_PLATFORMS != "cpu" or os.environ.get("RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(
        reason="slow serving test skipped under the CPU tier-1 time "
               "budget; set RUN_SLOW=1 to run it")
    for item in items:
        if "slow" in item.keywords and \
                item.fspath.basename == "test_serving.py":
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.RandomState(0)
