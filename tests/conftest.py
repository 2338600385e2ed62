"""Tier-1 test harness config: persistent XLA compilation cache.

A session-scoped autouse fixture enables the repo-local persistent
compile cache (repro.core.compile_cache) for the whole suite, so a repeat
``pytest`` run — locally or in CI with the cache directory restored —
pays tracing only and skips XLA compilation of every sweep program it has
seen before. Opt-outs:

- ``REPRO_COMPILE_CACHE=0`` in the environment disables it for the run;
- ``@pytest.mark.no_persistent_cache`` disables it for one test (tests
  that drive cache enable/disable themselves, or that assert on the
  process-wide hit/miss counters, must not race the ambient cache).
"""
import os

import pytest

from repro.core import compile_cache


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "no_persistent_cache: disable the persistent XLA compilation "
        "cache around this test (for tests that manage cache state or "
        "assert on the process-wide compile-accounting counters)")


@pytest.fixture(scope="session", autouse=True)
def persistent_compile_cache():
    """Warm every tier-1 run after the first: sweep-program executables
    land in the repo-local cache dir (JAX_COMPILATION_CACHE_DIR
    overrides) and are reloaded instead of recompiled."""
    if os.environ.get(compile_cache.DISABLE_ENV) == "0":
        yield None
        return
    yield compile_cache.enable()


@pytest.fixture(autouse=True)
def _no_persistent_cache_marker(request):
    """Honor @pytest.mark.no_persistent_cache: cache off for the test,
    restored afterwards (unless the whole session opted out)."""
    if request.node.get_closest_marker("no_persistent_cache") is None:
        yield
        return
    was_enabled = compile_cache.enabled()
    compile_cache.disable()
    try:
        yield
    finally:
        if was_enabled:
            compile_cache.enable()
