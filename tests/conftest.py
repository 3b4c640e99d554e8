"""Test configuration.

Tests run on a virtual 8-device CPU mesh — the analog of the
reference's DistributedQueryRunner trick of launching N servers in one
JVM (TESTING/DistributedQueryRunner.java:98): we get N XLA devices in
one process to exercise real sharding/collectives without TPU hardware.
"""

import os

# Must be set before jax initializes.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Validate the plan after every optimizer rewrite under tests (prod
# default is FINAL-only).  Env-seeded so fleet worker subprocesses
# inherit the setting (the session property default reads this env
# var at import time).
os.environ.setdefault("TRINO_TPU_PLAN_VALIDATION", "FULL")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


# XLA:CPU in-process compile accumulation: one process compiling many
# large query programs segfaults inside LLVM around the ~45th heavy
# compile (observed deterministically on the TPC-DS suite; the crash
# is cumulative, not query-specific — any 44 heavy tests then boom).
# Dropping jax's executable caches every N tests keeps the process
# healthy; the persistent on-disk cache makes re-JITs cheap.
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos soaks excluded from the tier-1 run",
    )
    config.addinivalue_line(
        "markers",
        "tpcds_full: TPC-DS long tail — the smoke subset stays in "
        "tier-1, the full sweep runs in its own (non-blocking) CI job "
        "via -m tpcds_full",
    )


def pytest_collection_modifyitems(config, items):
    # tier-1 is pinned to `-m 'not slow'`, so tpcds_full must imply
    # slow for the fast lane to actually exclude the long tail
    for item in items:
        if item.get_closest_marker("tpcds_full") is not None:
            item.add_marker(pytest.mark.slow)


_test_count = 0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    global _test_count
    yield
    _test_count += 1
    if _test_count % 15 == 0:
        jax.clear_caches()
