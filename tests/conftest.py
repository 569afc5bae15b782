"""Test configuration: force a virtual 8-device CPU mesh.

Must run before jax is imported anywhere.  Multi-chip sharding tests use this
virtual mesh; on the chip the program runs through ``benchmark/run.py`` and
``chip_smoke.py``, which do not import this file.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Persistent compilation cache: XLA compiles dominate suite runtime (the
# codec/mapper shapes recompile identically every run); caching them keeps
# the full suite inside the CI/driver time budget after the first run.
# Set BOTH the env vars and (post-import) the config knobs: pytest plugins
# can import jax before this conftest, after which the env is ignored.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/ceph_tpu_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import jax  # noqa: E402

jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update(
    "jax_persistent_cache_min_compile_time_secs",
    float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
jax.config.update(
    "jax_persistent_cache_min_entry_size_bytes",
    int(os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"]))

import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    """``soak`` and ``race`` are slow-implied (pytest.ini): every test
    carrying either mark also gets ``slow``, so the tier-1 gate's
    ``-m 'not slow'`` always deselects them without each test having
    to remember both marks — a soak (or a full-scale race-sanitizer
    scenario) accidentally landing on the bench hot path would violate
    the BENCH_NOTES round-13 contract."""
    for item in items:
        if ("soak" in item.keywords or "race" in item.keywords) \
                and "slow" not in item.keywords:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _lockdep_reset():
    """Reset the global lockdep state between tests: ordering edges are
    process-wide, so without this a (legitimate) A->B order learned in
    one test poisons a (legitimate) B->A order in the next into a false
    cycle; stale held entries from a crashed task would do the same."""
    from ceph_tpu.utils.lockdep import DepLock, LockDep

    LockDep.instance().reset()
    DepLock._held.clear()
    yield
    LockDep.instance().reset()
    DepLock._held.clear()


@pytest.fixture(autouse=True, scope="session")
def _idle_store_pool():
    """The store's spare mappings (PR 45) are one pool and one refill
    thread a PROCESS: left on, every test that lands a shard of 256 KiB
    would share with the tests after it a thread that populates 64 MiB
    beside them, and a test that asserts on the wall of a 30 ms op would
    be timing that too.  The session's pool has a bound of nothing, so it
    never starts its thread and every shard lands inline;
    ``tests/test_store_populated.py`` gives each of its tests a pool of
    its own."""
    from ceph_tpu.cluster import store

    store._POOL = store._Pool(bound=0)
    yield
