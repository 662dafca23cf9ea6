"""Test config: force a virtual 8-device CPU mesh so distributed/sharding
tests run without TPU hardware, and never take a chip that is there.

The platform is pinned twice: in os.environ for the subprocesses the tests
start, and via jax.config for this process in case jax was imported (and
read its environment) before this file ran.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compilation cache: the tier-1 suite is compile-dominated
# on CPU (hundreds of distinct jit shapes) and the driver's wall-clock
# budget is tight on slow boxes — a warm cache cuts repeat runs 2-4x.
# Entries key on HLO + compile options + jax/XLA version, so staleness
# cannot change results. Set in os.environ BEFORE any subprocess spawns
# so the bench/deploy smoke subprocesses share the cache; set via
# jax.config for THIS process in case jax was imported before the env
# var existed.
_JAX_CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _JAX_CACHE)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update(
    "jax_persistent_cache_min_compile_time_secs",
    float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
jax.devices()  # force CPU backend init before anything else can

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: slow marks the bench-sized tests
    # (served-traffic sweep etc.) that only manual/chip sessions run
    config.addinivalue_line(
        "markers", "slow: bench-sized test; tier-1 skips via -m 'not slow'")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield


@pytest.fixture
def library_log():
    """The records the library logger takes while the test runs (it does
    not propagate to the root logger, so `caplog` sees none of them)."""
    import logging

    from paddle_tpu.observability import log

    records = []

    class Keep(logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = Keep()
    log.get_logger().addHandler(handler)
    try:
        yield records
    finally:
        log.get_logger().removeHandler(handler)


# The tier-1 suite compiles >1000 jitted programs in ONE process; every
# live XLA CPU executable holds several mmap'd code regions, and the
# kernel's vm.max_map_count ceiling (65530 default) turns the ~900th
# compile into a SEGFAULT inside LLVM (mmap fails mid-codegen) — found
# when the sharded-serving suite landed at the end of the alphabet and
# the round-14 distributed-family fixes made ~30 previously-failing
# tests actually compile their programs. Dropping jax's executable
# caches releases the mappings (measured 1292 -> 398 for 300 jits);
# the persistent on-disk compilation cache (enabled above) makes any
# re-needed program a cheap deserialize, not a recompile.
_MAP_GUARD_LIMIT = 45_000
_MAP_GUARD_EVERY = 20
_map_guard_tick = 0


@pytest.fixture(autouse=True)
def _map_count_guard():
    yield
    global _map_guard_tick
    _map_guard_tick += 1
    if _map_guard_tick % _MAP_GUARD_EVERY:
        return
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:  # non-Linux: no map ceiling to guard
        return
    if n > _MAP_GUARD_LIMIT:
        import gc

        jax.clear_caches()
        gc.collect()
