# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Test configuration: run the suite on a virtual 8-device CPU mesh.

The TPU analog of the reference's ``legate.tester`` resource shapes
(reference ``test.py:24-32``): the same pytest files exercise 1-device
and 8-device behavior, with multi-device tests using the host-platform
device-count trick instead of a pod (SURVEY §4).
"""

import os
import sys

# Must run before the jax backend initializes (see _platform.pin_cpu).
# LEGATE_SPARSE_TPU_TEST_DEVICES re-runs the suite at a different
# resource shape (the legate.tester analog): 1 = single device, 8 =
# default mesh.  LEGATE_SPARSE_TPU_TEST_PLATFORM=tpu skips the pin so
# @pytest.mark.tpu smoke tests can run on a real chip.
TEST_DEVICES = int(os.environ.get("LEGATE_SPARSE_TPU_TEST_DEVICES", "8"))

# Persistent XLA compile cache: jit-compile time dominates suite wall
# time, and the compiled kernels are identical across runs.  A set
# JAX_COMPILATION_CACHE_DIR is kept; otherwise the checkout's
# .jax_cache is used (``_platform.use_compile_cache``'s default).  The
# env var (not jax.config) carries it, so SUBPROCESS lanes (bench
# smoke, dist-int64, obs v4, resilience scripts all spawn
# `sys.executable` with `dict(os.environ)`) share it: each child is a
# fresh jax process that would otherwise recompile its big
# shard_map/solver executables on every suite run.  Same >= 1 s
# persistence floor as below.  LEGATE_SPARSE_TPU_TEST_CACHE=0
# disables.
_USE_CACHE = os.environ.get("LEGATE_SPARSE_TPU_TEST_CACHE", "1") != "0"
_TEST_PLATFORM = os.environ.get("LEGATE_SPARSE_TPU_TEST_PLATFORM", "cpu")
if _USE_CACHE and _TEST_PLATFORM == "cpu":
    # CPU lane only: the real-chip lane keeps ERROR-level XLA/TPU
    # runtime diagnostics visible.  Must precede the first jaxlib load
    # so the AOT-loader's machine-feature log spam is suppressed.
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
if _USE_CACHE:
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"),
    )
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1.0")

# jit-compile time, not execution, dominates tier-1 wall time (the
# matrices are tiny), and the suite's ~1100 tests compile thousands of
# executables.  Backend optimization level 0 skips the expensive LLVM
# mid-end for a measured ~15% whole-suite win with identical test
# verdicts (tolerances are unaffected: XLA stays semantics-preserving,
# only fusion/scheduling effort drops).  CPU lane only — real-chip
# runs must measure what production compiles.
# LEGATE_SPARSE_TPU_TEST_FAST_COMPILE=0 restores default optimization.
if (_TEST_PLATFORM == "cpu"
        and os.environ.get("LEGATE_SPARSE_TPU_TEST_FAST_COMPILE",
                           "1") != "0"
        and "xla_backend_optimization_level"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_backend_optimization_level=0").strip()

if _TEST_PLATFORM == "cpu":
    # Named before the package import: the package resolves its dtype
    # policy from the platform at import, and an unnamed platform
    # would initialize JAX's default backend before the pin.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from legate_sparse_tpu._platform import pin_cpu

    pin_cpu(TEST_DEVICES, override_env=False)

import jax  # noqa: E402

if _USE_CACHE:
    # Only compiles >= 1 s are persisted.  XLA:CPU executable
    # (de)serialization segfaulted the suite three times (2026-07-31,
    # stacks in git history: put/get_executable_and_time under
    # compress_coo / spgemm_csr_csr_csr_impl) and the crash is
    # suite-context-dependent — not reproducible in isolation, so not
    # reportable upstream with a repro.  The sub-second executables it
    # struck are cheap to recompile; the multi-device shard_map and
    # solver compiles that dominate suite wall time (10-60 s each)
    # stay cached, which preserves nearly all of the warm-run win.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The full suite compiles thousands of XLA:CPU executables; each holds
# several JIT code mmaps, and one pytest process crosses the kernel's
# default vm.max_map_count (65530) at ~450 tests — the next mmap
# failure SEGFAULTS inside backend_compile_and_load (observed at
# 59k maps, 2026-07-31).  Two defenses: an opt-in raise of the limit
# (it is a HOST-GLOBAL sysctl that outlives the suite, so it never
# fires silently: set LEGATE_SPARSE_TPU_TEST_RAISE_MAP_COUNT=1 to
# allow it), and — always on — an adaptive cache flush that drops
# executables before the ceiling.  clear_caches() recompiles later
# reuses — the persistent compile cache absorbs the big ones.
if os.environ.get("LEGATE_SPARSE_TPU_TEST_RAISE_MAP_COUNT") == "1":
    try:
        with open("/proc/sys/vm/max_map_count", "r+") as _f:
            if int(_f.read()) < 262144:
                _f.seek(0)
                _f.write("262144")
                sys.stderr.write(
                    "conftest: raised host-global vm.max_map_count to "
                    "262144 (LEGATE_SPARSE_TPU_TEST_RAISE_MAP_COUNT=1)\n"
                )
    except OSError:
        pass

# Each clear_caches() costs ~12 s of teardown plus the recompiles of
# every executable still in use downstream; at 45000 the full suite
# flushes twice.  52000 keeps >13k maps of slack below the 65530
# ceiling (a test adds at most a few hundred maps, and the sampled
# check overshoots by at most ~5 tests' worth) while typically saving
# one flush per run.
_MAPS_SOFT_LIMIT = 52000
# Reading /proc/self/maps costs ~30 ms once the process holds 45k
# maps; over a ~1100-test run the every-test read alone burns ~15 s
# of the tier-1 budget.  Sampling every 5th teardown keeps the guard
# safe while shedding 80% of the proc reads.
_MAPS_CHECK_EVERY = 5
_maps_check_tick = 0


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return f.read().count(b"\n")
    except OSError:
        return 0


@pytest.fixture(autouse=True)
def _vma_guard():
    yield
    global _maps_check_tick
    _maps_check_tick += 1
    if _maps_check_tick % _MAPS_CHECK_EVERY:
        return
    if _map_count() > _MAPS_SOFT_LIMIT:
        import jax as _jax

        _jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
