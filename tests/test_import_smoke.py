# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Import smoke test in a pristine subprocess.

The r5 seed shipped a top-level ``from jax import shard_map`` that
fails on the installed jax and — because ``tests/conftest.py`` imports
the package — zeroed out collection of the ENTIRE suite.  This test
pins the contract that a bare ``import legate_sparse_tpu`` under
``JAX_PLATFORMS=cpu`` always works, in a subprocess so no previously
imported module can mask a broken import chain, and enumerates every
package module so a bad import in a leaf (e.g. one ``parallel``
module) can never again hide behind lazy imports."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # A pristine import must not depend on the test session's settings.
    env.pop("LEGATE_SPARSE_TPU_OBS", None)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, cwd=_REPO, env=env,
    )


def test_package_imports_under_cpu_pin():
    r = _run("import legate_sparse_tpu; print('ok')")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ok" in r.stdout


def test_all_package_modules_import():
    code = (
        "import importlib, pkgutil\n"
        "import legate_sparse_tpu as pkg\n"
        "bad = []\n"
        "for m in pkgutil.walk_packages(pkg.__path__,\n"
        "                               prefix='legate_sparse_tpu.'):\n"
        "    try:\n"
        "        importlib.import_module(m.name)\n"
        "    except Exception as e:\n"
        "        bad.append(f'{m.name}: {e!r}')\n"
        "assert not bad, bad\n"
        "print('all-modules-ok')\n"
    )
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "all-modules-ok" in r.stdout


@pytest.mark.slow
def test_bench_importable():
    # bench.py is the driver contract surface; a syntax/import error
    # there loses a whole evidence round.
    r = _run("import bench; print('bench-ok')")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "bench-ok" in r.stdout


def _run_unpinned(code: str) -> subprocess.CompletedProcess:
    """``code`` in a subprocess that names no platform, as a user's
    shell does: no ``JAX_PLATFORMS``, no virtual device count."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, cwd=_REPO, env=env,
    )


def test_planverify_pins_its_mesh_without_platform_env():
    # tools/planverify.py imports the package, then pins the 8-device
    # CPU mesh its contract programs lower against.
    r = _run_unpinned(
        "import runpy\n"
        "runpy.run_path('tools/planverify.py', run_name='planverify')\n"
        "import jax\n"
        "print(jax.default_backend(), len(jax.devices()))\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-2:] == ["cpu", "8"], r.stdout


def test_pin_cpu_after_backend_init_gets_its_device_count():
    r = _run_unpinned(
        "import jax\n"
        "jax.devices()\n"
        "from legate_sparse_tpu._platform import pin_cpu, restore_platform\n"
        "pin_cpu(4)\n"
        "print(jax.default_backend(), len(jax.devices()),"
        " jax.config.jax_enable_x64)\n"
        "restore_platform()\n"
        "print(jax.config.jax_num_cpu_devices)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["cpu", "4", "True", "-1"], r.stdout
