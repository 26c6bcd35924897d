# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Device-platform helpers: the CPU pin for the test lane and the
virtual-mesh dry run, and the placement of JAX's compile cache.

Everything else uses JAX's own platform choice: on the chip machine the
TPU, here the CPU that ``JAX_PLATFORMS=cpu`` names.  Nothing probes for
an accelerator or falls back from one: a program that needs the chip and
does not find it fails.

The pin belongs before the first backend init, where ``XLA_FLAGS``
still applies; besides the ``JAX_PLATFORMS`` env var it updates
``jax.config``, so a process that imported jax first is pinned too, and
a pin after a backend init drops the backends and sets the CPU device
count through ``jax.config`` instead.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# The checkout root: the default home of the persistent compile cache.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Place JAX's persistent compile cache before the first compile,
    and return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left alone (JAX reads it
    itself); otherwise the cache goes to ``<checkout>/.jax_cache`` —
    a fixed path, since the path is part of the cache's key.  Called by
    ``chip_smoke.py``, ``bench.py`` and the examples."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# State captured by the first pin_cpu() call, for restore_platform(),
# and the environment variables it covers.
_PINNED_ENV = ("JAX_PLATFORMS", "XLA_FLAGS", "PJRT_NPROC")
_saved: dict | None = None


def _obs_event(name: str, **attrs) -> None:
    """Structured trace event + always-on counter for a pinning
    outcome (a late pin is otherwise only a line on stderr)."""
    from .obs import counters, trace

    counters.inc(name)
    trace.event(name, **attrs)


def pin_cpu(n_devices: int = 0, *, override_env: bool = True) -> None:
    """Pin jax to the host (cpu) platform with >= n_devices devices.

    Safe to call whether or not jax is already imported.  Called after
    a backend init, it clears the backends (arrays on them become
    invalid) so the next init makes the pinned CPU backend.
    ``n_devices=0`` pins the platform without touching the virtual
    device count.
    """
    global _saved
    if _saved is None:
        _saved = {key: os.environ.get(key) for key in _PINNED_ENV}

    if override_env:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    count = 0   # the virtual CPU device count to pin, if any
    if n_devices > 0:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
        if m is None:
            flags = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
            os.environ["XLA_FLAGS"] = flags
        elif int(m.group(1)) < n_devices:
            os.environ["XLA_FLAGS"] = (
                flags[: m.start()] + f"{_COUNT_FLAG}={n_devices}"
                + flags[m.end():]
            )
        count = max(n_devices, int(m.group(1)) if m else 0)
        # The CPU client runs each device's share of a program on one
        # thread pool, sized to the cores by default, and a collective
        # blocks its pool thread until every device arrives.  With as
        # many virtual devices as cores, two collective programs in
        # flight can hold every thread and wait on each other (XLA
        # aborts after 40 s); headroom makes that impossible in practice.
        os.environ.setdefault(
            "PJRT_NPROC", str(max(os.cpu_count() or 1, 8 * count)))

    import jax
    from jax._src import xla_bridge

    if "jax_platforms_prior" not in _saved:
        _saved["jax_platforms_prior"] = jax.config.jax_platforms
    if xla_bridge.backends_are_initialized():
        # Too late for XLA_FLAGS (read once, at the first backend
        # init): drop the backends and hand the count to the CPU
        # backend the next init creates through jax.config.
        _obs_event("platform.pin_cpu_late", n_devices=n_devices)
        from jax.extend.backend import clear_backends

        clear_backends()
        if count:
            _saved.setdefault("num_cpu_devices_prior",
                              jax.config.jax_num_cpu_devices)
            jax.config.update("jax_num_cpu_devices", count)
    jax.config.update("jax_platforms", "cpu")
    _re_resolve_dtype_policy()


def _re_resolve_dtype_policy() -> None:
    """The x64 default is platform-dependent (settings ``auto`` mode),
    and the package is usually imported *before* pin_cpu runs (importing
    this module imports the package) — so re-resolve after repinning."""
    from .settings import settings, _resolve_x64

    import jax

    settings.x64 = _resolve_x64()
    jax.config.update("jax_enable_x64", settings.x64)


def restore_platform() -> None:
    """Undo pin_cpu: put back the env vars and platform selection so a
    later accelerator use in the same process is not silently degraded
    (clears the now-stale cpu backend caches)."""
    global _saved
    if _saved is None:
        return
    for key in _PINNED_ENV:
        val = _saved.get(key)
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = val

    import jax

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        from jax.extend.backend import clear_backends

        clear_backends()
    if "jax_platforms_prior" in _saved:
        jax.config.update("jax_platforms", _saved["jax_platforms_prior"])
    if "num_cpu_devices_prior" in _saved:
        jax.config.update("jax_num_cpu_devices",
                          _saved["num_cpu_devices_prior"])
    _saved = None
    _re_resolve_dtype_policy()
