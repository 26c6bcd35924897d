#!/usr/bin/env python3
# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Entry shim for planverify (``tools/verify/``): pins the 8-device
virtual CPU mesh BEFORE jax initializes (contract programs lower
against the same topology the test suite uses — see tests/conftest.py)
then dispatches to the package CLI.

Usage: ``python tools/planverify.py [--changed] [--json] [ids...]``
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# Name the CPU before the package import: it resolves its x64 policy
# from the platform, which would otherwise start JAX's default backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from legate_sparse_tpu._platform import pin_cpu  # noqa: E402

from tools.verify import catalog  # noqa: E402

pin_cpu(catalog.MESH_DEVICES, override_env=False)

from tools.verify.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
