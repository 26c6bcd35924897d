# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""monotonic-clock: ``time.time()`` is banned in the package.

PR 8 re-based ``CircuitBreaker`` and ``Deadline`` on
``time.monotonic_ns()`` after establishing that wall-clock timing in
latency/deadline/breaker paths breaks under NTP steps and clock
slew: a deadline computed from ``time.time()`` can expire requests
spuriously (or never) when the clock jumps.  This rule keeps the ban
from regressing: any ``time.time()`` call inside
``legate_sparse_tpu/`` is a finding.

A legitimate use (comparing against wall-clock *file* timestamps)
carries an inline justified suppression, which is exactly the
documentation the exception needs.
"""

from __future__ import annotations

import ast
from typing import Iterable, Sequence

from ..core import Context, Finding, PKG_PREFIX, Rule, register


@register
class MonotonicClockRule(Rule):
    id = "monotonic-clock"
    description = ("time.time() banned in the package (latency/"
                   "deadline/breaker paths need monotonic clocks)")
    scope_prefixes = (PKG_PREFIX,)
    bad_fixture = "tools/lint/fixtures/monotonic_clock_bad.py"

    def check(self, ctx: Context, files: Sequence[str]
              ) -> Iterable[Finding]:
        for rel in files:
            for node in ast.walk(ctx.tree(rel)):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "time" and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id == "time":
                    yield Finding(
                        rule="monotonic-clock", path=rel,
                        line=node.lineno,
                        message=("time.time() is wall-clock — use "
                                 "time.monotonic()/monotonic_ns() "
                                 "(or suppress with a justification "
                                 "for true epoch-timestamp uses)"))
