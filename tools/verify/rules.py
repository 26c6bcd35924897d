# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""planverify rules: the four lowered-IR contracts.

Mirrors the sparselint rule shape (stable kebab-case id, severity,
one-line description, registry via ``@register``, mandatory
falsifiability drill) but checks *programs* instead of source files:
``check(program, built, contract)`` yields ``Finding``s rendered
``path:line: severity: [rule-id] message`` with ``path`` = the
program's contract file (schedule/bytes drift) or its primary source
module (IR-intrinsic violations), and ``line`` 0 — a lowered program
has no meaningful line numbers, and the line-free position keeps
baseline keys stable (tools/common/findings.py).

Every rule must be falsifiable: ``falsifiability()`` lowers a small
known-bad synthetic program (an extra psum, a host callback inside a
while body, a silent bf16->f32 widen) and must produce at least one
finding — drilled by tests/test_verify.py, same discipline as
tools/lint.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from ..common.findings import Finding
from . import hlo
from .catalog import Built, Program
from .contracts import contract_name

# Partitioning bookkeeping custom_calls jax emits for sharded
# programs: annotations, not host transfers.
ALLOWED_CUSTOM_CALLS = frozenset({
    "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
})

_UPDATE_HINT = ("run `python tools/planverify.py --update-contracts "
                "--reason '...'` if the new program is intended")


def finding_path(program: Program, contract_side: bool) -> str:
    if contract_side:
        return "tools/verify/contracts/" + contract_name(program.pid)
    return program.sources[0]


def schedule_of(built: Built) -> List[dict]:
    """Contract-shaped schedule entries (signature + per-op bytes in
    ledger convention) from the lowered text."""
    from legate_sparse_tpu.obs import comm as _comm

    out = []
    for op in hlo.parse_collectives(built.hlo):
        sig = op.signature()
        n_groups, size = op.groups if op.groups else (0, 0)
        sig["bytes"] = _comm.lowered_op_bytes(
            op.kind, op.operand_bytes,
            group_sizes=(size,) * n_groups,
            moved_pairs=op.moved_pairs)
        out.append(sig)
    return out


def lowered_volumes(built: Built) -> Dict[str, int]:
    """Per-ledger-kind byte totals of the explicitly lowered
    collectives."""
    vols: Dict[str, int] = {}
    for entry in schedule_of(built):
        kind = hlo.ledger_kind(entry["kind"], entry.get("reduce"))
        vols[kind] = vols.get(kind, 0) + entry["bytes"]
    return {k: v for k, v in vols.items() if v > 0}


def transfer_violations(built: Built) -> List[Tuple[str, str]]:
    """(kind, detail) pairs for every host-transfer site in the
    program, from both the StableHLO text and the jaxpr."""
    out: List[Tuple[str, str]] = []
    for feed in hlo.parse_feeds(built.hlo):
        out.append(("feed", f"stablehlo.{feed} op in lowered IR"))
    for target in hlo.parse_custom_calls(built.hlo):
        if target not in ALLOWED_CUSTOM_CALLS:
            out.append(("custom_call",
                        f"non-partitioning custom_call @{target}"))
    if built.jaxpr is not None:
        for prim, in_loop in hlo.host_callbacks(built.jaxpr):
            where = (" inside a while/scan loop body (per-iteration "
                     "host sync)" if in_loop else "")
            out.append(("callback",
                        f"host callback primitive '{prim}'{where}"))
    return out


def contract_payload(program: Program, built: Built,
                     reason: str) -> dict:
    """The committed-contract JSON for one built program — written by
    ``--update-contracts``, compared by the rules.  Deterministic:
    same IR in, byte-identical file out."""
    sched = schedule_of(built)
    return {
        "version": 1,
        "program": program.pid,
        "reason": reason,
        "schedule": sched,
        "lowered_volumes": lowered_volumes(built),
        "predicted_volumes": built.predicted,
        "deferred_volumes": built.deferred,
        "custom_calls": sorted(set(hlo.parse_custom_calls(built.hlo))),
        "transfer_free": not transfer_violations(built),
        "widening_allowed": sorted(built.widening_allowed),
        "notes": built.notes,
    }


class VerifyRule:
    """Base class; subclasses register with ``@register``."""

    id: str = ""
    severity: str = "error"
    description: str = ""

    def check(self, program: Program, built: Built,
              contract: Optional[dict]) -> Iterable[Finding]:
        raise NotImplementedError

    def falsifiability(self) -> List[Finding]:
        """Findings on a seeded known-bad lowered program.  Must be
        non-empty — drilled by tests/test_verify.py."""
        raise NotImplementedError

    def _finding(self, program: Program, message: str,
                 contract_side: bool = True) -> Finding:
        return Finding(rule=self.id,
                       path=finding_path(program, contract_side),
                       line=0, message=message,
                       severity=self.severity)


_RULES: Dict[str, VerifyRule] = {}


def register(cls):
    inst = cls()
    if not inst.id:
        raise ValueError(f"rule class {cls.__name__} has no id")
    if inst.id in _RULES:
        raise ValueError(f"duplicate rule id {inst.id!r}")
    _RULES[inst.id] = inst
    return cls


def all_rules() -> Dict[str, VerifyRule]:
    return dict(_RULES)


def get_rule(rule_id: str) -> VerifyRule:
    return _RULES[rule_id]


def _sig_only(entry: dict) -> tuple:
    """Schedule identity the schedule rule compares: kind + operand
    size + topology (bytes equality is the comm-bytes rule's job,
    split so a finding names the invariant that actually broke)."""
    return (entry["kind"], entry["operand_bytes"],
            entry.get("moved_pairs"),
            tuple(entry["groups"]) if entry.get("groups") else None)


@register
class CollectiveScheduleRule(VerifyRule):
    id = "collective-schedule"
    description = ("lowered collective kind/count/topology/ordering "
                   "must match the committed contract")

    def check(self, program, built, contract):
        if contract is None:
            yield self._finding(
                program,
                f"{program.pid}: no committed contract — "
                f"{_UPDATE_HINT}")
            return
        got = [_sig_only(e) for e in schedule_of(built)]
        want = [_sig_only(e) for e in contract.get("schedule", [])]
        if got == want:
            return
        detail = (f"lowered {len(got)} collective(s), contract has "
                  f"{len(want)}")
        for i, (g, w) in enumerate(zip(got, want)):
            if g != w:
                detail = (f"op {i} diverges: lowered "
                          f"{g[0]}(operand={g[1]}B) vs contract "
                          f"{w[0]}(operand={w[1]}B)")
                break
        else:
            if len(got) > len(want):
                detail += f"; first extra lowered op: {got[len(want)][0]}"
            elif len(want) > len(got):
                detail += f"; first missing op: {want[len(got)][0]}"
        yield self._finding(
            program,
            f"{program.pid}: collective schedule drifted from "
            f"contract ({detail}) — {_UPDATE_HINT}")


@register
class CommBytesRule(VerifyRule):
    id = "comm-bytes"
    description = ("per-collective IR operand bytes must equal the "
                   "obs/comm model prediction and the contract, "
                   "exactly")

    def check(self, program, built, contract):
        vols = lowered_volumes(built)
        if built.predicted is not None:
            kinds = sorted(set(vols) | set(built.predicted))
            for kind in kinds:
                got = vols.get(kind, 0)
                want = built.predicted.get(kind, 0)
                if got != want:
                    yield self._finding(
                        program,
                        f"{program.pid}: lowered {kind} moves {got} "
                        f"bytes but obs/comm prices {want} — model "
                        f"and program disagree", contract_side=False)
        if contract is None:
            return
        if vols != contract.get("lowered_volumes", {}):
            yield self._finding(
                program,
                f"{program.pid}: lowered byte volumes {vols} != "
                f"contracted {contract.get('lowered_volumes')} — "
                f"{_UPDATE_HINT}")
        if built.predicted != contract.get("predicted_volumes"):
            yield self._finding(
                program,
                f"{program.pid}: obs/comm prediction "
                f"{built.predicted} != contracted "
                f"{contract.get('predicted_volumes')} (model "
                f"drifted?) — {_UPDATE_HINT}")
        if built.deferred != contract.get("deferred_volumes", {}):
            yield self._finding(
                program,
                f"{program.pid}: deferred (partitioner-inserted) "
                f"volumes {built.deferred} != contracted "
                f"{contract.get('deferred_volumes')} — {_UPDATE_HINT}")


@register
class TransferFreedomRule(VerifyRule):
    id = "transfer-freedom"
    description = ("no host callbacks/infeed/outfeed or "
                   "non-partitioning custom_calls in contracted "
                   "programs (solver cycle bodies especially)")

    def check(self, program, built, contract):
        for _kind, detail in transfer_violations(built):
            yield self._finding(
                program, f"{program.pid}: {detail}",
                contract_side=False)


@register
class DtypeDisciplineRule(VerifyRule):
    id = "dtype-discipline"
    description = ("no float-widening converts (bf16->f32, f32->f64) "
                   "beyond the program's declared accumulators")

    def check(self, program, built, contract):
        allowed = set(built.widening_allowed)
        if contract:
            allowed.update(contract.get("widening_allowed", []))
        seen = set()
        if built.jaxpr is not None:
            convs = hlo.jaxpr_widening_converts(built.jaxpr)
        else:
            convs = [(c, False)
                     for c in hlo.hlo_widening_converts(built.hlo)]
        for conv, in_loop in convs:
            if conv in allowed or conv in seen:
                continue
            seen.add(conv)
            where = " inside a loop body" if in_loop else ""
            yield self._finding(
                program,
                f"{program.pid}: undeclared float widening {conv}"
                f"{where} — declare it in widening_allowed if it is "
                f"an intended accumulator", contract_side=False)


# ------------------------------------------------------------------ #
# falsifiability fixtures: small known-bad programs, lowered the same
# way the catalog lowers real ones
# ------------------------------------------------------------------ #

_PROBE = Program(pid="zz-verify-falsifiability-probe", kind="dist",
                 sources=("tools/verify/rules.py",))


def _probe_mesh():
    from .catalog import _row_mesh

    return _row_mesh()


def _psum_built(elems_per_shard: int = 1) -> Built:
    """A one-psum shard_map program over the row mesh."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from legate_sparse_tpu.parallel.mesh import ROW_AXIS

    mesh = _probe_mesh()
    R = mesh.shape[ROW_AXIS]

    def f(a):
        return jax.lax.psum(a, ROW_AXIS)

    sm = shard_map(f, mesh=mesh, in_specs=P(ROW_AXIS),
                   out_specs=P(None), check_vma=False)
    x = jax.ShapeDtypeStruct(
        (R * elems_per_shard,), np.float32,
        sharding=NamedSharding(mesh, P(ROW_AXIS)))
    return Built(hlo=jax.jit(sm).lower(x).as_text(),
                 jaxpr=jax.make_jaxpr(sm)(x), predicted=None)


def _schedule_falsifiability() -> List[Finding]:
    # Inject an extra psum relative to the contract: the contract says
    # "no collectives", the program lowers one.
    built = _psum_built()
    contract = {"version": 1, "schedule": [], "lowered_volumes": {},
                "predicted_volumes": None, "deferred_volumes": {}}
    return list(get_rule("collective-schedule").check(
        _PROBE, built, contract))


def _bytes_falsifiability() -> List[Finding]:
    # Model says one psum of 1 element; the program psums 4 per shard.
    from legate_sparse_tpu.obs import comm as _comm

    built = _psum_built(elems_per_shard=4)
    built.predicted = {"psum": _comm.psum_bytes(
        1, 4, _probe_mesh().shape["rows"])}
    return list(get_rule("comm-bytes").check(_PROBE, built, None))


def _transfer_falsifiability() -> List[Finding]:
    # A debug print inside a while_loop body: exactly the
    # per-iteration host round-trip the rule exists to forbid.
    import jax
    import numpy as np

    def body(c):
        i, x = c
        jax.debug.print("iter {}", i)
        return i + 1, x + 1.0

    def prog(x):
        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (np.int32(0), x))

    spec = jax.ShapeDtypeStruct((4,), np.float32)
    built = Built(hlo=jax.jit(prog).lower(spec).as_text(),
                  jaxpr=jax.make_jaxpr(prog)(spec), predicted=None)
    return list(get_rule("transfer-freedom").check(_PROBE, built,
                                                   None))


def _dtype_falsifiability() -> List[Finding]:
    # Silent bf16 -> f32 widen with no declared accumulator.
    import jax
    import jax.numpy as jnp

    def prog(a):
        return jnp.sum(a.astype(jnp.float32))

    spec = jax.ShapeDtypeStruct((8,), jnp.bfloat16)
    built = Built(hlo=jax.jit(prog).lower(spec).as_text(),
                  jaxpr=jax.make_jaxpr(prog)(spec), predicted=None)
    return list(get_rule("dtype-discipline").check(_PROBE, built,
                                                   None))


CollectiveScheduleRule.falsifiability = (
    lambda self: _schedule_falsifiability())
CommBytesRule.falsifiability = lambda self: _bytes_falsifiability()
TransferFreedomRule.falsifiability = (
    lambda self: _transfer_falsifiability())
DtypeDisciplineRule.falsifiability = (
    lambda self: _dtype_falsifiability())
