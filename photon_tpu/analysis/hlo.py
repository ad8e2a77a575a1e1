"""Program checks: reusable passes over lowered/compiled XLA modules.

Generalizes the two one-off hlo-guard tests into passes any caller can
run over ANY module — in particular over every AOT-precompiled
executable of a fit (``audit_coordinates``), not just two hand-picked
fixtures:

* **collective-freedom** (PERF.md r5): the random-effect solves are
  per-entity independent by construction; a cross-device collective in
  one is pure overhead on real ICI and fatal straggle on the virtual
  CPU mesh.
* **constant-embedding bound** (PERF.md r4): closed-over arrays lower as
  HLO literal constants serialized INTO the module — observed as
  HTTP-413 rejections and multi-minute hangs at the remote compile
  service. Data rides as arguments; anything over a scalar-ish epsilon
  embedded in the module is a bug.
* **solve-shape census** (PERF.md r6): the PR 3 shape budget bounds the
  fit's TOTAL distinct (rows, d) solve shapes; the census counts what a
  built fit will actually compile and compares.

The passes take compiled executables, ``jax.stages.Lowered`` objects, or
raw module text, and cover both the post-optimization HLO dialect
(``f32[64,128]{1,0} constant(...)``, ``all-reduce``) and StableHLO
(``stablehlo.constant dense<...> : tensor<64x128xf32>``,
``stablehlo.all_reduce``).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Mapping

import numpy as np

#: anything bigger than this many bytes embedded in a program is a data
#: array smuggled through a closure, not a tolerable scalar table
DEFAULT_CONST_BYTES_LIMIT = 16 * 1024

_COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-\w+|reduce-scatter"
    r"|stablehlo\.all_reduce|stablehlo\.all_gather|stablehlo\.all_to_all"
    r"|stablehlo\.collective_\w+|stablehlo\.reduce_scatter"
)

# `f32[64,128]{1,0} constant(` — post-optimization HLO
_HLO_CONST_RE = re.compile(
    r"\b(?P<dtype>pred|[fsu]\d+|bf16|c64|c128)\[(?P<dims>[0-9,]*)\]"
    r"(?:\{[^}]*\})?\s+constant\("
)
# `stablehlo.constant dense<...> : tensor<64x128xf32>` — StableHLO
_SHLO_CONST_RE = re.compile(
    r"stablehlo\.constant\s+dense<[^:]*:\s*tensor<(?P<sig>[0-9x]*x?"
    r"(?P<dtype>pred|[fsu]\d+|bf16|i\d+|ui\d+))>"
)

_DTYPE_BYTES = {
    "pred": 1, "bf16": 2, "c64": 8, "c128": 16,
}


def _dtype_bytes(name: str) -> int:
    if name in _DTYPE_BYTES:
        return _DTYPE_BYTES[name]
    m = re.fullmatch(r"[fsu]?i?u?\w*?(\d+)", name)
    return max(1, int(m.group(1)) // 8) if m else 4


@dataclasses.dataclass(frozen=True)
class ProgramFinding:
    """One violated program contract (the HLO analogue of a Finding)."""

    check: str  # "no-collectives" | "const-embedding" | "shape-budget"
    program: str  # human label, e.g. "per_user:sweep"
    message: str

    def render(self) -> str:
        return f"[{self.check}] {self.program}: {self.message}"

    def to_json(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def module_text(obj: Any) -> str:
    """Module text from a Compiled/Lowered/str."""
    if isinstance(obj, str):
        return obj
    as_text = getattr(obj, "as_text", None)
    if as_text is not None:
        return as_text()
    raise TypeError(
        f"cannot extract module text from {type(obj).__name__}; pass a "
        "Lowered, a Compiled, or str"
    )


def try_module_text(obj: Any) -> tuple[str | None, str | None]:
    """``(text, None)`` or ``(None, reason)`` — some backends' executables
    raise from ``as_text()`` (serialization not implemented, transport
    errors). One unprintable program must degrade to a
    skipped-with-warning audit entry, not kill the whole ``--programs``
    run."""
    try:
        return module_text(obj), None
    except Exception as e:
        return None, f"{type(e).__name__}: {e}"


# --- collective freedom ---------------------------------------------------


def find_collectives(text: str) -> list[str]:
    return sorted(set(_COLLECTIVE_RE.findall(text)))


def check_no_collectives(obj: Any, program: str) -> list[ProgramFinding]:
    collectives = find_collectives(module_text(obj))
    if not collectives:
        return []
    return [
        ProgramFinding(
            check="no-collectives",
            program=program,
            message=(
                f"lowered cross-device collectives {collectives} — the "
                f"per-shard-independent solve contract is broken "
                f"(PERF.md r5: overhead on ICI, fatal straggle on the "
                f"virtual mesh)"
            ),
        )
    ]


# --- constant embedding ---------------------------------------------------


def collect_jaxpr_consts(closed_jaxpr: Any, out: list[Any]) -> None:
    """Consts of this jaxpr AND of every nested ClosedJaxpr: a jitted
    callee's closure constants live on the inner pjit equation's jaxpr —
    the outer ``make_jaxpr`` consts list stays empty, so a non-recursive
    check is vacuous for exactly the functions the guard protects."""
    out.extend(closed_jaxpr.consts)
    for eqn in closed_jaxpr.jaxpr.eqns:
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
                collect_jaxpr_consts(v, out)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                        collect_jaxpr_consts(item, out)


def check_jaxpr_const_embedding(
    closed_jaxpr: Any, program: str, limit: int = DEFAULT_CONST_BYTES_LIMIT
) -> list[ProgramFinding]:
    """Trace-level pass (pre-lowering): closure constants by array size."""
    consts: list[Any] = []
    collect_jaxpr_consts(closed_jaxpr, consts)
    offenders = [
        (int(np.asarray(c).nbytes), getattr(c, "shape", None))
        for c in consts
        if hasattr(c, "nbytes") and np.asarray(c).nbytes > limit
    ]
    if not offenders:
        return []
    return [
        ProgramFinding(
            check="const-embedding",
            program=program,
            message=(
                f"traced program embeds {offenders} as constants — pass "
                f"the data as jit arguments (HTTP-413 / remote-compile "
                f"hang class, PERF.md r4)"
            ),
        )
    ]


def find_large_constants(
    text: str, limit: int = DEFAULT_CONST_BYTES_LIMIT
) -> list[tuple[str, int]]:
    """(shape signature, nbytes) of every embedded literal over ``limit``
    in HLO or StableHLO module text."""
    out: list[tuple[str, int]] = []
    for m in _HLO_CONST_RE.finditer(text):
        dims = [int(d) for d in m.group("dims").split(",") if d]
        nbytes = math.prod(dims) * _dtype_bytes(m.group("dtype"))
        if nbytes > limit:
            out.append((f"{m.group('dtype')}[{m.group('dims')}]", nbytes))
    for m in _SHLO_CONST_RE.finditer(text):
        sig = m.group("sig")
        dims = [int(d) for d in sig.split("x")[:-1] if d.isdigit()]
        nbytes = math.prod(dims) * _dtype_bytes(m.group("dtype"))
        if nbytes > limit:
            out.append((f"tensor<{sig}>", nbytes))
    return out


def check_const_embedding(
    obj: Any, program: str, limit: int = DEFAULT_CONST_BYTES_LIMIT
) -> list[ProgramFinding]:
    offenders = find_large_constants(module_text(obj), limit)
    if not offenders:
        return []
    return [
        ProgramFinding(
            check="const-embedding",
            program=program,
            message=(
                f"module embeds literal constants {offenders} (> {limit} "
                f"bytes) — data must ride as program arguments (HTTP-413 "
                f"/ remote-compile hang class, PERF.md r4)"
            ),
        )
    ]


# --- solve-shape census ---------------------------------------------------


def solve_shape_census(
    coordinates: Mapping[str, Any]
) -> set[tuple[int, int]]:
    """Distinct (active_rows, d) solve shapes a built fit will compile,
    read off the device buckets of every random-effect coordinate —
    the same quantity the PR 3 shape budget bounds."""
    shapes: set[tuple[int, int]] = set()
    for coord in coordinates.values():
        for db in getattr(coord, "device_buckets", None) or []:
            f = db.features
            if getattr(f, "ndim", 0) == 3:  # [E, n_act, d]
                shapes.add((int(f.shape[1]), int(f.shape[2])))
    return shapes


def check_shape_budget(
    coordinates: Mapping[str, Any], budget: int | None
) -> list[ProgramFinding]:
    """Census vs the PR 3 budget: the fit's TOTAL distinct solve shapes
    must not exceed it (None/0 = budget disabled, census-only)."""
    census = solve_shape_census(coordinates)
    if not budget or len(census) <= budget:
        return []
    return [
        ProgramFinding(
            check="shape-budget",
            program="<fit>",
            message=(
                f"{len(census)} distinct solve shapes exceed the shape "
                f"budget of {budget}: {sorted(census)} — the bucket DP "
                f"(game/data._optimal_row_levels) is being bypassed or "
                f"the budget is not threaded (PERF.md r6 compile bill)"
            ),
        )
    ]


# --- whole-fit audit ------------------------------------------------------


@dataclasses.dataclass
class AuditReport:
    programs_checked: int
    findings: list[ProgramFinding]
    census: set[tuple[int, int]]
    #: per-executable comm/compute rows (the census table --programs
    #: prints): program, ledger_label, flops, collective sites, bytes
    comm: list[dict[str, Any]] = dataclasses.field(default_factory=list)
    #: executables whose module text was unreadable — audited checks
    #: skipped with a warning instead of crashing the run
    skipped: list[dict[str, Any]] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings


def _coordinate_contract(coord: Any) -> Any:
    """The coordinate's declared SPMD contract, or an inferred fallback
    for foreign coordinate objects: RE-like kinds (per-entity-independent
    solves) get the zero allowance, everything else is census-only."""
    from photon_tpu.analysis import spmd

    decl = getattr(coord, "spmd_contract", None)
    if callable(decl):
        contract = decl()
        if isinstance(contract, spmd.SpmdContract):
            return contract
    if "RandomEffect" in type(coord).__name__:
        return spmd.SpmdContract(comm=spmd.COLLECTIVE_FREE)
    return spmd.SpmdContract(comm=spmd.ANY_COMM)


def _audit_one_program(
    exe: Any,
    label: str,
    ledger_label: str,
    contract: Any,
    const_bytes_limit: int,
    findings: list[ProgramFinding],
    comm_rows: list[dict[str, Any]],
    skipped: list[dict[str, Any]],
    kind: str = "",
) -> bool:
    """All text+API passes over one executable. Returns False when the
    module text was unreadable (recorded in ``skipped``)."""
    from photon_tpu.analysis import spmd

    text, err = try_module_text(exe)
    if text is None:
        skipped.append({"program": label, "reason": err})
        return False
    sites = spmd.communication_census(text)
    findings.extend(
        spmd.check_comm_allowance(sites, contract.comm_for(kind), label)
    )
    findings.extend(check_const_embedding(text, label, const_bytes_limit))
    findings.extend(
        spmd.check_sharding_contract(text, label, contract.sharding)
    )
    if contract.sharding.on_mesh and contract.sharding.partitioned_results:
        findings.extend(spmd.check_result_partitioning(exe, label))
    comm_rows.append(
        {
            "program": label,
            "ledger_label": ledger_label,
            "flops": spmd.executable_flops(exe),
            "collective_sites": [s.to_json() for s in sites],
            "comm_bytes": spmd.comm_bytes(sites),
        }
    )
    return True


def audit_coordinates(
    coordinates: Mapping[str, Any],
    *,
    const_bytes_limit: int = DEFAULT_CONST_BYTES_LIMIT,
    shape_budget: int | None = None,
    contracts: Mapping[str, Any] | None = None,
) -> AuditReport:
    """Run every program pass over every AOT-precompiled executable of
    the given coordinates (run ``descent.precompile_coordinates`` first —
    the executables this audits are exactly the ones a fit dispatches).

    Each coordinate is audited against its own declared
    :class:`photon_tpu.analysis.spmd.SpmdContract`
    (``Coordinate.spmd_contract()``): the communication census must fit
    the coordinate's allowance (RE: collective-free, the PAPER §L4/L5
    per-entity-independence invariant; FE: bounded d-vector all-reduces),
    replicated parameters must stay under the contract's byte limit (the
    entity-table-compiled-replicated failure), meshed programs must keep
    partitioned results, and live table placement must match. Pass
    ``contracts`` (cid → SpmdContract) to override declarations. The
    constant-embedding bound applies to every program; an executable
    whose module text is unreadable is reported in ``report.skipped``
    instead of crashing the run.
    """
    from photon_tpu.analysis import spmd

    findings: list[ProgramFinding] = []
    comm_rows: list[dict[str, Any]] = []
    skipped: list[dict[str, Any]] = []
    programs = 0
    for cid, coord in coordinates.items():
        contract = (
            contracts[cid]
            if contracts is not None and cid in contracts
            else _coordinate_contract(coord)
        )
        executables = coord.aot_executables() or {}
        for key in sorted(executables, key=repr):
            label = f"{cid}:{':'.join(str(k) for k in key)}"
            kind = str(key[0]) if isinstance(key, tuple) and key else label
            ledger_label = f"{cid}:{kind}" if isinstance(key, tuple) else label
            programs += 1
            _audit_one_program(
                executables[key], label, ledger_label, contract,
                const_bytes_limit, findings, comm_rows, skipped, kind=kind,
            )
    findings.extend(spmd.check_table_placement(coordinates))
    findings.extend(check_shape_budget(coordinates, shape_budget))
    return AuditReport(
        programs_checked=programs,
        findings=findings,
        census=solve_shape_census(coordinates),
        comm=comm_rows,
        skipped=skipped,
    )


def audit_scorer(
    scorer: Any,
    *,
    const_bytes_limit: int = DEFAULT_CONST_BYTES_LIMIT,
    contract: Any = None,
) -> AuditReport:
    """The streaming scorer's analogue of :func:`audit_coordinates`:
    every per-batch-shape executable ``GameScorer.precompile`` built
    (``scorer.aot_executables()``) gets the same comm census, sharding
    contract, and constant-embedding passes. The default contract is the
    single-host one — collective-free (a fused scoring batch never talks
    across devices) with no mesh claims; a future mesh-sharded scorer
    passes its own."""
    from photon_tpu.analysis import spmd

    if contract is None:
        contract = spmd.SpmdContract(
            comm=dataclasses.replace(
                spmd.COLLECTIVE_FREE,
                reason="fused scoring batch: one device, zero collectives",
            )
        )
    findings: list[ProgramFinding] = []
    comm_rows: list[dict[str, Any]] = []
    skipped: list[dict[str, Any]] = []
    programs = 0
    executables = scorer.aot_executables() or {}
    for key in sorted(executables, key=repr):
        label = f"score:{key}"
        programs += 1
        _audit_one_program(
            executables[key], label, label, contract,
            const_bytes_limit, findings, comm_rows, skipped, kind="score",
        )
    return AuditReport(
        programs_checked=programs,
        findings=findings,
        census=set(),
        comm=comm_rows,
        skipped=skipped,
    )
