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


# --- device scopes ---------------------------------------------------------
#
# The join between what a profiler trace names (an executable's instruction
# names: ``fusion.60``) and what the program names (the ``photon.*`` scopes
# of ``photon_tpu/obs/scopes.py``, carried in ``metadata={op_name=...}``).
#
# THE TRAP: jax leaves metadata out of the persistent compilation cache's
# key (``jax_compilation_cache_include_metadata_in_key`` is false, and stays
# false: turning it on would make every moved source line a cold start). So
# an executable SERVED from a cache that an older tree wrote carries that
# tree's op_names, scopes or none. New scopes therefore cost no cold start,
# and a join has to be made on an executable that THIS tree compiled: a
# fresh cache directory, or ``jax_enable_compilation_cache`` off AND
# ``jax.clear_caches()`` first (``fn.lower(*args).compile()`` is otherwise
# handed the executable the process already holds, the served one).
# Instruction names do not depend on metadata, so the text of a fresh
# compile joins to a trace of the served executable (PERF.md, PR 29: the
# chip's machine came with PR 28's cache, and read 100 % under no scope).

_INSTR_RE = re.compile(r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_COMPUTATION_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_SCOPE_PREFIX = "photon."


@dataclasses.dataclass(frozen=True)
class HloInstruction:
    """One instruction line of post-optimization HLO text."""

    name: str
    shape: str  # the result's type as written: ``f32[8,128]{1,0}``, or a tuple
    opcode: str
    operands: str  # the text between the opcode's parentheses
    attributes: str  # what follows them: calls=, body=, metadata=, ...
    op_name: str | None  # metadata op_name: jax's name stack, scopes in it
    computation: str
    is_root: bool
    calls: str | None  # the fused computation of a fusion

    @property
    def operand_names(self) -> list[str]:
        return _OPERAND_RE.findall(self.operands)


def _matching_paren(text: str, start: int) -> int:
    """Index of the parenthesis closing the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


def parse_instructions(obj: Any) -> dict[str, HloInstruction]:
    """Every instruction of a compiled module's text by name, fused
    computations' included (names are unique within a module)."""
    out: dict[str, HloInstruction] = {}
    computation = ""
    for line in module_text(obj).splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            c = _COMPUTATION_RE.match(line)
            if c is not None:
                computation = c.group("name")
            continue
        rest = m.group("rest")
        # the result shape: a tuple in parentheses, or one token
        i = _matching_paren(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
        tail = rest[i:].lstrip() if i > 0 else ""
        open_at = tail.find("(")
        if open_at < 0:
            continue
        close_at = _matching_paren(tail, open_at)
        attrs = tail[close_at + 1 :]
        op = _OP_NAME_RE.search(attrs)
        calls = _CALLS_RE.search(attrs)
        out[m.group("name")] = HloInstruction(
            name=m.group("name"),
            shape=rest[:i].strip(),
            opcode=tail[:open_at].strip(),
            operands=tail[open_at + 1 : close_at],
            attributes=attrs,
            op_name=op.group(1) if op else None,
            computation=computation,
            is_root=m.group("root") is not None,
            calls=calls.group(1) if calls else None,
        )
    return out


def scope_path(op_name: str | None) -> tuple[str, ...]:
    """The ``photon.*`` components of a name stack, outermost first:
    ``jit(f)/while/body/photon.matvec/photon.gather/gather`` ->
    ``("photon.matvec", "photon.gather")``."""
    if not op_name:
        return ()
    return tuple(p for p in op_name.split("/") if p.startswith(_SCOPE_PREFIX))


#: these move no data: no scope unless they carry one
_NO_SCOPE = frozenset({"parameter", "tuple", "get-tuple-element", "constant"})
#: and control flow takes none from its operands (whole loop states)
_NO_OPERAND_SCOPE = _NO_SCOPE | {"while", "conditional", "call"}
_CALLEE_RE = re.compile(r"\b(?:body|condition|to_apply|calls)=%?([\w.\-]+)")


def instruction_scope_paths(obj: Any) -> dict[str, tuple[str, ...]]:
    """Instruction name -> its scopes, outermost first, for every
    instruction of a compiled executable that sits under one.

    A fusion has its root's: the compiler gives a fusion the metadata of
    the instruction it grew from, and where it gave none the root of the
    fused computation is read instead. The compiler also makes
    instructions that carry no name stack at all; each takes the scope of
    what it works for, found in this order:

    1. its first scoped operand's. jax lowers ``cumsum`` on a TPU through a
       cached sub-function (``reduce-window`` and its glue carry
       ``op_name="reduce_window_sum"`` and no caller's stack), and the
       compiler puts slices and copies on a scoped value's way;
    2. its nearest scoped user's, through tuples and other unscoped
       instructions. A reshape that changes a tiled layout becomes a
       generated ``while`` of ``dynamic-update-slice`` with the reshape's
       metadata dropped: the loop feeds only what the reshape fed (the
       index stream's relayout ahead of each gather of
       ``sparse_poisson.solve``);
    3. that of the instruction that calls its computation: the body of
       such a generated loop.

    ``parameter``, ``tuple`` and ``get-tuple-element`` move no data and get
    no scope; control flow (``while``, ``conditional``, ``call``) gets none
    from its operands, which are whole loop states."""
    instrs = parse_instructions(obj)
    roots = {i.computation: i for i in instrs.values() if i.is_root}
    out: dict[str, tuple[str, ...]] = {}
    for ins in instrs.values():  # text order: operands before their users
        path = scope_path(ins.op_name)
        if not path and ins.calls in roots:
            path = scope_path(roots[ins.calls].op_name)
        if not path and ins.opcode not in _NO_OPERAND_SCOPE:
            for operand in ins.operand_names:
                producer = instrs.get(operand)
                if (
                    producer is not None
                    and producer.computation == ins.computation
                    and operand in out
                ):
                    path = out[operand]
                    break
        if path:
            out[ins.name] = path

    users: dict[str, list[HloInstruction]] = {}
    for ins in instrs.values():
        for operand in ins.operand_names:
            users.setdefault(operand, []).append(ins)
    unscoped = [
        i for i in instrs.values() if i.name not in out and i.opcode not in _NO_SCOPE
    ]
    from_users = {i.name: _nearest_scoped_user(i, users, out) for i in unscoped}
    out.update({name: path for name, path in from_users.items() if path})

    callers: dict[str, HloInstruction] = {}
    for ins in instrs.values():
        for callee in _CALLEE_RE.findall(ins.attributes):
            callers.setdefault(callee, ins)
    for ins in unscoped:
        if ins.name in out:
            continue
        caller = callers.get(ins.computation)
        while caller is not None and caller.name not in out:
            caller = callers.get(caller.computation)
        if caller is not None:
            out[ins.name] = out[caller.name]
    return out


def _nearest_scoped_user(ins, users, scoped, limit: int = 64):
    """The scope path of the first scoped instruction reached from ``ins``
    along its users (breadth first, within its computation, at most
    ``limit`` instructions), or ``None``."""
    seen, frontier = {ins.name}, [ins]
    while frontier and len(seen) < limit:
        nxt = []
        for node in frontier:
            for user in users.get(node.name, ()):
                if user.computation != ins.computation or user.name in seen:
                    continue
                if user.name in scoped:
                    return scoped[user.name]
                seen.add(user.name)
                nxt.append(user)
        frontier = nxt
    return None


def instruction_scopes(obj: Any) -> dict[str, str]:
    """Instruction name -> innermost ``photon.*`` scope (see
    :func:`instruction_scope_paths`); instructions under none are left
    out. Join on an executable compiled by this tree (THE TRAP above)."""
    return {k: v[-1] for k, v in instruction_scope_paths(obj).items()}


#: the row of :func:`seconds_by_scope` for instructions under no scope
UNSCOPED = "(no photon scope)"


def seconds_by_scope(
    op_seconds: Mapping[str, float], scopes: Mapping[str, Any]
) -> dict[str, float]:
    """``{instruction: seconds}`` (one module's device time by
    instruction, as a trace reduction gives it) summed by the value
    ``scopes`` holds for the instruction: the innermost scope
    (:func:`instruction_scopes`) or a whole path, joined with ``/``.
    What no scope covers is summed under :data:`UNSCOPED`."""
    out: dict[str, float] = {}
    for name, secs in op_seconds.items():
        key = scopes.get(name, UNSCOPED)
        if not isinstance(key, str):
            key = "/".join(key)
        out[key] = out.get(key, 0.0) + secs
    return out


_METADATA_RE = re.compile(r',?\s*metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
# the tables metadata's stack_frame_id points into, printed ahead of the
# computations: a heading, numbered rows, a blank line
_SOURCE_TABLES_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*\n*",
    re.MULTILINE,
)


def strip_metadata(text: str) -> str:
    """Module text without any ``metadata={...}`` and without the source
    tables it points into: what is left is the program itself. Two builds
    that differ only in scopes (or in the lines their source sits on) are
    byte-identical after this."""
    return _METADATA_RE.sub("", _SOURCE_TABLES_RE.sub("", text))


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
