"""The ``photon.*`` device scopes: the table and the code agree, the two
benchmark cells' solver programs carry every scope their path uses, every
instruction that reads the data sits under one, and a scope changes nothing
but metadata.

The programs are compiled here for the CPU at the cells' ``rehearse``
shapes, traced under ``target.compiling_for("tpu")`` (the ``for_tpu``
fixture) so that they take the TPU's branches, and never run. The
persistent compile cache is off around them:
metadata is not part of its key, so a program served from it would carry
the scopes of whichever tree wrote the entry.
"""
import contextlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.analysis import hlo
from photon_tpu.obs import scopes
from photon_tpu.obs.scopes import SCOPES
from photon_tpu.util import target

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "photon_tpu")

#: innermost scopes: nothing sits under ``photon.gather`` itself, its two
#: halves are named apart (PR 30)
SPARSE_PATH = {
    "photon.matvec", "photon.rmatvec", "photon.gather.fetch",
    "photon.gather.select", "photon.loss",
    "photon.rmatvec.prefix", "photon.rmatvec.bounds", "photon.rmatvec.combine",
    "photon.owlqn.direction", "photon.owlqn.linesearch", "photon.owlqn.history",
}
DENSE_PATH = {
    "photon.matvec", "photon.rmatvec", "photon.loss", "photon.hvp",
    "photon.tron.cg", "photon.tron.step",
}
#: instructions that hand a value on unchanged or enclose others: they read
#: no data themselves
PASS_THROUGH = {
    "parameter", "tuple", "get-tuple-element", "while", "conditional",
    "call", "bitcast", "copy", "constant",
}


# --- the table and the code ------------------------------------------------


def _scope_calls() -> dict[str, list[str]]:
    """Every literal name passed to ``scope(...)`` in the package -> the
    files that pass it."""
    found: dict[str, list[str]] = {}
    for dirpath, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                text = f.read()
            for m in re.finditer(r'\bscope\(\s*"([^"]+)"', text):
                found.setdefault(m.group(1), []).append(
                    os.path.relpath(path, ROOT)
                )
    return found


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_every_scope_of_the_table_is_used(name):
    assert name in _scope_calls(), f"{name} is in SCOPES and no code opens it"
    layer, meaning = SCOPES[name]
    assert layer and meaning and "\n" not in meaning


def test_every_scope_the_code_opens_is_in_the_table():
    calls = _scope_calls()
    assert calls, "found no scope(...) call: the scan is broken"
    stray = {n: files for n, files in calls.items() if n not in SCOPES}
    assert not stray, f"scopes opened but not in SCOPES: {stray}"
    with pytest.raises(KeyError):
        scopes.scope("photon.not_in_the_table")


def test_named_scope_is_used_through_the_helper_only():
    users = []
    for dirpath, _, files in os.walk(PACKAGE):
        for name in files:
            path = os.path.join(dirpath, name)
            if name.endswith(".py") and "named_scope(" in open(path).read():
                users.append(os.path.relpath(path, PACKAGE))
    assert users == [os.path.join("obs", "scopes.py")]


# --- the two cells' programs -----------------------------------------------


@pytest.fixture()
def fresh_compiles():
    """No persistent cache (module docstring)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def for_tpu():
    """Trace as for a TPU: window layout, prefix rmatvec, row fetch. The
    tests that take it compile and inspect; none runs a program."""
    with target.compiling_for("tpu"):
        yield


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"]}


def _sparse_segmented():
    """``sparse_poisson``'s ``SegmentedOWLQN`` over its batch, as the TPU
    takes it (``for_tpu``): window layout, prefix rmatvec, row fetch."""
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.ops.sparse_windows import build_column_windows
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.owlqn import SegmentedOWLQN
    from photon_tpu.types import SparseBatch, TaskType

    config = _config("sparse_poisson")
    feat, solver = config["features"], config["solver"]
    n, d, k = feat["n"], feat["d"], feat["nnz_per_row"]
    rng = np.random.default_rng(0)
    idx = rng.integers(0, d, size=(n, k), dtype=np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    # built outright: the rehearsal's d is under the policy's 1024
    windows = build_column_windows(idx, vals, d)
    batch = SparseBatch(
        indices=jnp.asarray(idx),
        values=jnp.asarray(vals),
        labels=jnp.ones((n,), jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
        windows=windows,
    )
    lam, alpha = solver["regularization_weight"], solver["elastic_net_alpha"]
    objective = GLMObjective(
        loss=loss_for_task(TaskType[config["task"]]),
        l2_weight=(1 - alpha) * lam,
        l1_weight=alpha * lam,
    )
    seg = SegmentedOWLQN(
        None,
        alpha * lam,
        OptimizerConfig(
            max_iterations=solver["max_iterations"],
            tolerance=solver["tolerance"],
        ),
        oracle_factory=objective.smooth_margin_oracle,
        segment_iters=solver["segment_iters"],
    )
    return seg, batch, jax.ShapeDtypeStruct((d,), jnp.float32)


def _sparse_segment_program():
    """Its segment program, and the shapes of the data it reads."""
    seg, batch, w0 = _sparse_segmented()
    windows = batch.windows
    state = jax.eval_shape(seg._init_f, w0, batch)
    data_shapes = {
        _shape(batch.indices), _shape(batch.values), _shape(windows.rows),
        _shape(windows.vals), _shape(windows.bounds),
    }
    return seg._segment_f.lower(state, batch).compile(), data_shapes


def _dense_tron_program():
    """``linear_tron``'s whole TRON solve under one jit, as the benchmark's
    runner builds it."""
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import (
        GLMProblem,
        GLMProblemConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import LabeledBatch, OptimizerType, TaskType

    config = _config("linear_tron")
    feat, solver = config["features"], config["solver"]
    n, d = feat["n"], feat["d"]
    problem = GLMProblem.build(
        GLMProblemConfig(
            task=TaskType[config["task"]],
            optimizer=OptimizerType.TRON,
            optimizer_config=OptimizerConfig(
                max_iterations=solver["max_iterations"],
                tolerance=solver["tolerance"],
                max_cg_iterations=solver["max_cg_iterations"],
                cg_tolerance=solver["cg_tolerance"],
            ),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=solver["l2_weight"],
        )
    )
    batch = LabeledBatch(
        features=jax.ShapeDtypeStruct((n, d), jnp.float32),
        labels=jax.ShapeDtypeStruct((n,), jnp.float32),
        offsets=jax.ShapeDtypeStruct((n,), jnp.float32),
        weights=jax.ShapeDtypeStruct((n,), jnp.float32),
    )

    @jax.jit
    def tron_solve(batch, w0):
        return problem.solve(batch, w0)

    w0 = jax.ShapeDtypeStruct((d,), jnp.float32)
    return tron_solve.lower(batch, w0).compile(), {_shape(batch.features)}


def _shape(a) -> str:
    """``f32[16384,8]`` as HLO text writes an operand's type."""
    dtype = {"float32": "f32", "int32": "s32"}[str(a.dtype)]
    return f"{dtype}[{','.join(str(s) for s in a.shape)}]"


PROGRAMS = {
    "sparse_poisson": (_sparse_segment_program, SPARSE_PATH),
    "linear_tron": (_dense_tron_program, DENSE_PATH),
}


def _scoped_modules():
    """Every module that took ``scope`` by name."""
    import importlib

    files = {f for fs in _scope_calls().values() for f in fs}
    names = {f[: -len(".py")].replace(os.sep, ".") for f in files}
    return [importlib.import_module(n) for n in sorted(names)]


@pytest.mark.parametrize("cell", sorted(PROGRAMS))
def test_cell_program_carries_its_scopes(cell, for_tpu, fresh_compiles):
    build, expected = PROGRAMS[cell]
    compiled, data_shapes = build()
    scope_of = hlo.instruction_scopes(compiled)
    assert expected <= set(scope_of.values()), (
        f"missing: {sorted(expected - set(scope_of.values()))}"
    )
    assert set(scope_of.values()) <= set(SCOPES)
    # every instruction that reads the feature block, the index stream or
    # the window layout does so under a photon.* scope
    instrs = hlo.parse_instructions(compiled)
    fused = {ins.calls for ins in instrs.values() if ins.calls}
    readers, outside = 0, []
    for ins in instrs.values():
        # what a trace names: not the inside of a fusion
        if ins.opcode in PASS_THROUGH or ins.computation in fused:
            continue
        operand_types = {
            instrs[name].shape.split("{")[0]
            for name in ins.operand_names
            if name in instrs
        }
        if data_shapes & operand_types:
            readers += 1
            if ins.name not in scope_of:
                outside.append((ins.name, ins.opcode, ins.op_name))
    assert readers > 0, "no instruction reads the data: the shapes are wrong"
    assert not outside, f"read the data under no photon.* scope: {outside}"
    # the per-scope sum loses no time and names what it could not place
    seconds = {name: 1.0 for name in instrs}
    by_scope = hlo.seconds_by_scope(seconds, scope_of)
    assert sum(by_scope.values()) == pytest.approx(len(seconds))
    assert by_scope[hlo.UNSCOPED] == len(seconds) - len(scope_of)


def test_segment_loop_names_the_gathers_two_halves(
    monkeypatch, for_tpu, fresh_compiles
):
    """The segment program with its passes cut into several segments (the
    rehearsal shapes at a 2^20 B segment): under ``photon.gather`` a scope
    join finds ``photon.gather.fetch`` and ``photon.gather.select`` in both
    passes and nothing else, and the passes' consumers stay outside it."""
    import photon_tpu.ops.gather as gather_mod

    monkeypatch.setattr(gather_mod, "_SEG_BYTES", 1 << 20)
    config = _config("sparse_poisson")["features"]
    assert gather_mod.segment_plan(
        config["n"], config["nnz_per_row"], 4, 128
    ).steps >= 3
    compiled, _ = _sparse_segment_program()
    assert " while(" in compiled.as_text()
    paths = set(hlo.instruction_scope_paths(compiled).values())
    under = {p for p in paths if "photon.gather" in p}
    halves = ("photon.gather.fetch", "photon.gather.select")
    assert {p[-1] for p in under} == set(halves), sorted(under)
    for half in halves:
        for outer in ("photon.matvec", "photon.rmatvec"):
            assert any(
                p[-3:] == (outer, "photon.gather", half) for p in under
            ), (outer, half, sorted(under))
    # the backward pass's consumer runs in the loop, under its own names
    assert {"photon.rmatvec.prefix", "photon.rmatvec.bounds"} <= {
        p[-1] for p in paths
    }


@pytest.mark.parametrize("cell", sorted(PROGRAMS))
def test_scopes_change_nothing_but_metadata(
    cell, monkeypatch, for_tpu, fresh_compiles
):
    build, _ = PROGRAMS[cell]
    scoped = build()[0].as_text()
    for module in _scoped_modules():
        monkeypatch.setattr(
            module, "scope", lambda name: contextlib.nullcontext()
        )
    bare = build()[0].as_text()
    assert "photon." in scoped and "photon." not in bare
    assert hlo.strip_metadata(scoped) == hlo.strip_metadata(bare)
    assert "metadata=" not in hlo.strip_metadata(scoped)


def test_scope_path_and_fusion_root_and_inheritance():
    text = """
HloModule m

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/photon.matvec/photon.gather/add"}
}

%wide.body (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.7 = f32[8]{0} get-tuple-element(%s), index=1
  %dynamic-update-slice.9 = f32[8]{0} dynamic-update-slice(%get-tuple-element.7, %get-tuple-element.7)
  %get-tuple-element.8 = s32[] get-tuple-element(%s), index=0
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%get-tuple-element.8, %dynamic-update-slice.9)
}

ENTRY %main (a: f32[8], b: (s32[], f32[8])) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = (s32[], f32[8]{0}) parameter(1)
  %while.5 = (s32[], f32[8]{0}) while(%b), condition=%wide.cond, body=%wide.body
  %get-tuple-element.6 = f32[8]{0} get-tuple-element(%while.5), index=1
  %fusion.3 = f32[8]{0} fusion(%get-tuple-element.6), kind=kLoop, calls=%fused_computation
  %reduce-window.1 = f32[8]{0} reduce-window(%fusion.3, %a), window={size=8}, metadata={op_name="reduce_window_sum"}
  %neg.2 = f32[8]{0} negate(%a), metadata={op_name="jit(f)/neg"}
  %exp.3 = f32[8]{0} exponential(%neg.2), metadata={op_name="jit(f)/exp"}
  ROOT %mul.4 = f32[8]{0} multiply(%reduce-window.1, %a), metadata={op_name="jit(f)/while/body/photon.loss/mul" stack_frame_id=3}
}
"""
    paths = hlo.instruction_scope_paths(text)
    gather = ("photon.matvec", "photon.gather")
    assert paths["add.1"] == gather
    assert paths["fusion.3"] == gather  # its root's
    assert paths["reduce-window.1"] == gather  # its producer's
    assert paths["while.5"] == gather  # a generated loop: its user's
    assert paths["dynamic-update-slice.9"] == gather  # and the loop's body
    for moves_nothing in ("a", "b", "s", "get-tuple-element.6", "tuple.2"):
        assert moves_nothing not in paths
    assert "neg.2" not in paths and "exp.3" not in paths  # reach no scope
    assert hlo.instruction_scopes(text) == {
        "add.1": "photon.gather", "fusion.3": "photon.gather",
        "reduce-window.1": "photon.gather", "while.5": "photon.gather",
        "dynamic-update-slice.9": "photon.gather", "mul.4": "photon.loss",
    }
    by_path = hlo.seconds_by_scope({"fusion.3": 2.0, "neg.2": 0.5}, paths)
    assert by_path == {"photon.matvec/photon.gather": 2.0, hlo.UNSCOPED: 0.5}
    stripped = hlo.strip_metadata(text)
    assert "metadata" not in stripped
    assert "multiply(%reduce-window.1, %a)" in stripped


# --- the passes a solve's boundary evaluations may hold ---------------------
#
# Compiled for the CPU with the passes cut into segments, a pass over the
# feature block is a ``while`` under ``photon.matvec`` (forward) or
# ``photon.rmatvec`` (backward), and its name stack says where it sits: in a
# branch of the start's ``cond``, in the optimizer's iteration loop, or
# in neither.

_PLACE_RE = re.compile(r"/(cond/branch_\d+_fun|while/body)/")


def _passes_by_place(compiled) -> dict[tuple[str, str], list[str]]:
    """``{(place, scope): [names, in text order]}`` of the program's pass
    loops: place is ``branch_0``/``branch_1`` of the start's ``cond``,
    ``iterations`` (the optimizer's loop) or ``top``; scope is the pass's
    ``photon.matvec`` or ``photon.rmatvec``."""
    out: dict[tuple[str, str], list[str]] = {}
    for ins in hlo.parse_instructions(compiled).values():
        # the loop's OWN name stack: a scope join also hands the optimizer's
        # loop the scope of what it encloses (on a TPU, ``photon.matvec``)
        path = hlo.scope_path(ins.op_name)
        if ins.opcode != "while" or not path or path[-1] not in (
            "photon.matvec", "photon.rmatvec"
        ):
            continue
        # what encloses the pass: the name stack ahead of its own scope
        ahead = ins.op_name.split("/photon.")[0] + "/"
        m = _PLACE_RE.search(ahead)
        place = "top"
        if m is not None:
            place = (
                "iterations" if m.group(1) == "while/body"
                else m.group(1)[len("cond/"):-len("_fun")]
            )
        out.setdefault((place, path[-1]), []).append(ins.name)
    return out


def test_init_program_reads_no_block_forward_from_a_zero_start(
    monkeypatch, for_tpu, fresh_compiles
):
    """``jit_init_f`` (a solve's start, the benchmark runner's first call):
    outside the start's ``cond`` there is ONE pass, the zero point's
    backward one; the branch a zero start takes holds none, the other
    branch the start point's two."""
    import photon_tpu.ops.gather as gather_mod

    monkeypatch.setattr(gather_mod, "_SEG_BYTES", 1 << 20)
    seg, batch, w0 = _sparse_segmented()
    passes = _passes_by_place(seg._init_f.lower(w0, batch).compile())
    assert {k: len(v) for k, v in passes.items()} == {
        ("top", "photon.rmatvec"): 1,
        ("branch_1", "photon.matvec"): 1,
        ("branch_1", "photon.rmatvec"): 1,
    }, passes


def test_fe_sweep_program_reads_the_block_once_after_its_loop(
    monkeypatch, for_tpu, fresh_compiles
):
    """``jit_fe_sweep``: ahead of the iteration loop the zero point's
    backward pass and nothing forward outside the branch a non-zero start
    takes; in the loop a forward and a backward pass; after it ONE forward
    pass, the last exact re-evaluation's, whose product the rescoring
    takes (it made a second one), and that evaluation's backward pass."""
    import photon_tpu.ops.gather as gather_mod
    from photon_tpu.game.config import FixedEffectCoordinateConfig
    from photon_tpu.game.coordinate import FixedEffectCoordinate
    from photon_tpu.game.data import CSRMatrix, GameData
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import (
        GLMProblemConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.game.config import FeatureRepresentation
    from photon_tpu.types import TaskType

    monkeypatch.setattr(gather_mod, "_SEG_BYTES", 1 << 20)
    # the rehearsal's d is under the policy's 1024
    monkeypatch.setattr(
        "photon_tpu.ops.sparse_windows.windows_pay", lambda num_features: True
    )
    feat = _config("sparse_poisson")["features"]
    n, d, k = feat["n"], feat["d"], feat["nnz_per_row"]
    rng = np.random.default_rng(0)
    data = GameData.build(
        labels=(rng.uniform(size=n) > 0.5).astype(np.float64),
        feature_shards={
            "global": CSRMatrix(
                indptr=np.arange(n + 1, dtype=np.int64) * k,
                indices=rng.integers(0, d, size=n * k).astype(np.int32),
                values=rng.normal(size=n * k),
                num_cols=d,
            )
        },
        id_tags={},
    )
    coord = FixedEffectCoordinate.build(
        data,
        FixedEffectCoordinateConfig(
            feature_shard="global",
            optimization=GLMProblemConfig(
                task=TaskType.LOGISTIC_REGRESSION,
                regularization=RegularizationContext(RegularizationType.L2),
                optimizer_config=OptimizerConfig(max_iterations=10),
            ),
            regularization_weights=(1.0,),
            representation=FeatureRepresentation.SPARSE,
        ),
    )
    assert coord.batch.windows is not None
    compiled = coord._sweep_lowered(False).compile()
    passes = _passes_by_place(compiled)
    assert {k: len(v) for k, v in passes.items()} == {
        ("top", "photon.matvec"): 1,
        ("top", "photon.rmatvec"): 2,
        ("branch_1", "photon.matvec"): 1,
        ("branch_1", "photon.rmatvec"): 1,
        ("iterations", "photon.matvec"): 1,
        ("iterations", "photon.rmatvec"): 1,
    }, passes
    # text order is the schedule's: the one forward pass outside branch and
    # loop comes after the iteration loop, as does one of the backward two
    instrs = hlo.parse_instructions(compiled)
    order = list(instrs)
    (forward,) = passes[("top", "photon.matvec")]
    (loop,) = [
        ins.name
        for ins in instrs.values()
        if ins.opcode == "while"
        and ins.computation == instrs[forward].computation
        and "photon." not in (ins.op_name or "")
    ]
    assert order.index(forward) > order.index(loop)
    before, after = sorted(order.index(p) for p in passes[("top", "photon.rmatvec")])
    assert before < order.index(loop) < after


def test_scope_join_charges_operations_to_the_execution_they_start_in():
    """``scripts/scope_join.py``: two coordinates run one program name;
    every operation goes to the execution it started in, keeping its self
    time (a ``while`` encloses its body), and a scope path falls into the
    row of the summary that names it."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "scripts", "scope_join.py")
    spec = importlib.util.spec_from_file_location("scope_join", path)
    join = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(join)
    executions = [(0.0, 1.0, "jit_re_sweep(1)"), (1.0, 3.0, "jit_re_sweep(2)"),
                  (3.0, 3.5, "jit_other(9)"), (4.0, 5.0, "jit_re_sweep(1)")]
    ops = [("while.1", 0.125, 0.875), ("fusion.2", 0.25, 0.5), ("fusion.2", 1.0, 2.5),
           ("sort.9", 2.5, 3.0), ("copy.3", 3.125, 3.25), ("fusion.2", 4.25, 4.5)]
    per, module_s, other_s = join.charge(
        executions, ops, {"jit_re_sweep(1)": "per_user", "jit_re_sweep(2)": "per_movie"})
    assert per == {"per_user": {"while.1": 0.5, "fusion.2": 0.5},
                   "per_movie": {"fusion.2": 1.5, "sort.9": 0.5}}
    assert module_s == {"per_user": 2.0, "per_movie": 2.0} and other_s == 0.5
    assert join._group(("photon.descent.rescore", "photon.re.rescore")) == "photon.re.rescore"
    assert join._group(("photon.re.solve", "photon.re.fetch")) == "photon.re.fetch"
    assert join._group(("photon.re.solve", "photon.re.chunk", "photon.lbfgs.direction")) \
        == "photon.re.solve (solver)"
    assert join._group(("photon.re.solve",)) == "photon.re.solve (own)"
    assert join._group(("photon.descent.residual",)) == "photon.descent.*"
    assert join._group(()) == "(no photon scope)"
