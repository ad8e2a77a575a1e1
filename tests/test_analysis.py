"""photon-lint: rule fixtures, the gate, the baseline, the program passes.

Layout mirrors the suite: per-rule positive/negative fixture pairs under
tests/fixtures/phl/ (each positive is the MINIMIZED form of a bug this
repo actually shipped), CLI gate semantics (exit 1 on new findings, 2 on
stale baseline entries), the three historical bug patterns pinned
end-to-end through the CLI, the stale-allowlist detector over the
COMMITTED baseline, and the program passes on synthetic + real modules.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from photon_tpu.analysis import analyze_source, analyze_tree, hlo
from photon_tpu.analysis.baseline import (
    BaselineEntry,
    apply_baseline,
    load_baseline,
    write_baseline,
)
from photon_tpu.analysis.cli import main
from photon_tpu.analysis.core import default_scan_files, is_hot_path

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "phl"

ALL_RULES = (
    "PHL001", "PHL002", "PHL003", "PHL004", "PHL005", "PHL006",
    "PHL007", "PHL008", "PHL009", "PHL010",
)


def _findings(name: str, rule: str):
    src = (FIXTURES / name).read_text()
    return [
        f
        for f in analyze_source(src, name, hot=True, mesh_scoped=True)
        if f.rule == rule and f.status == "new"
    ]


# --- every rule: positive fires, negative is silent -----------------------


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_fires_on_positive_fixture(rule):
    found = _findings(f"{rule.lower()}_bad.py", rule)
    assert found, f"{rule} missed every planted bug in its positive fixture"


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_silent_on_negative_fixture(rule):
    found = _findings(f"{rule.lower()}_good.py", rule)
    assert not found, (
        f"{rule} false-positives on the sanctioned pattern:\n"
        + "\n".join(f.render() for f in found)
    )


def test_phl001_catches_every_escape_route():
    src = (FIXTURES / "phl001_bad.py").read_text()
    lines = {f.line for f in analyze_source(src, "x.py", hot=True)
             if f.rule == "PHL001"}
    # callback arg, return, attribute store, dict-of-views — every
    # escape route of the PR 2 shape
    assert len(lines) == 4, lines


def test_phl001_copy_false_is_still_a_view():
    """copy=False is an explicitly REQUESTED view — the PR 2 hazard
    spelled one kwarg differently must not slip past either rule."""
    src = (
        "import numpy as np\n"
        "def f(state):\n"
        "    return np.asarray(state, copy=False)[:10]\n"
    )
    rules = {f.rule for f in analyze_source(src, "x.py", hot=True)}
    assert "PHL001" in rules


def test_phl003_str_join_is_not_a_thread_reap():
    src = (
        "import threading\n"
        "def f(items, work):\n"
        "    t = threading.Thread(target=work)\n"
        "    t.start()\n"
        "    try:\n"
        "        pass\n"
        "    finally:\n"
        "        print(','.join(items))\n"
    )
    found = [f for f in analyze_source(src, "x.py") if f.rule == "PHL003"]
    assert found, "a str.join in a finally satisfied the thread-reap check"


def test_phl003_positional_blocking_put_is_flagged():
    src = (
        "import queue, threading\n"
        "def produce(chunks, q):\n"
        "    for c in chunks:\n"
        "        q.put(c, True)\n"  # blocking, no timeout
    )
    found = [
        f for f in analyze_source(src, "x.py")
        if f.rule == "PHL003" and "timeout" in f.message
    ]
    assert found
    src_ok = src.replace("q.put(c, True)", "q.put(c, False)")
    found_ok = [
        f for f in analyze_source(src_ok, "x.py")
        if f.rule == "PHL003" and "timeout" in f.message
    ]
    assert not found_ok  # non-blocking put is interruptible


def test_write_baseline_refuses_phl000_and_partial_scans(tmp_path, capsys):
    root = _tree(
        tmp_path, {"photon_tpu/util/broken.py": "def broken(:\n"}
    )
    assert main(["--root", str(root)]) == 1  # PHL000 gates
    assert main(["--root", str(root), "--write-baseline"]) == 0
    entries = load_baseline(root / "photon_tpu/analysis/baseline.toml")
    assert not entries, "a parse failure was written into the allowlist"
    assert main(["--root", str(root)]) == 1  # still gating
    with pytest.raises(SystemExit):
        main(["--root", str(root), "--rules", "PHL006", "--write-baseline"])


def test_phl003_catches_all_three_lifecycle_bugs():
    found = _findings("phl003_bad.py", "PHL003")
    messages = " ".join(f.message for f in found)
    assert "timeout" in messages  # blocking put in loop
    assert "unbounded" in messages  # Queue() without maxsize
    assert "join" in messages  # thread never reaped
    assert len(found) == 3


def test_phl005_distinguishes_static_from_traced():
    found = _findings("phl005_bad.py", "PHL005")
    assert len(found) == 3  # tracer if, tracer while, unhashable default
    # `n` is static in loop_on_tracer — only `mask` may be named
    assert not any("'n'" in f.message for f in found)


def test_hot_path_scoping():
    # PHL002 is scoped: the same sync outside a hot-path module is fine
    src = "import numpy as np\ndef f(x):\n    return float(x.sum())\n"
    hot = analyze_source(src, "photon_tpu/game/descent.py")
    cold = analyze_source(src, "photon_tpu/io/avro.py")
    assert any(f.rule == "PHL002" for f in hot)
    assert not any(f.rule == "PHL002" for f in cold)
    assert is_hot_path("photon_tpu/optimize/lbfgs.py")
    assert not is_hot_path("photon_tpu/obs/tracer.py")


def test_mesh_scoping_for_phl007():
    """PHL007 fires in mesh-scoped modules (hot paths + parallel/) and
    stays silent in scripts — a default-device put in a load harness is
    fine; in the sharding layer it is the replicated-table hazard. PHL008
    is whole-tree (a shard_map call site is mesh code wherever it is)."""
    from photon_tpu.analysis.core import is_mesh_scoped

    assert is_mesh_scoped("photon_tpu/parallel/mesh.py")
    assert is_mesh_scoped("photon_tpu/game/scoring.py")
    assert not is_mesh_scoped("scripts/load_harness.py")
    src = "import jax\ndef f(x):\n    return jax.device_put(x)\n"
    mesh_scoped = analyze_source(src, "photon_tpu/parallel/mesh.py")
    script = analyze_source(src, "scripts/load_harness.py")
    assert any(f.rule == "PHL007" for f in mesh_scoped)
    assert not any(f.rule == "PHL007" for f in script)
    sm = (
        "from photon_tpu.parallel.mesh import shard_map\n"
        "def g(f, mesh, spec):\n"
        "    return shard_map(f, mesh=mesh, in_specs=(spec,))\n"
    )
    assert any(
        f.rule == "PHL008" for f in analyze_source(sm, "scripts/whatever.py")
    )


def test_phl007_accepts_positional_and_kwarg_targets():
    base = "import jax\ndef f(x, s):\n    return jax.device_put(x{})\n"
    for ok in (", s", ", device=s", ", sharding=s"):
        found = [
            f
            for f in analyze_source(
                base.format(ok), "x.py", mesh_scoped=True
            )
            if f.rule == "PHL007"
        ]
        assert not found, f"PHL007 false-positive on device_put(x{ok})"
    # the scopes are independent: forcing hot must not force mesh scope
    bad = base.format("")
    assert not [
        f for f in analyze_source(bad, "x.py", hot=True)
        if f.rule == "PHL007"
    ]
    assert [
        f for f in analyze_source(bad, "x.py", mesh_scoped=True)
        if f.rule == "PHL007"
    ]


def test_phl008_accepts_positional_out_specs():
    src = (
        "from jax.experimental.shard_map import shard_map\n"
        "def g(f, mesh, si, so):\n"
        "    return shard_map(f, mesh, si, so)\n"
    )
    assert not [
        f for f in analyze_source(src, "x.py", hot=True)
        if f.rule == "PHL008"
    ]


def test_annotation_requires_reason():
    base = "import time\nt = time.time()  # phl-ok: PHL006{}\n"
    without = analyze_source(base.format(""), "x.py")
    with_reason = analyze_source(base.format(" epoch anchor"), "x.py")
    assert [f.status for f in without if f.rule == "PHL006"] == ["new"]
    assert [f.status for f in with_reason if f.rule == "PHL006"] == [
        "annotated"
    ]


def test_annotation_inside_string_literal_does_not_suppress():
    """Only real COMMENTS annotate — the marker in a log message or a
    docstring must not silently suppress the finding below it."""
    src = (
        "import time\n"
        'MSG = "annotate with # phl-ok: PHL006 see docs"\n'
        "t = time.time()\n"
    )
    found = [f for f in analyze_source(src, "x.py") if f.rule == "PHL006"]
    assert [f.status for f in found] == ["new"]


def test_syntax_error_is_a_finding_not_a_crash():
    found = analyze_source("def broken(:\n", "x.py")
    assert [f.rule for f in found] == ["PHL000"]


# --- the gate: CLI semantics over a temp tree -----------------------------


def _tree(tmp_path: Path, files: dict[str, str]) -> Path:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return tmp_path


def _clean_tree(tmp_path):
    return _tree(
        tmp_path,
        {"photon_tpu/game/descent.py": "def sweep(states):\n    return states\n"},
    )


def test_cli_exit0_on_clean_tree(tmp_path, capsys):
    root = _clean_tree(tmp_path)
    assert main(["--root", str(root)]) == 0
    assert "PASS" in capsys.readouterr().out


@pytest.mark.parametrize(
    "rule,fixture,target",
    [
        # the three historical bug patterns, re-introduced verbatim:
        # PR 2 donated-view aliasing, PR 5 unreaped producer thread,
        # PR 3 ctypes temporary-buffer indexing
        ("PHL001", "phl001_bad.py", "photon_tpu/game/descent.py"),
        ("PHL003", "phl003_bad.py", "photon_tpu/game/scoring.py"),
        ("PHL004", "phl004_bad.py", "photon_tpu/io/native_avro.py"),
    ],
)
def test_cli_blocks_reintroduced_historical_bug(
    tmp_path, capsys, rule, fixture, target
):
    root = _tree(tmp_path, {target: (FIXTURES / fixture).read_text()})
    rc = main(["--root", str(root)])
    out = capsys.readouterr().out
    assert rc == 1, f"the {rule} historical pattern passed the gate:\n{out}"
    assert rule in out


def test_cli_jsonl_artifact(tmp_path, capsys):
    root = _tree(
        tmp_path,
        {"photon_tpu/io/native_avro.py": (FIXTURES / "phl004_bad.py").read_text()},
    )
    artifact = tmp_path / "out" / "findings.jsonl"
    assert main(["--root", str(root), "--jsonl", str(artifact)]) == 1
    rows = [json.loads(ln) for ln in artifact.read_text().splitlines()]
    assert rows and all(r["rule"] == "PHL004" for r in rows)
    assert {"engine", "path", "line", "snippet", "status"} <= set(rows[0])


def test_cli_rules_filter(tmp_path, capsys):
    root = _tree(
        tmp_path,
        {"photon_tpu/io/native_avro.py": (FIXTURES / "phl004_bad.py").read_text()},
    )
    assert main(["--root", str(root), "--rules", "PHL006"]) == 0
    assert main(["--root", str(root), "--rules", "PHL004,PHL006"]) == 1


def test_baseline_allows_and_goes_stale(tmp_path, capsys):
    bad = "import time\n\ndef f():\n    return time.time()\n"
    root = _tree(tmp_path, {"photon_tpu/util/x.py": bad})
    baseline = root / "photon_tpu" / "analysis" / "baseline.toml"
    baseline.parent.mkdir(parents=True)
    write_baseline(
        baseline,
        [
            BaselineEntry(
                rule="PHL006",
                path="photon_tpu/util/x.py",
                snippet="return time.time()",
                note="pinned for the test",
            )
        ],
    )
    assert main(["--root", str(root)]) == 0  # allowed by baseline
    # fix the site → the entry is STALE → exit 2 until it is removed
    (root / "photon_tpu/util/x.py").write_text(
        "import time\n\ndef f():\n    return time.monotonic()\n"
    )
    rc = main(["--root", str(root)])
    assert rc == 2
    assert "STALE" in capsys.readouterr().out


def test_write_baseline_round_trip(tmp_path, capsys):
    root = _tree(
        tmp_path,
        {"photon_tpu/util/x.py": "import time\nT0 = time.time()\n"},
    )
    assert main(["--root", str(root)]) == 1
    assert main(["--root", str(root), "--write-baseline"]) == 0
    entries = load_baseline(root / "photon_tpu/analysis/baseline.toml")
    assert [e.rule for e in entries] == ["PHL006"]
    assert main(["--root", str(root)]) == 0  # now allowed


# --- the committed baseline: every entry resolves, HEAD is clean ----------


def test_committed_tree_passes_and_baseline_has_no_stale_entries():
    """The stale-allowlist detector: every committed baseline entry must
    still match a real finding, and HEAD must carry no NEW findings —
    this is exactly `python -m photon_tpu.analysis` exiting 0."""
    findings = analyze_tree(REPO)
    entries = load_baseline(REPO / "photon_tpu/analysis/baseline.toml")
    assert entries, "committed baseline is missing or empty"
    gate = apply_baseline(findings, entries)
    assert not gate.new, "HEAD has unbaselined findings:\n" + "\n".join(
        f.render() for f in gate.new
    )
    assert not gate.stale, (
        "stale baseline entries (fix shipped but entry not removed):\n"
        + "\n".join(e.render() for e in gate.stale)
    )


def test_scan_covers_package_scripts_and_bench():
    files = {p.as_posix() for p in default_scan_files(REPO)}
    assert any("photon_tpu/game/coordinate.py" in f for f in files)
    assert any("scripts/" in f for f in files)
    assert any(f.endswith("bench.py") for f in files)
    assert not any("tests/" in f for f in files)


# --- program checks -------------------------------------------------------


def test_find_collectives_both_dialects():
    hlo_text = "ROOT %r = f32[] all-reduce(f32[] %x), replica_groups={}"
    shlo_text = '%1 = "stablehlo.all_reduce"(%0) : (tensor<4xf32>)'
    assert hlo.find_collectives(hlo_text) == ["all-reduce"]
    assert hlo.find_collectives(shlo_text) == ["stablehlo.all_reduce"]
    assert hlo.find_collectives("%1 = f32[8] add(%a, %b)") == []


def test_find_large_constants_both_dialects():
    hlo_text = "%c = f32[64,1024]{1,0} constant({...})"
    shlo_text = "%c = stablehlo.constant dense<1.0> : tensor<64x1024xf32>"
    small = "%c = f32[4]{0} constant({1,2,3,4})"
    assert hlo.find_large_constants(hlo_text, 16 * 1024) == [
        ("f32[64,1024]", 262144)
    ]
    assert hlo.find_large_constants(shlo_text, 16 * 1024) == [
        ("tensor<64x1024xf32>", 262144)
    ]
    assert hlo.find_large_constants(small, 16 * 1024) == []


def test_planted_closure_constant_detected_end_to_end():
    """Meta-test on a REAL compiled module: the pass must see a closure
    constant at the jaxpr level, the lowered level, and the compiled
    level — otherwise the audits prove nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    big = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, 1024)), jnp.float32
    )

    @jax.jit
    def leaky(v):
        return jnp.sum(big * v)

    jaxpr = jax.make_jaxpr(lambda v: leaky(v))(jnp.float32(2.0))
    assert hlo.check_jaxpr_const_embedding(jaxpr, "leaky")
    lowered = jax.jit(leaky).lower(jnp.float32(2.0))
    assert hlo.check_const_embedding(lowered, "leaky")
    assert hlo.check_const_embedding(lowered.compile(), "leaky")
    # and a clean program stays clean at every level
    clean = jax.jit(lambda v: v * 2.0).lower(jnp.ones((8,), jnp.float32))
    assert not hlo.check_const_embedding(clean, "clean")
    assert not hlo.check_const_embedding(clean.compile(), "clean")


def test_shape_budget_census():
    import numpy as np

    class FakeCoord:
        def __init__(self, shapes):
            class B:
                def __init__(self, e, r, d):
                    self.features = np.zeros((e, r, d), np.float32)

            self.device_buckets = [B(4, r, d) for r, d in shapes]

    coords = {
        "RandomEffectCoordinate": FakeCoord([(8, 4), (16, 4), (8, 4)]),
        "other": FakeCoord([(32, 6)]),
    }
    assert hlo.solve_shape_census(coords) == {(8, 4), (16, 4), (32, 6)}
    assert hlo.check_shape_budget(coords, 3) == []
    over = hlo.check_shape_budget(coords, 2)
    assert over and "exceed the shape budget" in over[0].message
    assert hlo.check_shape_budget(coords, None) == []  # disabled


@pytest.mark.slow
def test_audit_every_precompiled_executable():
    """The generalized hlo-guards: every AOT-precompiled executable of
    the canonical fixture passes collective-freedom and the
    constant-embedding bound, and the census respects the budget —
    the `python -m photon_tpu.analysis --programs` path."""
    from photon_tpu.analysis.cli import build_canonical_fixture
    from photon_tpu.game.data import re_shape_budget

    coordinates = build_canonical_fixture()
    report = hlo.audit_coordinates(
        coordinates, shape_budget=re_shape_budget(None)
    )
    assert report.programs_checked >= 4  # FE sweep+score, RE sweep+score
    assert report.ok, "\n".join(f.render() for f in report.findings)
    assert report.census  # the RE coordinate contributed solve shapes
