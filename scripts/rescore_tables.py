"""The random-effect rescoring alone, by the height of its coefficient table.

    python3 scripts/rescore_tables.py [--heights 27278 65536 ...] \
        [--seg-bytes 33554432 134217728] [--out chiprun_out/rescore_tables.json]

What ``ops/gather._PACKED_TABLE_BYTES`` was read from (PERF.md section 6,
PR 40), and what a change to it or to the rescoring's segments is read with.
For every table height it runs ``RandomEffectCoordinate._rescore_rows``
itself on one ``[2**23, 16]`` block of random rows, slots and coefficients,
once with the plain ``coefs[slot]`` (the constant forced to 0) and once with
the packed lane-row fetch (forced past every table), and for the first
three heights the packed fetch again at each ``--seg-bytes`` in place of
``gather._SEG_BYTES``. Prints a JSON line a program: wall of the median of
five calls, the compiler's temporaries, whether the packed scores equal the
plain ones bit for bit; then, from one profiler trace of two calls each,
device seconds by innermost ``photon.*`` scope and the largest operations.
Needs a TPU; ``--rehearse`` runs tiny shapes on any backend, the programs
traced as for a TPU, up to the trace (a CPU trace has no device plane).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

WIDTH = 16


def _coordinate(n):
    """A random-effect coordinate of ``n`` samples with no data: all that
    ``_rescore_rows`` reads of it is that it has no mesh."""
    import jax.numpy as jnp

    from photon_tpu.game.config import RandomEffectCoordinateConfig
    from photon_tpu.game.coordinate import RandomEffectCoordinate
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import (
        GLMProblemConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    opt = GLMProblemConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=3),
    )
    return RandomEffectCoordinate(
        config=RandomEffectCoordinateConfig(
            random_effect_type="e", feature_shard="e", optimization=opt,
            regularization_weights=(1.0,)),
        dataset=None, device_buckets=[],
        problem_config=opt.with_regularization_weight(1.0),
        num_samples=n, dtype=jnp.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heights", type=int, nargs="+",
                    default=[27278, 65536, 262144, 524288, 1048576, 2097152],
                    help="entities a table (the zero row comes on top)")
    ap.add_argument("--seg-bytes", type=int, nargs="*", default=[],
                    help="the packed fetch again with gather._SEG_BYTES set to each of these")
    ap.add_argument("--rows", type=int, default=1 << 23)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "scripts")]
    import jax
    import jax.numpy as jnp
    import scope_join

    from benchmarks.lib import trace
    from photon_tpu.analysis import hlo
    from photon_tpu.obs.scopes import scope
    from photon_tpu.ops import gather
    from photon_tpu.util import target

    jax.config.update("jax_enable_compilation_cache", False)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print(f"needs a TPU; JAX found {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    n = args.rows
    coord = _coordinate(n)
    configs = [(e, kind, None) for e in args.heights for kind in ("plain", "packed")]
    configs += [(e, "packed", seg) for e in args.heights[:3] for seg in args.seg_bytes]

    k_feats, k_table, k_slot = jax.random.split(jax.random.PRNGKey(40), 3)
    feats = jax.random.normal(k_feats, (n, WIDTH), jnp.float32)
    rows, programs, plain_scores = [], {}, {}
    for i, (e, kind, seg) in enumerate(configs):
        name = f"r{i}_{kind}_{e}" + (f"_seg{seg}" if seg else "")
        table = jax.random.normal(k_table, (e, WIDTH), jnp.float32)
        # slot e is the zero row the program appends
        slot = jax.random.randint(jax.random.fold_in(k_slot, e), (n,), 0, e + 1, jnp.int32)

        def rescoring(table, feats, slot):  # a fresh function: a trace is cached by function
            with scope("photon.re.rescore"):
                whole = jnp.concatenate([table, jnp.zeros((1, WIDTH), table.dtype)])
                return coord._rescore_rows(feats, slot, whole)

        rescoring.__name__ = name
        constants = gather._PACKED_TABLE_BYTES, gather._SEG_BYTES
        gather._PACKED_TABLE_BYTES = 0 if kind == "plain" else 1 << 62
        gather._SEG_BYTES = seg or gather._SEG_BYTES
        try:
            t0 = time.perf_counter()
            with target.compiling_for("tpu") if args.rehearse else contextlib.nullcontext():
                exe = jax.jit(rescoring).lower(table, feats, slot).compile()
            compile_s = time.perf_counter() - t0
        finally:
            gather._PACKED_TABLE_BYTES, gather._SEG_BYTES = constants
        scores = exe(table, feats, slot).block_until_ready()
        if kind == "plain":
            plain_scores[e] = scores
        walls = []
        for _ in range(5):
            t0 = time.perf_counter()
            exe(table, feats, slot).block_until_ready()
            walls.append(time.perf_counter() - t0)
        row = {
            "name": name, "entities": e + 1, "kind": kind, "seg_bytes": seg,
            "packed_table_bytes": gather.packed_table_bytes(e + 1, WIDTH, 4),
            "wall_s": sorted(walls)[2], "ns_a_row": sorted(walls)[2] / n * 1e9,
            "compile_s": compile_s, "temp_bytes": exe.memory_analysis().temp_size_in_bytes,
            "bit_equal_to_plain": None if kind == "plain"
            else bool(jnp.array_equal(scores, plain_scores[e])),
        }
        rows.append(row)
        programs[name] = (exe, table, slot)
        print(json.dumps(row), flush=True)

    tdir = tempfile.mkdtemp(prefix="rescore_tables_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            for exe, table, slot in programs.values():
                for _ in range(2):
                    exe(table, feats, slot).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        loaded = trace.load(trace.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if loaded["devices"]:
        (device,) = list(loaded["devices"].values())[:1]
        executions = sorted((s, e, raw) for raw, s, e in device["modules"])
        owner = {raw: trace.module_name(raw)[4:] for _, _, raw in executions
                 if trace.module_name(raw)[4:] in programs}
        per, module_s, _ = scope_join.charge(executions, device["ops"], owner)
        for row in rows:
            name = row["name"]
            paths = hlo.instruction_scope_paths(programs[name][0].as_text())
            by_scope: dict[str, float] = {}
            for op, secs in per.get(name, {}).items():
                key = (paths.get(op) or ("(no photon scope)",))[-1]
                by_scope[key] = by_scope.get(key, 0.0) + secs / 2
            row["device_s"] = module_s.get(name, 0.0) / 2
            row["by_scope_s"] = dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))
            row["top_ops"] = [(op, s / 2) for op, s in trace.top(per.get(name, {}), 6)]
            print(f"{name}: {row['device_s'] * 1e3:.2f} ms, {row['device_s'] / n * 1e9:.2f} ns a row; "
                  + ", ".join(f"{k} {v * 1e3:.2f}" for k, v in row["by_scope_s"].items())
                  + "; " + ", ".join(f"{op} {s * 1e3:.2f}" for op, s in row["top_ops"][:4]), flush=True)
    elif not args.rehearse:
        print("no device plane in the trace", file=sys.stderr)
        return 1
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
