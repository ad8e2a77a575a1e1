"""Device seconds of a GAME cell's sweep programs by ``photon.*`` scope.

    python3 scripts/scope_join.py --workload glmix_movielens.sweeps --seed 7 \
        [--steps 2] [--top 14] [--out chiprun_out/join.json]

What PERF.md section 5's breakdowns are made with (the harness itself keeps
device seconds per operation NAME, merged over programs, and knows no
scope: PERF.md section 7 row 8). Run it on the chip from the root of the
tree to be read: the tree in the working directory is the one imported, so
one copy of this file reads a parent checkout and a change alike.

It builds the cell's state through the benchmark's own runner, compiles
every coordinate's sweep program ahead of time with the persistent cache
off (metadata is not in the cache's key: a cached executable may carry
another tree's scopes, or none) and lets the fit dispatch THOSE executables,
so the text that is joined is the text that ran. Two untraced steps warm
up, ``--steps`` steps are traced. Every operation event is charged to the
program execution (``XLA Modules`` event) it started in, keeping only its
self time (``benchmarks/lib/trace.self_seconds``), and is joined to its
scope path by ``analysis/hlo.instruction_scope_paths``. Executions of one
program name (``jit_re_sweep``) are told apart by the order in which the
update sequence first runs them.

Prints, per coordinate and per step: seconds by scope path, the groups a
random effect's time falls into (``photon.re.rescore``, ``photon.re.fetch``,
the rest of ``photon.re.solve``, the descent's own), and the largest
operations with their opcode and scope. Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def _group(path: tuple) -> str:
    """The row of the summary a scope path falls into."""
    if "photon.re.rescore" in path:
        return "photon.re.rescore"
    if "photon.re.fetch" in path:
        return "photon.re.fetch"
    if "photon.re.solve" in path:
        # where the tree has no photon.re.fetch, the fetch is the part of
        # this row that sits under no optimizer scope: see the paths
        inner = [p for p in path if p.startswith(("photon.lbfgs", "photon.owlqn", "photon.tron"))]
        return "photon.re.solve (solver)" if inner else "photon.re.solve (own)"
    if any(p.startswith("photon.descent") for p in path):
        return "photon.descent.*"
    return "/".join(path[:1]) or "(no photon scope)"


def charge(executions, ops, owner):
    """Every operation event charged to the program execution it started
    in. ``executions``: sorted (start, end, raw module name); ``ops``:
    (name, start, end) of the same device; ``owner``: raw module name ->
    coordinate. -> ({coordinate: {operation: self seconds}}, {coordinate:
    seconds of its executions}, seconds of executions nobody owns)."""
    from benchmarks.lib import trace

    ops = sorted(ops, key=lambda ev: ev[1])
    per: dict[str, dict[str, float]] = {cid: {} for cid in owner.values()}
    module_s: dict[str, float] = {}
    other_s = 0.0
    i = 0
    for s, e, raw in executions:
        while i < len(ops) and ops[i][1] < s:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < e:
            j += 1
        cid = owner.get(raw)
        if cid is None:
            other_s += e - s
        else:
            module_s[cid] = module_s.get(cid, 0.0) + e - s
            for name, secs in trace.self_seconds(ops[i:j], s, e).items():
                per[cid][name] = per[cid].get(name, 0.0) + secs
        i = j
    return per, module_s, other_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=None, help="traced steps (the cell's steps_traced)")
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--out", default=None, help="write the whole join here as JSON")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny shapes on any backend, up to the trace: "
                         "a CPU trace has no device plane to join")
    args = ap.parse_args(argv)

    root = os.getcwd()
    sys.path.insert(0, root)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)

    from benchmarks import run as bench
    from benchmarks.lib import trace
    from photon_tpu.analysis import hlo
    from photon_tpu.game.descent import precompile_coordinates

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"needs a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 2
    cell = bench.load_json("benchmarks", "workloads", f"{args.workload}.json")
    config = bench.load_json("benchmarks", "configs", f"{cell['config']}.json")
    if args.rehearse:
        config = {**config, **config["rehearse"]}
    runner = bench.load_module("runners", config["runner"])
    spans = bench.Spans()
    state = runner.setup(config, args.seed, spans)
    built = getattr(state, "built", None)
    if built is None:
        print(f"{args.workload}: the runner keeps no BuiltFit; only GAME cells are joined",
              file=sys.stderr)
        return 2
    order = list(dict.fromkeys(built.update_sequence))
    report = precompile_coordinates(
        {cid: built.coordinates[cid] for cid in order}, include_score=False)
    texts = {}
    for cid in order:
        (exe,) = [v for k, v in built.coordinates[cid].aot_executables().items() if k[0] == "sweep"]
        texts[cid] = exe.as_text()
    bench.run_steps(runner, state, spans, count=2)

    steps = args.steps or cell["traffic"]["steps_traced"]
    tdir = tempfile.mkdtemp(prefix="scope_join_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            _, window_s = bench.run_steps(runner, state, spans, count=steps)
        finally:
            jax.profiler.stop_trace()
        loaded = trace.load(trace.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if not loaded["devices"]:
        print(f"no device plane in the trace ({dev.platform}): nothing to join", file=sys.stderr)
        return 0 if args.rehearse else 1
    reduced = trace.reduce(loaded)
    step_spans = [(s, e) for name, s, e in loaded["host"] if name == "bench.step"]
    lo, hi = step_spans[0][0], step_spans[-1][1]
    (device,) = list(loaded["devices"].values())[:1]

    # program name -> the coordinates that run it, in the update sequence's order
    by_program: dict[str, list[str]] = {}
    for cid in order:
        name = texts[cid].split(None, 2)[1].rstrip(",")  # "HloModule jit_re_sweep, ..."
        by_program.setdefault(name, []).append(cid)
    executions = sorted((s, e, raw) for raw, s, e in device["modules"] if e > lo and s < hi)
    raw_of: dict[str, list[str]] = {}  # program name -> its raw names, by first run
    for _, _, raw in executions:
        seen = raw_of.setdefault(trace.module_name(raw), [])
        if raw not in seen:
            seen.append(raw)
    owner = {}
    for name, cids in by_program.items():
        raws = raw_of.get(name, [])
        if len(raws) != len(cids):
            print(f"{name}: {len(cids)} coordinates, {len(raws)} executables in the trace",
                  file=sys.stderr)
            return 1
        owner.update(zip(raws, cids))

    per, module_s, other_s = charge(executions, device["ops"], owner)

    out = {"workload": args.workload, "seed": args.seed, "steps": steps,
           "device_kind": dev.device_kind, "window_s": window_s,
           "busy_s": reduced["busy_s"], "other_programs_s_a_step": other_s / steps,
           "precompile_wall_s": report.get("wall_s"), "coordinates": {}}
    for cid in order:
        paths = hlo.instruction_scope_paths(texts[cid])
        instrs = hlo.parse_instructions(texts[cid])
        by_path = hlo.seconds_by_scope(per[cid], paths)
        groups: dict[str, float] = {}
        for name, secs in per[cid].items():
            key = _group(paths.get(name, ()))
            groups[key] = groups.get(key, 0.0) + secs
        top = [
            {"op": name, "opcode": instrs[name].opcode if name in instrs else "?",
             "s_a_step": secs / steps, "scope": "/".join(paths.get(name, ()))}
            for name, secs in trace.top(per[cid], args.top)
        ]
        out["coordinates"][cid] = {
            "program_s_a_step": module_s.get(cid, 0.0) / steps,
            "groups_s_a_step": {k: v / steps for k, v in sorted(groups.items(), key=lambda kv: -kv[1])},
            "paths_s_a_step": {k: v / steps for k, v in sorted(by_path.items(), key=lambda kv: -kv[1])},
            "top_ops": top,
        }
        print(f"== {cid}: {module_s.get(cid, 0.0) / steps:.4f} s a step")
        for k, v in out["coordinates"][cid]["groups_s_a_step"].items():
            print(f"   {v:9.4f}  {k}")
        print("   -- by scope path")
        for k, v in list(out["coordinates"][cid]["paths_s_a_step"].items())[: args.top]:
            print(f"   {v:9.4f}  {k}")
        print("   -- largest operations")
        for row in top:
            print(f"   {row['s_a_step']:9.4f}  {row['op']} ({row['opcode']})  {row['scope']}")
    print(json.dumps({k: v for k, v in out.items() if k != "coordinates"}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
