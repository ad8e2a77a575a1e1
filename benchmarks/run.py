"""One run of one cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds ``benchmarks/workloads/<cell>.json``, the configuration's file named
there, the runner module named in that, and one reader module per metric
that ``BENCHMARK.json`` lists for the cell. Builds the data from the seeds,
drives the first steps through the window's own call (they warm every
program up and are what ``correct`` compares), measures for ``--seconds``,
frees the program's state, runs the plain reference, and prints the
contract's one JSON line last. Fails without a TPU; ``--rehearse`` runs the
configuration's ``rehearse`` shapes on whatever JAX finds and prints no
device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # before anything heavy is imported

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: steps driven in set-up through the window's own call: the warm-up, and
#: the steps the reference follows
FIRST_STEPS = 3
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Spans:
    """The benchmark's own host spans: (name, start, end) on perf_counter,
    mirrored into the profiler's trace as ``bench.<name>`` while it runs."""

    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(e - s for n, s, e in self.rows if n == name)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell: str, section: str) -> list[dict]:
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


def follow_steps(config: dict) -> int:
    """How many of the first steps the reference's own path is followed for."""
    return config.get("follow_steps", FIRST_STEPS)


def against_reference(runner, config: dict, inputs: dict, observed: list, ref: dict | None = None):
    """(the numbers ``lib/check.py`` compares, the reference's record) for the
    first steps ``observed``, of the program or of what stands in its place."""
    from benchmarks.lib import check

    follow = follow_steps(config)
    if ref is None:
        ref = runner.reference_record(config, inputs, follow)
    at_x = [runner.reference_at(config, inputs, rec["x"], gradient=i == 0)
            for i, rec in enumerate(check.points(observed, follow))]
    rule = runner.stopping_rule(config)
    return check.compare(observed, ref, inputs["w0"], at_x, follow, rule), ref


def stand_in(runner, config: dict, inputs: dict, **changed) -> list:
    """The reference's record in the program's place, as many first steps as
    the path is followed for: ``precision="bf16"`` is the control,
    ``weights=...`` a planted fault."""
    follow = follow_steps(config)
    precision = changed.pop("precision", None)
    rec = runner.reference_record(config, {**inputs, **changed}, follow, precision=precision)
    return [{**rec, "fresh": True}] * follow


def run_steps(runner, state, spans, *, seconds=None, count=None, observe=False):
    """Steps until ``seconds`` have passed (the step in flight then ends the
    window) or ``count`` steps are done. Each is closed by its read-back."""
    steps = []
    t_open = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with spans.span("step"):
            out = runner.step(state)
        t1 = time.perf_counter()
        if observe:
            runner.observe(state, out)
        steps.append({"wall_s": t1 - t0, "units": out["units"], "passes": out["passes"],
                      "ok": bool(out.get("ok", True))})
        if count is not None and len(steps) >= count:
            break
        if seconds is not None and t1 - t_open >= seconds:
            break
    return steps, time.perf_counter() - t_open


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on any backend; prints no device metric")
    ap.add_argument("--control", action="store_true",
                    help="run the lower-precision control in the program's place")
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = load_json("benchmarks", "workloads", f"{args.workload}.json")
    config = load_json("benchmarks", "configs", f"{cell['config']}.json")
    if args.rehearse:
        config = {**config, **config["rehearse"]}

    import jax

    from benchmarks.lib import check, peaks, trace
    from photon_tpu.util import compile_watch
    from photon_tpu.util.compile_cache import enable_persistent_cache

    dev = jax.devices()[0]
    if not args.rehearse and (dev.platform != "tpu" or jax.device_count() < cell["chips"]):
        print(f"needs {cell['chips']} TPU chip(s); JAX found {jax.device_count()} "
              f"{dev.platform} device(s)", file=sys.stderr)
        return 2
    enable_persistent_cache()
    compile_watch.install()
    runner = load_module("runners", config["runner"])
    spans = Spans()

    # ---- set-up: data, placement, programs, the first steps ------------
    c0 = compile_watch.snapshot()
    state = runner.setup(config, args.seed, spans,
                         control=args.control and config["control"] == "program_bf16")
    first, _ = run_steps(runner, state, spans, count=FIRST_STEPS, observe=True)
    compiled_setup = compile_watch.delta(c0)
    setup_s = time.perf_counter() - T_START

    # ---- the window ----------------------------------------------------
    c1 = compile_watch.snapshot()
    traced = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        try:
            steps, window_s = run_steps(runner, state, spans, count=cell["traffic"]["steps_traced"])
        finally:
            jax.profiler.stop_trace()
        traced = trace.reduce(trace.load(trace.find_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        steps, window_s = run_steps(runner, state, spans, seconds=args.seconds)
    compiled_window = compile_watch.delta(c1)

    stats = dev.memory_stats() or {}
    run = {
        "cell": cell, "config": config, "device_kind": dev.device_kind,
        "rows": config["features"]["n"], "block": state.block, "programs": state.programs,
        "spans": spans, "setup_s": setup_s, "steps": steps, "window_s": window_s,
        "compile": {"setup": compiled_setup, "window": compiled_window},
        "trace": traced, "peaks": None if args.rehearse else peaks.peaks_for(dev.device_kind),
    }

    # ---- correct: the first steps against the plain reference ----------
    inputs, observed = state.inputs, state.first
    runner.release(state)
    del state
    t_ref = time.perf_counter()
    if args.control and config["control"] == "reference_bf16":
        # the program has no lower-precision path of its own here: the
        # reference, computed in bfloat16, stands in the program's place
        observed = stand_in(runner, config, inputs, precision="bf16")
    numbers, ref = against_reference(runner, config, inputs, observed)
    ref_s = time.perf_counter() - t_ref
    verdict = check.judge(numbers, config["limits"])
    all_steps = first + steps
    failed = sum(1 for s in all_steps if not s["ok"])
    correct = bool(all(v["ok"] for v in verdict.values()) and failed == 0)

    # ---- the result ----------------------------------------------------
    section, kind = ("per_layer", "metrics") if args.trace else ("end_to_end", "end_to_end")
    metrics = {}
    for m in metrics_for(bench, args.workload, section):
        value = load_module(kind, m["name"]).read(run)
        if value is not None and not args.rehearse:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count(),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": correct, "attempted": len(all_steps), "failed": failed,
              "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"], device["window_s"] = traced["busy_s"], traced["window_s"]
        result["breakdown"] = {"device_ops": trace.top(traced["op_s"]),
                               "idle_gaps": trace.top(traced["gap_s"])}
    result["reference_s"] = ref_s
    result["check"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in verdict.items()}
    names = sorted({n for n, _, _ in spans.rows if n != "step"})
    print("spans " + " ".join(f"{n}={spans.total(n):.3f}s" for n in names)
          + f" setup={setup_s:.3f}s window={window_s:.3f}s steps={len(steps)}"
          + f" units={sum(x['units'] for x in steps)} passes={sum(x['passes'] for x in steps)}"
          + f" compile={compiled_setup['backend_compile_s']:.3f}s/{compiled_setup['backend_compiles']}"
          + f" reference={ref_s:.3f}s", file=sys.stderr)
    print("history program " + " ".join(f"{v:.9g}" for v in observed[follow_steps(config) - 1]["loss"][:ref["iterations"] + 1])
          + " | reference " + " ".join(f"{v:.9g}" for v in ref["loss"]), file=sys.stderr)
    for k, v in verdict.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r} {'ok' if v['ok'] else 'FAILED'}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
