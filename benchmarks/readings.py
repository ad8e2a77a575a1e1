"""The readings a configuration's ``limits`` are set from: over many seeds
in ONE process (set-up is most of a run), the numbers ``lib/check.py``
compares, for the program against the reference (the lower readings) and
for the lower-precision control in the program's place (the upper ones).

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

One JSON line per seed on standard output. Needs the chip at the cell's own
size; ``--rehearse`` reads the tiny shapes anywhere.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as harness  # noqa: E402


def first_steps(runner, config, seed, control):
    state = runner.setup(config, seed, harness.Spans(), control=control)
    harness.run_steps(runner, state, harness.Spans(), count=harness.FIRST_STEPS, observe=True)
    inputs, observed = state.inputs, state.first
    runner.release(state)
    return inputs, observed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="seeds on which the reference with half of the batch left out "
                         "is read in the program's place")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_json("benchmarks", "workloads", f"{args.workload}.json")
    config = harness.load_json("benchmarks", "configs", f"{cell['config']}.json")
    if args.rehearse:
        config = {**config, **config["rehearse"]}
    from photon_tpu.util.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    runner = harness.load_module("runners", config["runner"])
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in sorted({int(s) for s in args.seeds.split(",")} | controls | faults):
        inputs, observed = first_steps(runner, config, seed, control=False)
        numbers, ref = harness.against_reference(runner, config, inputs, observed)
        row = {"seed": seed, "program": numbers,
               "iterations": [observed[-1]["iterations"], ref["iterations"]],
               "steps": [[rec["iterations"], rec["reason"], rec["fresh"]] for rec in observed]}
        if seed in controls:
            if config["control"] == "program_bf16":
                del inputs
                inputs, low = first_steps(runner, config, seed, control=True)
            else:
                low = harness.stand_in(runner, config, inputs, precision="bf16")
            row["control"], _ = harness.against_reference(runner, config, inputs, low, ref)
        if seed in faults:
            import numpy as np

            half = np.ones(config["features"]["n"])
            half[len(half) // 2:] = 0.0
            broken = harness.stand_in(runner, config, inputs, weights=half)
            row["half_batch"], _ = harness.against_reference(runner, config, inputs, broken, ref)
        row["history"] = [list(map(float, observed[-1]["loss"][:observed[-1]["iterations"] + 1])),
                          list(map(float, ref["loss"]))]
        row["host_maxrss_gb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(json.dumps(row), flush=True)
        del inputs, observed, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
