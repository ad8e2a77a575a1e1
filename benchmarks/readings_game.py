"""``readings.py`` for a GAME cell: the numbers ``lib/check.py`` compares,
over many seeds in ONE process, for the program against the reference (the
lower readings) and, in the program's place, for the bfloat16 control and
the planted faults of ``lib/reference_game.py`` (the upper ones): what the
configuration's ``limits`` are set from. ``readings.py`` itself reads the
program and the control of such a cell; what it cannot plant are faults by
name, so this file drives the first steps through its ``first_steps`` and
adds them.

    python3 benchmarks/readings_game.py --workload glmix_ctr.sweeps \
        --seeds 1,2,3 --control-seeds 1 --fault-seeds 1

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

from benchmarks import readings  # noqa: E402
from benchmarks import run as harness  # noqa: E402

FAULTS = ("idle_single", "stale_last", "steepest_fixed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
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
        inputs, observed = readings.first_steps(runner, config, seed, control=False)
        numbers, ref = harness.against_reference(runner, config, inputs, observed)
        row = {"seed": seed, "program": numbers,
               "history": [list(map(float, observed[-1]["loss"])), list(map(float, ref["loss"]))]}
        # a stand-in leaves its own fixed effects for the reference that
        # judges it, so each gets a reference record of its own
        if seed in controls:
            low = harness.stand_in(runner, config, inputs, precision="bf16")
            row["control"], _ = harness.against_reference(runner, config, inputs, low)
        if seed in faults:
            for fault in FAULTS:
                broken = harness.stand_in(runner, config, inputs, fault=fault)
                row[fault], _ = harness.against_reference(runner, config, inputs, broken)
        row["host_maxrss_gb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(json.dumps(row), flush=True)
        del inputs, observed, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
