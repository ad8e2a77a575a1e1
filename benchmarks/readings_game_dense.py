"""``readings_game.py`` for the ratings-shaped GAME cell
(``glmix_movielens.sweeps``): the numbers ``lib/check.py`` compares, over
many seeds in ONE process, for the program against the reference (the lower
readings) and, in the program's place, for the bfloat16 control and this
deployment's planted faults (``lib/reference_game_dense.py``; the upper
ones): what the configuration's ``limits`` are set from.

    python3 benchmarks/readings_game_dense.py --workload glmix_movielens.sweeps \
        --seeds 1,2,3 --control-seeds 1 --fault-seeds 1

needs the chip at the cell's own size (``--rehearse`` reads the tiny shapes
anywhere). The stand-ins are host NumPy alone, so

    python3 benchmarks/readings_game_dense.py --workload glmix_movielens.sweeps \
        --host-only --seeds 1,2

reads the control and the faults against the sound reference with no
program and no chip: every entity trains on its first ``cap`` rows (which
rows a capped entity keeps is the program's draw; any draw is a reading).
One JSON line per seed on standard output.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks import readings_game  # noqa: E402
from benchmarks import run as harness  # noqa: E402

FAULTS = ("idle_capped", "stale_last", "steepest_fixed")


def host_inputs(config: dict, seed: int) -> dict:
    """The reference's inputs from the generator alone."""
    from benchmarks.lib import datagen_movielens

    struct = datagen_movielens.structure(config)
    vals = datagen_movielens.values(config, struct, seed)
    inputs = {"fe_x": vals["fe_x"], "labels": vals["labels"], "_shared": {}}
    size = config["features"]["d"]
    for name, re in config["random_effects"].items():
        ids = struct[name]
        order = np.argsort(ids, kind="stable")
        starts = np.concatenate(([0], np.cumsum(np.bincount(ids, minlength=re["entities"]))))
        rank = np.empty(len(ids), np.int64)
        rank[order] = np.arange(len(ids)) - starts[ids[order]]
        inputs[name + ".ids"] = ids
        inputs[name + ".features"] = vals[name]
        inputs[name + ".active"] = rank < re["cap"]
        size += re["entities"] * re["d"]
    inputs["w0"] = np.zeros(size, np.float32)
    return inputs


def host_only(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--host-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_json("benchmarks", "workloads", f"{args.workload}.json")
    config = harness.load_json("benchmarks", "configs", f"{cell['config']}.json")
    if args.rehearse:
        config = {**config, **config["rehearse"]}
    runner = harness.load_module("runners", config["runner"])
    for seed in (int(s) for s in args.seeds.split(",")):
        inputs = host_inputs(config, seed)
        row = {"seed": seed}
        low = harness.stand_in(runner, config, inputs, precision="bf16")
        row["control"], _ = harness.against_reference(runner, config, inputs, low)
        for fault in FAULTS:
            broken = harness.stand_in(runner, config, inputs, fault=fault)
            row[fault], _ = harness.against_reference(runner, config, inputs, broken)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    readings_game.FAULTS = FAULTS  # what ``readings_game.main`` plants by name
    sys.exit(host_only(argv) if "--host-only" in argv else readings_game.main(argv))
