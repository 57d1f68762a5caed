#!/usr/bin/env python3
"""The control's readings, which the upper end of each limit of
``checks.py`` is set from.

    python chipbench/control.py --workload helr-paper.serial --seeds 201-203

The control is the cell's program with the configuration's ``control``
overrides applied: for CKKS a lower scale, the precision a later change
could be tempted to give up.  For each seed: set-up as a run of the cell
makes it (keys, program, request pool), then ``keep`` batches of the
cell's traffic, then the checks, in this one process so that compiles
are shared.  Prints one line per seed and the smallest reading last.
The program's own readings are the ``max_err`` of the benchmark's runs;
the benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import run


def seeds(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def reading(cell: run.Cell, seed: int) -> float:
    from chipbench import checks

    setup = run.Setup(cell, seed)
    traffic = cell.traffic
    _, _, kept = run.window(setup, float("inf"), traffic["keep"],
                            traffic["keep"])
    return checks.run_checks(setup, kept, traffic)["checks"]["max_err"][
        "value"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.Cell(json.loads((run.ROOT / "BENCHMARK.json").read_text()),
                    args.workload)
    try:
        run.device_info(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    run.use_compile_cache()
    control = copy.copy(cell)
    control.cfg = copy.deepcopy(cell.cfg)
    for key, value in cell.cfg["control"].items():
        if isinstance(value, dict):
            control.cfg[key].update(value)
        else:
            control.cfg[key] = value
    got = []
    for s in args.seeds:
        t = time.perf_counter()
        got.append(reading(control, s))
        print(json.dumps({"seed": s, "max_err": got[-1],
                          "s": time.perf_counter() - t}), flush=True)
    print(json.dumps({"upper": min(got), "control": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
