"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines and writes JSON results to
benchmarks/results/ (consumed by EXPERIMENTS.md).

Usage: python -m benchmarks.run [table4|fig14|...|all]
                                [--smoke] [--seed N] [--chaos]
                                [--quiet] [--trace] [--list]

--smoke restricts every module to its cheapest workload (CI fast path).
--seed  sets the shared base seed (``benchmarks.common.SEED``) that the
        measured benches derive plaintexts, tenant keys, and arrival
        traces from; analytic figure modules are seed-free.
--chaos runs the serving bench under its seeded fault-injection
        schedule and gates on recovery (accounting, goodput, victims,
        retraces); the chaos report lands under the ``"chaos"`` key of
        BENCH_serving.json next to the fault-free run's numbers.
--quiet gates out info-level ``benchmarks.common.log`` progress lines
        (warn/error still print; CSV results are unaffected).
--trace makes the bootstrap and serving benches run one obs-traced
        pass and write Perfetto traces (benchmarks/results/
        trace_bootstrap.json / trace_serving.json, CI artifacts).
--list  prints the available module names with a one-line description
        and exits.
"""
from __future__ import annotations

import pathlib
import sys
import time


def main() -> None:
    from repro import compile_cache

    compile_cache.enable(pathlib.Path(__file__).resolve().parent.parent)
    from benchmarks import (
        bench_bootstrap, bench_keyswitch, bench_pallas, bench_runtime,
        bench_serving, bench_workloads, common, fig6_parallelism,
        fig7_bsgs, fig14_ablation, fig15_hero, fig16_util,
        fig17_sensitivity, table1_ai, table4_end2end,
    )

    modules = {
        "table1": table1_ai,
        "table4": table4_end2end,
        "keyswitch": bench_keyswitch,
        "pallas": bench_pallas,
        "runtime": bench_runtime,
        "bootstrap": bench_bootstrap,
        "workloads": bench_workloads,
        "serving": bench_serving,
        "fig6": fig6_parallelism,
        "fig7": fig7_bsgs,
        "fig14": fig14_ablation,
        "fig15": fig15_hero,
        "fig16": fig16_util,
        "fig17": fig17_sensitivity,
    }
    argv = sys.argv[1:]
    if "--list" in argv:
        for name, mod in modules.items():
            doc = (mod.__doc__ or "").strip().splitlines()
            print(f"{name:<12} {doc[0] if doc else ''}")
        return
    common.SMOKE = "--smoke" in argv
    common.CHAOS = "--chaos" in argv
    common.QUIET = "--quiet" in argv
    common.TRACE = "--trace" in argv
    args, it = [], iter(argv)
    for a in it:
        if a == "--seed":
            common.SEED = int(next(it))
        elif not a.startswith("--"):
            args.append(a)
    which = args[0] if args else "all"
    selected = modules if which == "all" else {which: modules[which]}
    print("name,us_per_call,derived")
    for name, mod in selected.items():
        t0 = time.time()
        for line in mod.run():
            print(line)
        dt = time.time() - t0
        print(f"{name}/_total,{dt*1e6:.0f},ok")


if __name__ == "__main__":
    main()
