"""Compiles of set-up that JAX's persistent compilation cache did not
hold, from the program's set-up counters (``repro.obs.SETUP``)."""
from chipbench import program_trace


def read(run: dict):
    setup = program_trace.setup_for_run(run)
    return None if setup is None else setup["cache_misses"]
