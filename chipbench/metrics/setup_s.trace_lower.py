"""Self seconds of set-up in JAX's tracing and lowering to MLIR, Pallas
to Mosaic included, from the program's set-up counters
(``repro.obs.SETUP``)."""
from chipbench import program_trace


def read(run: dict):
    setup = program_trace.setup_for_run(run)
    return None if setup is None else setup["trace_lower_s"]
