"""Self seconds of set-up in XLA's backend compiles and loads from the
persistent compilation cache, from the program's set-up counters
(``repro.obs.SETUP``)."""
from chipbench import program_trace


def read(run: dict):
    setup = program_trace.setup_for_run(run)
    return None if setup is None else setup["compile_s"]
