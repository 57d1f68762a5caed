"""Self seconds of set-up in the program's key generation
(``KeyChain._gen_evk``), from the program's set-up counters
(``repro.obs.SETUP``)."""
from chipbench import program_trace


def read(run: dict):
    setup = program_trace.setup_for_run(run)
    return None if setup is None else setup["keygen_s"]
