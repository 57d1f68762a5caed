"""Device milliseconds per request of ops under the keyswitch phase
scope ``ks.modup`` (``core/keyswitch.py``), Pallas kernels included."""
from chipbench import program_trace


def read(run: dict):
    return program_trace.scope_ms_per_req(run, "ks.modup")
