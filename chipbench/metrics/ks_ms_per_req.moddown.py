"""Device milliseconds per request of ops under the keyswitch phase
scope ``ks.moddown`` (``core/keyswitch.py``)."""
from chipbench import program_trace


def read(run: dict):
    return program_trace.scope_ms_per_req(run, "ks.moddown")
