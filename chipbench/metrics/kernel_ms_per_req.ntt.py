"""Device milliseconds per request in the ``ntt`` Pallas kernel."""
from chipbench import kernels, trace


def read(run: dict):
    t = trace.op_times(run["trace"], kernels.matcher("ntt"))
    return sum(t.values()) / 1e6 / run["requests"] if t else None
