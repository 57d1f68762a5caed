"""Device milliseconds per request in the ``modup`` Pallas kernel."""
from chipbench import kernels, trace


def read(run: dict):
    t = trace.op_times(run["trace"], kernels.matcher("modup"))
    return sum(t.values()) / 1e6 / run["requests"] if t else None
