"""Device milliseconds per request in the ``bconv`` Pallas kernel."""
from chipbench import kernels, trace


def read(run: dict):
    t = trace.op_times(run["trace"], kernels.matcher("bconv"))
    return sum(t.values()) / 1e6 / run["requests"] if t else None
