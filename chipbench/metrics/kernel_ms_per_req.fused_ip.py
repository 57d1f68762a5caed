"""Device milliseconds per request in the ``fused_ip`` Pallas kernel."""
from chipbench import kernels, trace


def read(run: dict):
    t = trace.op_times(run["trace"], kernels.matcher("fused_ip"))
    return sum(t.values()) / 1e6 / run["requests"] if t else None
