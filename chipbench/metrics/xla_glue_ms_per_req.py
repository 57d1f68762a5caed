"""Device milliseconds per request of ops that are no Pallas kernel:
the uint64 casts, emulated 64-bit arithmetic, gathers and copies around
the kernels."""
from chipbench import kernels, trace


def read(run: dict):
    t = trace.op_times(run["trace"], kernels.is_glue)
    return sum(t.values()) / 1e6 / run["requests"] if t else None
