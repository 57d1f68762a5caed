"""Which device ops are which Pallas kernel, and what each must move.

The kernels carry no name in the trace: every Pallas call is a
``tpu_custom_call`` op (``trace.op_label``).  Each kernel has a file
``costs/<kernel>.py`` that recognises its calls by their operand shapes
(``is_call``) and gives the least HBM bytes of one call
(``min_bytes``).  Device ops that are no kernel's call are the XLA glue
around the kernels.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
COSTS = HERE / "costs"
_SHAPE = re.compile(r"\b([a-z]+)(\d+)\[([\d,]*)\]")


def shapes(text: str) -> list[tuple[int, tuple[int, ...]]]:
    """``(bytes per element, dims)`` of every array shape in ``text``."""
    return [(max(int(bits), 8) // 8,
             tuple(int(d) for d in dims.split(",") if d))
            for _, bits, dims in _SHAPE.findall(text)]


def nbytes(shape) -> int:
    item, dims = shape
    return item * math.prod(dims)


@functools.cache
def signature(label: str):
    """``(result shapes, operand shapes)`` of a Pallas call's label,
    None for any other op."""
    if not label.endswith(" tpu_custom_call"):
        return None
    rhs = label.partition(" = ")[2]
    result, _, operands = rhs.partition(" custom-call(")
    return shapes(result), shapes(operands)


@functools.cache
def cost_module(kernel: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_cost_{kernel}", COSTS / f"{kernel}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernels() -> list[str]:
    return sorted(p.stem for p in COSTS.glob("*.py"))


def matcher(kernel: str):
    """``match(label)`` for the calls of ``kernel``."""
    mod = cost_module(kernel)

    def match(label: str) -> bool:
        sig = signature(label)
        return sig is not None and mod.is_call(*sig)
    return match


def is_glue(label: str) -> bool:
    """An op that is no call of a kernel in ``costs/``."""
    return not any(matcher(k)(label) for k in kernels())


def hbm_bytes_per_s(device_kind: str) -> float:
    """The device's HBM bandwidth from ``peaks.json``; an unknown
    device is an error."""
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json")
    return peaks[device_kind]["hbm_bytes_per_s"]


def roofline(trace: dict, kernel: str, device_kind: str) -> float | None:
    """Per cent of the HBM roofline the kernel's calls in the window
    reach: their least bytes over the peak bandwidth, over their device
    time.  None where the window has no call of the kernel."""
    from chipbench import trace as tr

    match = matcher(kernel)
    times = tr.op_times(trace, match)
    if not times:
        return None
    lo, hi = trace["window"]
    mod = cost_module(kernel)
    moved = sum(mod.min_bytes(*signature(n)) for n, s, d in trace["ops"]
                if match(n) and s >= lo and s + d <= hi)
    seconds = sum(times.values()) / 1e9
    return 100.0 * moved / hbm_bytes_per_s(device_kind) / seconds
