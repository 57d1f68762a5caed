"""Reduction of a profiler trace to device time, idle share and gaps.

A trace is reduced to plain lists before anything is computed from it,
so the arithmetic below is tested on a small recorded trace
(``tests/data/trace_small.json``) without a chip:

* ``ops``: device operations, ``[label, start_ns, dur_ns]``, from the
  ``XLA Ops`` line of the first TPU plane (``op_label``);
* ``spans``: the harness's own host spans (``jax.profiler.TraceAnnotation``
  around each request's dispatch and its wait), same layout;
* ``window``: ``[start_ns, end_ns]`` of the measured window, one clock
  for all three.
"""
from __future__ import annotations

import glob
import os
import re

#: Host spans the harness writes, by what the host does inside them.
SPANS = ("window", "dispatch", "block", "request")


def op_label(hlo: str) -> str:
    """An XLA op's trace name (its HLO instruction) without layouts,
    operand names and attributes: ``name = shape op(operand shapes)``,
    with `` tpu_custom_call`` appended for a Pallas kernel.  Kernels are
    told apart by their operand shapes (``costs/<k>.py``)."""
    kernel = 'custom_call_target="tpu_custom_call"' in hlo
    text = hlo.lstrip("%")
    for _ in range(3):
        text = re.sub(r"\{[^{}]*\}", "", text)
    text = re.sub(r" %[\w.\-]+", "", text)
    name, eq, rhs = text.partition(" = ")
    call = re.match(r"(\([^()]*\)|\S+) ([\w\-]+)\(", rhs)
    if eq and call:
        depth, rest = 1, rhs[call.end():]
        for end, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        text = f"{name} = {call.group(0)}{rest[:end + 1]}"
    return text + (" tpu_custom_call" if kernel else "")


def load_xplane(trace_dir: str) -> dict:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax._src.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, spans = [], []
    device_seen = False
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and not device_seen:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_seen = True
                    ops += [[op_label(e.name), e.start_ns, e.duration_ns]
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if e.name in SPANS]
    windows = [s for s in spans if s[0] == "window"]
    if not windows:
        raise ValueError("the trace has no 'window' span")
    w = windows[-1]
    return {"ops": ops, "spans": [s for s in spans if s[0] != "window"],
            "window": [w[1], w[1] + w[2]]}


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """``[name, start, dur]`` intervals cut to ``[lo, hi]``, as
    ``(start, end)`` pairs, empty ones dropped."""
    out = []
    for _, s, d in intervals:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(pairs) -> list[tuple[float, float]]:
    """Merge overlapping ``(start, end)`` pairs."""
    merged: list[list[float]] = []
    for a, b in sorted(pairs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: dict) -> float:
    """Length of the union of device-op intervals inside the window."""
    lo, hi = trace["window"]
    return sum(b - a for a, b in union(clip(trace["ops"], lo, hi)))


def window_ns(trace: dict) -> float:
    lo, hi = trace["window"]
    return hi - lo


def idle_share(trace: dict) -> float | None:
    """Share of the window in which no device op ran, in [0, 1]; None
    where no device op was traced."""
    if not trace["ops"]:
        return None
    return 1.0 - busy_ns(trace) / window_ns(trace)


def op_times(trace: dict, match=None) -> dict[str, float]:
    """Device nanoseconds per op name inside the window, for names
    that ``match(name)`` accepts (all when None)."""
    lo, hi = trace["window"]
    out: dict[str, float] = {}
    for name, s, d in trace["ops"]:
        if match is not None and not match(name):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def op_calls(trace: dict, match) -> int:
    """Number of device ops inside the window that ``match`` accepts."""
    lo, hi = trace["window"]
    return sum(1 for name, s, d in trace["ops"]
               if match(name) and s < hi and s + d > lo)


def gaps(trace: dict) -> list[tuple[str, float]]:
    """Idle gaps of the device inside the window, longest first, each
    labelled by the innermost harness span that covers its middle
    (``"none"`` where none does)."""
    lo, hi = trace["window"]
    busy = union(clip(trace["ops"], lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [s for s in trace["spans"] if s[1] <= mid <= s[1] + s[2]]
        label = min(cover, key=lambda s: s[2])[0] if cover else "none"
        out.append((label, b - a))
    return sorted(out, key=lambda g: -g[1])


def breakdown(trace: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run: the device ops that took most
    time and the longest idle gaps, in seconds."""
    ops = sorted(op_times(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps(trace)[:top]]}
