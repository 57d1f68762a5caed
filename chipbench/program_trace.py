"""What the program marks in a traced run, read beside the harness's own
reduction (``trace.py``), for the readers of ``metrics/``.

* **Scopes.**  Each jitted keyswitch plan is named ``ks_<kind>_L<level>``
  and traces its phases under ``jax.named_scope`` (``ks.modup``,
  ``ks.ip``, ``ks.moddown``, ``ks.automorph``), so a device op's name
  path, as the profiler records it, says which phase it belongs to.
  The profiler keeps that path in the ``tf_op`` stat of the op's event
  *metadata*, which ``ProfileData`` does not expose, so ``device_ops``
  reads the device plane of the ``xplane.pb`` itself.
* **Spans.**  While ``repro.obs`` is enabled its spans (``exec.run``,
  ``exec.batch``, ``exec.step.<kind>``, ``keys.gen_evk``) are profiler
  annotations on the host plane, on the device's clock.
* **Set-up counters.**  ``repro.obs.SETUP`` times tracing, compiling
  and key generation in the process, on ``time.time``.

``load`` reduces the newest ``*.xplane.pb`` under a trace directory to
``{"ops": [[scope, start_ns, dur_ns], ...], "spans": [[name, start_ns,
dur_ns, batch_index], ...]}``, on the clock of ``trace.load_xplane``'s
``window``; ``ops`` holds only the device ops that carry a scope.  A
program without scopes or spans leaves the lists empty and the readers
return None.  ``for_run`` and ``setup_for_run`` load what a run's readers
share once and keep it in the run's dict, where a test puts its own.
"""
from __future__ import annotations

import glob
import os
import pathlib

#: Where ``run.py`` writes the traced window's profile.
TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".chipbench" \
    / "trace"
#: Name prefixes of the program's spans.
SPAN_PREFIXES = ("exec.", "keys.")


def xplane_path(trace_dir) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def op_scope(path: str) -> str:
    """The program's scopes in an op's name path: the segments that
    start with ``ks_`` (a plan) or ``ks.`` (a phase), unwrapped from
    ``jit(...)``/``vmap(...)``, joined by ``/``:
    ``jit(ks_hoisted_L35)/vmap(ks.automorph)/gather`` gives
    ``ks_hoisted_L35/ks.automorph``."""
    out = []
    for seg in path.split("/"):
        while "(" in seg and seg.endswith(")"):
            seg = seg[seg.index("(") + 1:-1]
        if seg.startswith(("ks_", "ks.")):
            out.append(seg)
    return "/".join(out)


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """``(field number, value)`` of one protobuf message in
    ``buf[i:end]``: an int for a varint or fixed-width field, a
    ``(start, end)`` pair for a length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + n], "little")
            i += n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _plane_ops(buf: bytes, lo: int, hi: int) -> list | None:
    """``[[path, start_ns, dur_ns], ...]`` of the ``XLA Ops`` line of one
    ``XPlane`` (``tsl/profiler/protobuf/xplane.proto``); None where the
    plane has no such line.  An op's name path is its event metadata's
    ``tf_op`` stat, which ``ProfileData`` does not expose."""
    line_events, metadata, stat_names = None, {}, {}
    for field, value in _fields(buf, lo, hi):
        if field == 3:                                  # lines
            name, ts, events = "", 0, []
            for f, v in _fields(buf, *value):
                if f == 2:
                    name = _text(buf, v)
                elif f == 3:
                    ts = v
                elif f == 4:
                    events.append(v)
            if name == "XLA Ops":
                line_events = (ts, events)
        elif field in (4, 5):                           # metadata maps
            key, entry = None, None
            for f, v in _fields(buf, *value):
                if f == 1:
                    key = v
                elif f == 2:
                    entry = v
            if entry is None:
                continue
            if field == 5:
                stat_names[key] = next(
                    (_text(buf, v) for f, v in _fields(buf, *entry)
                     if f == 2), "")
            else:
                metadata[key] = entry
    if line_events is None:
        return None
    paths: dict[int, str] = {}

    def path_of(mid: int) -> str:
        if mid not in paths:
            paths[mid] = ""
            for f, v in _fields(buf, *metadata.get(mid, (0, 0))):
                if f != 5:                              # stats
                    continue
                sid, val = None, ""
                for g, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 5:
                        val = _text(buf, w)
                    elif g == 7:                        # interned
                        val = stat_names.get(w, "")
                if stat_names.get(sid) == "tf_op":
                    paths[mid] = val
        return paths[mid]

    ts, events = line_events
    ops = []
    for ev in events:
        mid = offset = dur = 0
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                offset = v
            elif f == 3:
                dur = v
        ops.append([path_of(mid), ts + offset // 1000, dur // 1000])
    return ops


def device_ops(path: str) -> list:
    """The name path, start and duration (ns) of every op on the
    ``XLA Ops`` line of the first TPU plane in the ``xplane.pb`` at
    ``path`` that has one, on the clock ``ProfileData`` gives it."""
    buf = pathlib.Path(path).read_bytes()
    for field, (lo, hi) in _fields(buf, 0, len(buf)):
        if field != 1:                                  # XSpace.planes
            continue
        name = next((_text(buf, v) for f, v in _fields(buf, lo, hi)
                     if f == 2), "")
        if name.startswith("/device:TPU:"):
            ops = _plane_ops(buf, lo, hi)
            if ops is not None:
                return ops
    return []


def load(trace_dir) -> dict:
    """The scoped device ops and the program's spans of the newest
    profile under ``trace_dir`` (the planes ``trace.load_xplane``
    reads)."""
    from jax._src.profiler import ProfileData

    path = xplane_path(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    ops = []
    for p, start, dur in device_ops(path):
        scope = op_scope(p)
        if scope:
            ops.append([scope, start, dur])
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, e.start_ns, e.duration_ns,
                                      dict(e.stats).get("batch_index")])
    return {"ops": ops, "spans": spans}


def for_run(run: dict) -> dict:
    """The program's marks in the run's traced window: ``run["program"]``,
    loaded from ``TRACE_DIR`` by the first reader that asks."""
    if "program" not in run:
        run["program"] = load(TRACE_DIR)
    return run["program"]


def scope_ns(window, program: dict, scope: str) -> float | None:
    """Device nanoseconds inside ``window`` of ops whose innermost scope
    is ``scope`` (``ks.modup``); None where no op carries a scope."""
    if not program["ops"]:
        return None
    lo, hi = window
    return float(sum(max(0, min(s + d, hi) - max(s, lo))
                     for path, s, d in program["ops"]
                     if path.rpartition("/")[2] == scope))


def scope_ms_per_req(run: dict, scope: str) -> float | None:
    ns = scope_ns(run["trace"]["window"], for_run(run), scope)
    return None if ns is None else ns / 1e6 / run["requests"]


def idle_in_spans_ns(trace: dict, program: dict,
                     prefix: str) -> float | None:
    """Device-idle nanoseconds inside ``trace``'s window that fall
    inside the union of the program's spans named ``prefix...``
    (``exec.step.``: the device waiting on the host's dispatch of a
    step); None where there is no such span or no device op."""
    from chipbench import trace as tr

    lo, hi = trace["window"]
    spans = [s[:3] for s in program["spans"] if s[0].startswith(prefix)]
    if not spans or not trace["ops"]:
        return None
    busy = tr.union(tr.clip(trace["ops"], lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    cover = tr.union(tr.clip(spans, lo, hi))
    return float(sum(max(0, min(b, d) - max(a, c))
                     for a, b in idle for c, d in cover))


def setup_for_run(run: dict) -> dict | None:
    """The program's set-up counters (``repro.obs.SETUP``) up to the
    moment the traced window's profile was written: ``run["setup"]``,
    read by the first reader that asks; None for a program without
    them.  The window compiles nothing (``run.py`` logs its compiles),
    so this is set-up's share."""
    if "setup" not in run:
        from repro import obs

        counters = getattr(obs, "SETUP", None)
        path = xplane_path(TRACE_DIR)
        run["setup"] = (None if counters is None or path is None
                        else counters.read(until=os.path.getmtime(path)))
    return run["setup"]
