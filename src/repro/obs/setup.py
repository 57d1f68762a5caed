"""Set-up counters: where a process's seconds go before its first request.

``SetupCounters`` listens to ``jax.monitoring`` and keeps one interval
per event, on the host's ``time.time`` clock:

* ``trace_lower``: ``jaxpr_trace_duration`` and
  ``jaxpr_to_mlir_module_duration`` — Python tracing of a jitted
  function and its lowering to MLIR, Pallas-to-Mosaic lowering included;
* ``compile``: ``backend_compile_duration`` and
  ``cache_retrieval_time_sec`` — XLA compiles and loads from the
  persistent compilation cache;
* ``keygen``: what the program times itself with :meth:`timed` (key
  generation in ``core/keys.py``).

Events nest (a jit traced inside another's trace, a cache load inside a
backend compile, eager compiles inside key generation), so each second
is counted once, for the innermost interval that covers it: the
categories' self seconds never add up to more than the wall time they
cover.  ``cache_misses`` counts persistent-cache writes, one per
compile the cache did not hold, and ``cache_hits`` the loads from it.
Listeners cannot be removed, so the process-global instance
(``repro.obs.SETUP``) registers once, on import, and keeps everything
it hears; :meth:`read` sums a stretch of time, such as a set-up that
ended before a traced window.
"""
from __future__ import annotations

import contextlib
import heapq
import math
import time

#: ``jax.monitoring`` time-span events, by the category they count for.
SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
CATEGORIES = ("trace_lower", "compile", "keygen")


def self_seconds(intervals) -> dict[str, float]:
    """Seconds of each category's ``(start, end, category)`` intervals
    in which it is the innermost: the latest-started interval that
    covers the instant (the shorter one on a tie)."""
    out = {c: 0.0 for c in CATEGORIES}
    edges = sorted({x for a, b, _ in intervals for x in (a, b)})
    starts = sorted(intervals)
    live: list[tuple[float, float, str]] = []     # (-start, end, category)
    i = 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(starts) and starts[i][0] <= lo:
            a, b, cat = starts[i]
            heapq.heappush(live, (-a, b, cat))
            i += 1
        # the top started last; ended ones below it surface later
        while live and live[0][1] <= lo:
            heapq.heappop(live)
        if live:
            out[live[0][2]] += hi - lo
    return out


class SetupCounters:
    """Intervals of tracing, compiling and key generation, and the times
    of persistent-cache misses and hits, on ``clock``."""

    def __init__(self, clock=time.time):
        self.clock = clock
        self.intervals: list[tuple[float, float, str]] = []
        self.misses: list[float] = []
        self.hits: list[float] = []

    def register(self) -> None:
        """Listen to ``jax.monitoring`` (once per instance)."""
        from jax import monitoring

        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        cat = SPAN_EVENTS.get(event)
        if cat is not None:
            self.intervals.append((start, end, cat))

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == RETRIEVAL_EVENT:      # reported as it ends
            end = self.clock()
            self.intervals.append((end - duration, end, "compile"))

    def _on_event(self, event: str, **_) -> None:
        if event == MISS_EVENT:
            self.misses.append(self.clock())
        elif event == HIT_EVENT:
            self.hits.append(self.clock())

    @contextlib.contextmanager
    def timed(self, category: str):
        """An interval of ``category`` around the ``with`` body."""
        start = self.clock()
        try:
            yield
        finally:
            self.intervals.append((start, self.clock(), category))

    def read(self, since: float = -math.inf,
             until: float = math.inf) -> dict:
        """Self seconds per category between ``since`` and ``until``
        (intervals clipped to them) and the cache misses and hits in
        that stretch, as ``{"trace_lower_s", "compile_s", "keygen_s",
        "cache_misses", "cache_hits"}``."""
        clipped = [(max(a, since), min(b, until), cat)
                   for a, b, cat in self.intervals if a < until and b > since]
        secs = self_seconds(clipped)
        return {"trace_lower_s": secs["trace_lower"],
                "compile_s": secs["compile"],
                "keygen_s": secs["keygen"],
                "cache_misses": sum(since <= t <= until
                                    for t in self.misses),
                "cache_hits": sum(since <= t <= until for t in self.hits)}
