"""Unified observability for the HE^2 repro: spans, metrics, Perfetto.

One process-global tracer and metrics registry, opt-in.
Instrumented modules (``runtime``, ``core``, ``serve``) call the
module-level helpers here; when disabled each call is a branch and a
no-op return, adds zero jit retraces, and costs <2% of end-to-end
runtime (gated in ``benchmarks/bench_bootstrap.py``).

While enabled, spans also reach a running ``jax.profiler`` trace as
annotations on the host plane.  ``SETUP`` counts set-up's tracing,
compiling and key generation at all times (:mod:`repro.obs.setup`).

Typical use::

    from repro import obs

    obs.enable()
    ... run workload ...
    obs.export.write_trace("trace.json", tracer=obs.TRACER,
                           timelines=sim_result.timelines)
    print(obs.METRICS.to_text())
"""

from . import budget, export, registry, setup, tracer  # noqa: F401
from .budget import PAPER_STALL_BUDGET, StallBudget, analyze  # noqa: F401
from .registry import (  # noqa: F401
    MetricsRegistry,
    publish_counters,
    publish_energy,
    publish_serving,
)
from .setup import SetupCounters  # noqa: F401
from .tracer import NULL_SPAN, Span, Tracer  # noqa: F401

#: Process-global tracer; disabled until :func:`enable` is called.
TRACER = Tracer()

#: Process-global metrics registry.
METRICS = MetricsRegistry()

#: Process-global set-up counters, listening to ``jax.monitoring`` from
#: this import on, whether or not tracing is enabled.
SETUP = SetupCounters()
SETUP.register()


def enabled() -> bool:
    return TRACER.enabled


def enable() -> None:
    """Turn on span collection (idempotent)."""
    TRACER.enable()


def disable() -> None:
    TRACER.disable()


def reset() -> None:
    """Drop collected spans and metrics; keeps the enabled flag."""
    TRACER.reset()
    METRICS.reset()


def span(name: str, **attrs):
    """Open a span on the global tracer (``NULL_SPAN`` when disabled)."""
    return TRACER.span(name, **attrs)


def event(name: str, **attrs) -> None:
    """Record a point event on the global tracer's current span."""
    TRACER.event(name, **attrs)


def metrics() -> MetricsRegistry:
    return METRICS
