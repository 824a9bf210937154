"""repro.obs — first-class observability for the serving stack
(DESIGN.md §8).

One ``ObsContext`` bundles the three primitives every layer records into:

  * ``registry`` — the metrics registry (counters / gauges / histograms),
    the single source of truth behind ``ServeStats`` and the
    ``serve/scale.py`` policies;
  * ``events``   — the bounded ring-buffer event log;
  * ``tracer``   — race-level trace spans over that log (per-ticket trace
    ids propagated submit → queue → admit → each race epoch → terminal).

``get_obs()`` returns the process-default context (what the launchers
export); tests and embedders can pass their own ``ObsContext`` to
``RequestPlane`` / ``make_session`` for isolation. ``REPRO_OBS=0``
disables event/span recording and the spans' profiler annotations
process-wide (metrics counters stay on — ``ServeStats`` must keep
working); ``REPRO_OBS_EVENTS`` sizes the default ring.
"""
from __future__ import annotations

import os
from typing import Optional

from repro.obs.audit import (DeltaAuditor, FlightRecorder,
                             clopper_pearson_upper, exact_topk,
                             load_bundle, replay_bundle, wilson_upper)
from repro.obs.export import (dump_events, dump_metrics, events_doc,
                              json_snapshot, prometheus_text)
from repro.obs.health import (dump_health, health_snapshot,
                              print_health)
from repro.obs.jaxmon import compiles_total, install_compile_hook
from repro.obs.registry import (DEFAULT_MS_BUCKETS, Counter, EventLog,
                                Gauge, Histogram, MetricsRegistry)
from repro.obs.slo import (SLO, Alert, AlertSink, BurnRule, SLOEngine,
                           default_slos, plane_sources)
from repro.obs.trace import NULL_SPAN, Span, Tracer, new_trace_id

__all__ = [
    "Alert", "AlertSink", "BurnRule", "Counter", "DEFAULT_MS_BUCKETS",
    "DeltaAuditor", "EventLog", "FlightRecorder", "Gauge", "Histogram",
    "MetricsRegistry", "NULL_SPAN", "ObsContext", "SLO", "SLOEngine",
    "Span", "Tracer", "clopper_pearson_upper", "compiles_total",
    "default_slos", "dump_events", "dump_health", "dump_metrics",
    "events_doc", "exact_topk", "get_obs", "health_snapshot",
    "install_compile_hook", "json_snapshot", "load_bundle",
    "new_trace_id", "plane_sources", "print_health", "prometheus_text",
    "replay_bundle", "reset_obs", "set_obs", "wilson_upper",
]


class ObsContext:
    """One observability namespace: registry + event log + tracer."""

    def __init__(self, name: str = "default", *,
                 event_capacity: int = 16384,
                 enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get("REPRO_OBS", "1") != "0"
        self.name = name
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.events = EventLog(event_capacity)
        self.tracer = Tracer(self.events, enabled=enabled)
        # ring overflow must be visible, not silent (DESIGN.md §10): every
        # overwrite counts into the registry, and the FIRST one warns so a
        # truncated trace never masquerades as a complete one
        self._drops_counter = self.registry.counter(
            "repro_obs_event_drops_total",
            "trace events overwritten before export (ring overflow)",
            ring=name)
        self._drop_warned = False
        self.events.on_drop = self._on_event_drop

    def _on_event_drop(self, ring) -> None:
        self._drops_counter.inc()
        if not self._drop_warned:
            self._drop_warned = True
            from repro.utils import get_logger
            get_logger("repro.obs").bind(ring=self.name).warning(
                "trace event ring overflowed (capacity %d): oldest events "
                "are being dropped — raise REPRO_OBS_EVENTS or export "
                "more often", ring.capacity)


_default: Optional[ObsContext] = None


def get_obs() -> ObsContext:
    """The process-default context (created lazily; honours ``REPRO_OBS``)."""
    global _default
    if _default is None:
        cap = int(os.environ.get("REPRO_OBS_EVENTS", "16384"))
        _default = ObsContext("default", event_capacity=cap)
    return _default


def set_obs(ctx: ObsContext) -> ObsContext:
    """Install ``ctx`` as the process default; returns the previous one."""
    global _default
    old = get_obs()
    _default = ctx
    return old


def reset_obs() -> ObsContext:
    """Fresh default context (test isolation)."""
    global _default
    _default = None
    return get_obs()


# jax compile-time telemetry (repro_xla_compiles_total) rides on the
# process-wide jax.monitoring listener; the hook resolves get_obs() per
# event, so it composes with set_obs()-swapped contexts. Best-effort: a
# jax build without the monitoring API simply leaves the counter at 0.
install_compile_hook()
