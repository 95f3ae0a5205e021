"""repro.obs — observability for the serve/train stack.

Three pillars, one facade:

* ``trace``    — span tracer exporting Chrome trace-event JSON (Perfetto):
                 host-loop phases, trainer rounds, admission cache fills,
                 per-request async tracks; per-host ``pid`` tagging so a
                 pod run merges into one timeline.  Every span is also a
                 ``jax.profiler.TraceAnnotation``, so a profile holds the
                 host phases on the device ops' clock.
* ``registry`` — typed counters/gauges/histograms with labels, snapshotted
                 to JSON-lines at window boundaries (live metrics for
                 long-lived engines).
* ``timeline`` — per-request lifecycle records (queued → scored →
                 admitted → first tick → retired-at-cut → client-finished)
                 with wall timestamps and exact finish ticks recovered
                 from the engine's ``(k, slots)`` done stack.

Usage — hand an :class:`ObsConfig` to the engine (or trainer)::

    cfg = EngineConfig(..., obs=ObsConfig(trace_path="trace.json",
                                          metrics_path="metrics.jsonl"))
    res = ServeEngine(cfg, params).serve(requests)
    res.timelines[req_id]       # the lifecycle record

Everything is opt-in and zero-cost when off: ``obs=None`` (the default)
resolves to :data:`NULL_OBS`, whose tracer/registry/timeline answer every
call with cached no-op singletons — no allocation, no clock reads, no
branches beyond one attribute hop.  The ``benchmarks.run --only
obs_overhead`` gate holds obs-off bitwise identical to the pre-obs engine
and obs-on within 5% ticks/sec at 256 in-flight requests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.obs.registry import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                                MetricsRegistry, NULL_REGISTRY, NullRegistry,
                                read_jsonl)
from repro.obs.timeline import (NULL_TIMELINES, STAGES, NullTimelines,
                                TimelineRecorder)
from repro.obs.trace import (NULL_TRACER, NullTracer, Tracer, load_trace,
                             merge_traces, validate_events)

__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_OBS", "NULL_REGISTRY", "NULL_TIMELINES", "NULL_TRACER",
    "NullRegistry", "NullTimelines", "NullTracer", "ObsConfig",
    "Observability", "STAGES", "TimelineRecorder", "Tracer", "load_trace",
    "merge_traces", "read_jsonl", "resolve_obs", "validate_events",
]


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Declarative observability knobs (frozen, like EngineConfig).

    ``trace``          span tracing on/off (forced on by ``trace_path``).
    ``trace_path``     export the Chrome trace JSON here after each
                       ``serve()``; pod hosts should interpolate their
                       host id (the engine appends ``.host<i>`` when
                       ``hosts > 1`` and the path has no placeholder).
    ``metrics_path``   append one registry snapshot line per
                       ``metrics_every`` window boundaries (JSON-lines).
    ``metrics_every``  snapshot cadence in windows.
    ``timelines``      record per-request lifecycle events.

    Spans also annotate any ``jax.profiler`` capture the caller has
    running around the call (``with jax.profiler.trace(dir): ...``).
    """

    trace: bool = True
    trace_path: Optional[str] = None
    metrics_path: Optional[str] = None
    metrics_every: int = 1
    timelines: bool = True

    def __post_init__(self):
        assert self.metrics_every >= 1, self.metrics_every


class Observability:
    """The bundle a subsystem threads: ``.tracer``, ``.registry``,
    ``.timelines``, plus the request-lifecycle helper shared by the engine
    and the metrics sink."""

    enabled = True

    def __init__(self, config: Optional[ObsConfig] = None, *,
                 host_id: int = 0):
        self.config = config if config is not None else ObsConfig()
        self.host_id = int(host_id)
        trace_on = self.config.trace or self.config.trace_path is not None
        self.tracer = Tracer(pid=self.host_id) if trace_on else NULL_TRACER
        self.registry = MetricsRegistry()
        self.timelines = (TimelineRecorder(tracer=self.tracer)
                          if self.config.timelines else NULL_TIMELINES)

    def __bool__(self) -> bool:
        return True

    # ------------------------------------------------------------------
    def request(self, req_id: int, stage: str,
                tick: Optional[int] = None, **detail) -> None:
        """Record one lifecycle stage (timeline + async trace event)."""
        self.timelines.record(req_id, stage, tick=tick, **detail)

    def trace_path_for_host(self, hosts: int = 1) -> Optional[str]:
        """The per-host trace export path (pod runs must not clobber each
        other's files; events stay pid-tagged for a later merge)."""
        p = self.config.trace_path
        if p is None or hosts <= 1:
            return p
        return f"{p}.host{self.host_id}"


class _NullObs:
    """Disabled facade: one shared instance, all pillars no-op."""

    enabled = False
    config = None
    host_id = 0
    tracer = NULL_TRACER
    registry = NULL_REGISTRY
    timelines = NULL_TIMELINES

    def __bool__(self) -> bool:
        return False

    def request(self, req_id, stage, tick=None, **detail) -> None:
        pass

    def trace_path_for_host(self, hosts: int = 1) -> Optional[str]:
        return None


NULL_OBS = _NullObs()


def resolve_obs(spec, *, host_id: int = 0):
    """None -> NULL_OBS; ObsConfig -> fresh Observability; an
    Observability instance passes through (shared by engine + trainer)."""
    if spec is None:
        return NULL_OBS
    if isinstance(spec, (Observability, _NullObs)):
        return spec
    if isinstance(spec, ObsConfig):
        return Observability(spec, host_id=host_id)
    raise TypeError(f"obs must be None, ObsConfig or Observability; "
                    f"got {type(spec).__name__}")
