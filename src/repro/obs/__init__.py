# Unified observability (docs/API.md §Observability): structured spans/
# events on one JSONL schema, each span also a profiler annotation; the
# solve loop's named scopes and their op map (``repro.obs.scopes``); and
# per-iteration convergence telemetry.  Only the zero-dependency trace
# surface is imported eagerly (span() must stay near-free when the sink is
# off); the telemetry helpers import jax and live in
# ``repro.obs.convergence``.
from repro.obs.trace import (SCHEMA, Tracer, active, current, disable,
                             emit, enable, event, make_event, make_metric,
                             read_trace, span, summarize, validate_record,
                             validate_stream)

__all__ = [
    "SCHEMA",
    "Tracer",
    "active",
    "current",
    "disable",
    "emit",
    "enable",
    "event",
    "make_event",
    "make_metric",
    "read_trace",
    "span",
    "summarize",
    "validate_record",
    "validate_stream",
]
