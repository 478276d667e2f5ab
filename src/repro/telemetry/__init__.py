"""``repro.telemetry`` — zero-cost-when-disabled tracing + metrics for
the whole stack (kernel plans, serve lifecycles, train steps)."""

from repro.telemetry.telemetry import (  # noqa: F401
    SCHEMA_VERSION,
    TRACK_TID_BASE,
    Counter,
    Gauge,
    Recorder,
    Span,
    complete_span,
    counter,
    disable,
    enable,
    enabled,
    event,
    export,
    gauge,
    recorder,
    snapshot,
    span,
)
