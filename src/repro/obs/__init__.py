"""Observability layer: flight recorder, phase telemetry, health matrix.

Three jit-compatible instruments threaded through the stack, all off by
default with the disabled paths bit-identical to the uninstrumented code:

- ``repro.obs.trace``    per-trial protocol event rings (``run_protocol(trace=)``)
- ``repro.obs.phase``    timing spans + compiled-memory watermarks (contextvar
                         recorder picked up by ``sweep``/``bringup``/benchmarks);
                         its spans always reach a ``jax.profiler`` trace as
                         host annotations, and ``stage`` names the engine's
                         stages on the device ops (``arb.<stage>`` op names)
- ``repro.obs.health``   per-step x per-link chaos health codes
                         (``run_fabric_timeline(health=True)``)
- ``repro.obs.taxonomy`` post-hoc failure classifier over traces
- ``repro.obs.manifest`` JSONL run-manifest writer
- ``repro.obs.report``   terminal report CLI (``python -m repro.obs.report``)

``trace``/``phase``/``health`` are dependency-light and re-exported eagerly;
``taxonomy``/``manifest``/``report`` load lazily (taxonomy pulls in
``repro.core``, which itself imports this package — keep the cycle cold).
"""
from __future__ import annotations

from repro.obs.health import HEALTH_CODES, health_codes, health_matrix_summary
from repro.obs.phase import (
    STAGES,
    PhaseRecorder,
    Span,
    current_recorder,
    measured_call,
    note,
    span,
    stage,
    use_recorder,
)
from repro.obs.trace import (
    EVENT_FIELDS,
    EVENT_KINDS,
    TraceBuffer,
    format_events,
    merge_traces,
    trace_append,
    trace_buffer,
    trace_events,
    trace_summary,
)

_LAZY = {
    "classify_trials": "repro.obs.taxonomy",
    "explain_residuals": "repro.obs.taxonomy",
    "TAXONOMY": "repro.obs.taxonomy",
    "RunManifest": "repro.obs.manifest",
    "latest_manifest": "repro.obs.manifest",
    "read_manifest": "repro.obs.manifest",
    "render_report": "repro.obs.report",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)


__all__ = [
    "EVENT_FIELDS",
    "EVENT_KINDS",
    "HEALTH_CODES",
    "STAGES",
    "PhaseRecorder",
    "Span",
    "TraceBuffer",
    "current_recorder",
    "format_events",
    "health_codes",
    "health_matrix_summary",
    "measured_call",
    "merge_traces",
    "note",
    "span",
    "stage",
    "trace_append",
    "trace_buffer",
    "trace_events",
    "trace_summary",
    "use_recorder",
    *sorted(_LAZY),
]
