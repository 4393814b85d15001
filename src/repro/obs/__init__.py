"""Observability: step-trace telemetry for the serving + RL stack.

Layering (no engine imports here — `obs` depends only on `roofline`):

- `obs.events`   — the typed event schema (JSON-native dataclasses)
- `obs.tracer`   — `NULL_TRACER` default + recording `StepTracer`
- `obs.timeline` — per-request TTFT/TPOT/queue-wait/preemption post-pass
- `obs.export`   — JSONL sink + Chrome trace-event (Perfetto) exporter
- `obs.scopes`   — the names the program gives its work on the profiler's
  clock: device scopes in the compiled HLO, program host spans

The engine owns one tracer (`NULL_TRACER` unless a `StepTracer` is
passed), every instrumentation site costs one branch when disabled, and
everything derived (timelines, percentiles, Chrome traces) is a pure
post-pass over the event list — see `benchmarks/observability.py` for
the zero-perturbation + exact-reconciliation gate.
"""
from repro.obs.events import (  # noqa: F401
    AdmitEvent,
    CowEvent,
    DecodeEvent,
    DraftEvent,
    Event,
    EVENT_KINDS,
    FinishEvent,
    GaugeEvent,
    GrowEvent,
    PrefillEvent,
    StepEvent,
    SubmitEvent,
    SwapOutEvent,
    VerifyEvent,
    WeightsEvent,
    event_from_dict,
)
from repro.obs.export import (  # noqa: F401
    JsonlSink,
    chrome_trace,
    read_events_jsonl,
    write_events_jsonl,
)
from repro.obs.scopes import (  # noqa: F401
    DEVICE_SCOPES,
    PHASES,
    PROGRAM_SPANS,
    scope,
    span,
)
from repro.obs.timeline import (  # noqa: F401
    RequestTimeline,
    build_timelines,
    percentile,
    summarize_timelines,
)
from repro.obs.tracer import NULL_TRACER, NullTracer, StepTracer  # noqa: F401
