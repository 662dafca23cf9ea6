"""paddle_tpu.observability — unified runtime telemetry (ISSUE 2) and
the serving operations plane (ISSUE 10).

Pillars, shared by serving and training:

  * `metrics` — process-wide registry of counters/gauges/histograms
    with labels; Prometheus-text and JSON snapshot exporters; near-zero
    cost when disabled.
  * `tracing` — span API: every span is a `jax.profiler`
    annotation (`pt:<name>`, on the device's clock under a profiler
    session) and, when telemetry is on, a JSONL event with monotonic
    timestamps (bounded/rotating sink); plus the per-request trace
    assembler (queue-wait / admission / prefill / decode / detokenize
    phases, TTFT, per-token latency).
  * `exporter` — stdlib http.server daemon thread serving /metrics
    (Prometheus text), /statusz (live JSON engine state), /healthz
    (ok | degraded | stalled); started via
    `PagedGenerationServer(expose_port=...)` / `FrontDoor` or
    PADDLE_TPU_METRICS_PORT.
  * `compile_tracker` — exact XLA compile detection at the decode jit
    boundaries (`serving_xla_compiles_total{program,in_flight,shard}`),
    always on, with a window API the benchmark's cells use to prove
    measurement windows compile-clean.
  * `gc_tracker` — the collector's twin of `compile_tracker`
    (ISSUE 34): a `gc.callbacks` hook installed with the first engine
    or DataLoader, a running total of collection seconds that a
    serving round and a loader wait read at their two ends, and a
    `pt:gc` span a collection.
  * `flight_recorder` — bounded ring buffer of structured engine
    events + the stall watchdog that auto-dumps it (no-op when
    disabled, like all telemetry).
  * `log` — the library logger (PADDLE_TPU_LOG_LEVEL verbosity);
    library code uses this instead of bare print()
    (scripts/check_no_print.py enforces it).
  * `trace_context` — fleet-wide causal tracing (ISSUE 14): a
    `TraceContext` (trace_id + hop + cause) minted at submit and
    carried through retries, failover, and migration; the causal
    assembler stitches one request's whole fleet lifetime into a
    single span tree whose phases tile wall-clock exactly.
  * `slo` — declarative `SLO(objective, target, window)` specs over
    TTFT/ITL/availability/goodput with sliding-window reservoirs and
    multi-window fast/slow burn-rate states (ok | warn | page),
    exported as `slo_*` gauges and the `/slo` ops endpoint.
  * `timeline` — Chrome/Perfetto trace-event JSON export of the span
    sink + flight-recorder rings, per-replica-per-track
    (`FleetRouter.export_timeline`,
    `PagedGenerationServer.export_timeline`).
  * `attribution` — ISSUE 17: per-tenant / per-request cost ledgers
    with exact integer conservation (device-seconds, KV
    block-seconds, host byte-seconds, wire bytes, compile time,
    prefix savings); `serving_tenant_*` metrics,
    `stats()["attribution"]`, `CostReport.to_json()` billing export.
  * `capacity` — ISSUE 17: the deterministic `PressureSignals` bus —
    pool headroom + reclaim trend + exhaustion-ETA forecast, tier
    occupancy, queue depths, shed/exhaustion pressure and SLO burns
    in one versioned snapshot (`/capacity` endpoint, federated by
    the fleet router; the ROADMAP-3 Autoscaler input contract).

One switch turns metrics and the tracing event log on:
PADDLE_TPU_TELEMETRY=1 in the environment, or `observability.enable()`
at runtime.
"""
from __future__ import annotations

from . import attribution, capacity  # noqa: F401
from . import compile_tracker, exporter, flight_recorder  # noqa: F401
from . import gc_tracker  # noqa: F401
from . import log, metrics, slo, timeline, trace_context  # noqa: F401
from . import tracing  # noqa: F401
from .attribution import (CostReport, ResourceLedger,  # noqa: F401
                          apportion, disabled_attribution_stats)
from .capacity import (PressureSignals,  # noqa: F401
                       federate_capacity)
from .exporter import OpsEndpoint  # noqa: F401
from .flight_recorder import FlightRecorder, StallWatchdog  # noqa: F401
from .log import get_logger  # noqa: F401
from .metrics import (REGISTRY, counter, gauge, histogram,  # noqa: F401
                      snapshot, to_prometheus)
from .slo import SLO, SLOEngine, default_slos  # noqa: F401
from .timeline import write_chrome_trace  # noqa: F401
from .trace_context import (TraceContext,  # noqa: F401
                            assemble_causal_traces)
from .tracing import (TRACER, assemble_request_traces,  # noqa: F401
                      span, summarize_traces)


def enable():
    """Turn on metrics collection AND tracing."""
    metrics.enable()
    tracing.enable()


def disable():
    metrics.disable()
    tracing.disable()


def enabled():
    return metrics.enabled() or tracing.enabled()
