"""Unified telemetry subsystem.

  * :mod:`~tpu_compressed_dp.obs.registry` — typed metric registry: every
    stat key the system emits, declared once with kind/unit/cross-worker
    reduction; the conformance test fails on undeclared keys.
  * :mod:`~tpu_compressed_dp.obs.trace` — phase-level step tracing:
    ``jax.named_scope`` phase annotations through both sync engines, the
    sharded wire path and all three step factories, plus the host-side
    :class:`~tpu_compressed_dp.obs.trace.StepTimeline` ring buffer (the
    loop's spans and a completion stamp per step: p50/p95/p99 of the
    completion intervals, device-starved fraction, step rate).
  * :mod:`~tpu_compressed_dp.obs.export` — schema-versioned JSONL event
    stream, Prometheus textfile exporter, and the heartbeat telemetry
    snapshot consumed by ``tools/watchdog.py --check``.
  * :mod:`~tpu_compressed_dp.obs.flight` — per-rank flight recorder:
    bounded ring buffers over every telemetry stream, atomic
    ``blackbox.rank<R>.json`` dumps on the failure paths, and the live
    cross-rank ``straggler/*`` gauges; ``tools/postmortem.py`` merges the
    bundles offline into a root-cause verdict.
"""

from tpu_compressed_dp.obs import export, flight, registry, trace
from tpu_compressed_dp.obs.export import (EventStream, SCHEMA_VERSION,
                                          read_events, telemetry_snapshot,
                                          write_prometheus)
from tpu_compressed_dp.obs.flight import (FLIGHT_SCHEMA, FlightRecorder,
                                          classify_failure, read_bundles,
                                          straggler_gauges, validate_bundle)
from tpu_compressed_dp.obs.registry import MetricSpec
from tpu_compressed_dp.obs.trace import PHASES, StepTimeline, phase

__all__ = [
    "registry", "trace", "export", "flight",
    "MetricSpec", "PHASES", "StepTimeline", "phase",
    "EventStream", "SCHEMA_VERSION", "read_events", "telemetry_snapshot",
    "write_prometheus",
    "FLIGHT_SCHEMA", "FlightRecorder", "classify_failure", "read_bundles",
    "straggler_gauges", "validate_bundle",
]
