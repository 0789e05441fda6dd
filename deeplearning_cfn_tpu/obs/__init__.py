"""Cluster observability plane: flight recorder, spans, liveness, profiler.

The control plane (broker, elasticity, recovery, provisioner) and the
data plane (trainer) both feed one bounded JSONL flight journal; the
``dlcfn status`` / ``dlcfn events`` / ``dlcfn trace`` commands and the
Prometheus exporter read it back out.  Nothing in here imports jax at
module scope — the broker and CLI processes must stay light; the one
jax dependency (``train.metrics.json_safe``) is imported lazily at
first record.
"""

from deeplearning_cfn_tpu.obs.recorder import (
    FlightRecorder,
    configure,
    follow_journal,
    get_recorder,
    read_journal,
)
from deeplearning_cfn_tpu.obs.tracing import (
    counter,
    counters,
    recent_drains,
    recent_spans,
    reset_aggregates,
    span,
    span_aggregates,
)
from deeplearning_cfn_tpu.obs.liveness import (
    LivenessConfig,
    LivenessTable,
    WorkerState,
)
from deeplearning_cfn_tpu.obs.heartbeat import Heartbeater
from deeplearning_cfn_tpu.obs.profiler import (
    NULL_PROFILER,
    RollingQuantiles,
    StepProfiler,
    program_attribution,
    program_cost,
)
from deeplearning_cfn_tpu.obs.trace_export import (
    chrome_trace,
    merge_journals,
    straggler_table,
)
from deeplearning_cfn_tpu.obs.aggregator import (
    FleetAggregator,
    agent_snapshot,
    decode_snapshot,
    encode_snapshot,
    fleet_metric_values,
    telemetry_source,
)
from deeplearning_cfn_tpu.obs.slo import DEFAULT_RULES, SloEngine, SloRule
from deeplearning_cfn_tpu.obs.blackbox import (
    BlackBox,
    capture_bundle,
    merge_bundles,
    read_bundle,
    render_timeline,
    write_bundle,
)

__all__ = [
    "FlightRecorder",
    "configure",
    "follow_journal",
    "get_recorder",
    "read_journal",
    "span",
    "span_aggregates",
    "recent_spans",
    "recent_drains",
    "counter",
    "counters",
    "reset_aggregates",
    "LivenessConfig",
    "LivenessTable",
    "WorkerState",
    "Heartbeater",
    "NULL_PROFILER",
    "RollingQuantiles",
    "StepProfiler",
    "program_attribution",
    "program_cost",
    "chrome_trace",
    "merge_journals",
    "straggler_table",
    "FleetAggregator",
    "agent_snapshot",
    "decode_snapshot",
    "encode_snapshot",
    "fleet_metric_values",
    "telemetry_source",
    "DEFAULT_RULES",
    "SloEngine",
    "SloRule",
    "BlackBox",
    "capture_bundle",
    "merge_bundles",
    "read_bundle",
    "render_timeline",
    "write_bundle",
]
