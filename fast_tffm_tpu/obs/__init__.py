"""Observability layer: telemetry, heartbeat, tracing, and the live
observability plane (status endpoint + alert watchdog).

``obs.Telemetry`` is the shared instrument registry (counters, gauges,
ring-buffer timings) every pipeline stage writes into; ``obs.NULL`` is
the always-safe disabled registry; ``obs.trace_span`` names host phases
in xprof traces and ``obs.Phase`` pairs one with a timer;
``obs.Heartbeat``/``obs.JsonlWriter`` turn a running
train into a self-reporting JSONL stream; ``obs.Tracer`` /
``obs.NULL_TRACER`` record Chrome-trace (Perfetto-loadable) spans from
every stage, correlated per batch/super-batch (trace.py), with windowed
rotation for multi-hour runs; ``obs.StatusServer`` serves ``/metrics``
(Prometheus) + ``/status`` (heartbeat JSON) live from a running
process (status.py); ``obs.AlertEngine`` evaluates declarative alert
rules against the heartbeat stream (alerts.py); ``obs.CompileSentinel``
/ ``obs.read_rss`` are the resource plane (resource.py) — component
memory ledgers, process RSS, and train-step compile accounting.  See
telemetry.py for the shared design constraints (thread-safety,
near-zero hot-path overhead, no jax or numpy imports).
"""

from fast_tffm_tpu.obs.alerts import (
    AlertEngine, AlertHaltError, AlertRule, halt_error,
    parse_rules, run_until_halt,
)
from fast_tffm_tpu.obs.blackbox import NULL_BLACKBOX, Blackbox
from fast_tffm_tpu.obs.fleet import (
    MergeSpec, TrainFleet, labeled_lines, merge_blocks,
)
from fast_tffm_tpu.obs.heartbeat import (
    Heartbeat, JsonlWriter, rank_suffix_path,
)
from fast_tffm_tpu.obs.quality import (
    QualityMonitor, ServeSkewMonitor, StreamSketch,
)
from fast_tffm_tpu.obs.resource import (
    CompileSentinel, basic_block, read_open_fds, read_rss,
)
from fast_tffm_tpu.obs.sketch import FreqSketch, QuantileSketch, SketchSet
from fast_tffm_tpu.obs.status import StatusServer, render_prometheus
from fast_tffm_tpu.obs.telemetry import (
    NULL, Counter, DepthHist, Gauge, Phase, Telemetry, Timing, trace_span,
)
from fast_tffm_tpu.obs.trace import NULL_TRACER, Tracer

__all__ = [
    "Counter", "Gauge", "Timing", "DepthHist", "Telemetry", "NULL",
    "Phase", "trace_span", "Heartbeat", "JsonlWriter", "rank_suffix_path",
    "Tracer", "NULL_TRACER",
    "MergeSpec", "TrainFleet", "labeled_lines", "merge_blocks",
    "StatusServer", "render_prometheus",
    "AlertEngine", "AlertHaltError", "AlertRule", "halt_error",
    "parse_rules", "run_until_halt",
    "CompileSentinel", "read_rss", "read_open_fds", "basic_block",
    "Blackbox", "NULL_BLACKBOX",
    "FreqSketch", "QuantileSketch", "SketchSet",
    "QualityMonitor", "ServeSkewMonitor", "StreamSketch",
]
