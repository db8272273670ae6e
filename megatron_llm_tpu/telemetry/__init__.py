"""Flight-recorder telemetry (ISSUE 13): structured tracing, Prometheus
metrics, and postmortem recording across the train and serve hot paths.

Three pieces, one contract:

- `trace.SpanTracer` — nestable host-side spans with request-id /
  train-step correlation: always a `jax.profiler` annotation (the
  span lands in any profiler capture, on the device's clock), and with
  a --trace_dir also Chrome trace-event JSON (Perfetto);
- `recorder.FlightRecorder` — a bounded ring of structured events +
  counter snapshots, auto-dumped to a JSON artifact on engine poison,
  watchdog rollback and SIGTERM emergency save;
- `prometheus.Histogram` / `render_prometheus` — real histogram metrics
  (TTFT, decode-round ms, queue wait, step ms) behind the
  content-negotiated Prometheus text exposition on GET /metrics.

ISSUE 15 adds the device-cost layer on top:

- `chipspec.ChipSpec` / `detect_chip` — the TPU generation spec table
  (per-chip peak FLOP/s, HBM bytes/s) the runtime gauges read;
- `costs.CostRegistry` — compiled-cost capture (cost_analysis FLOPs /
  bytes + memory_analysis temp/args) at jit-mint time, keyed by
  compile-contract name + specialization;
- `goodput.GoodputLedger` — the trainer's exclusive wall-time
  partition (productive / compile / checkpoint / data_wait / watchdog
  / idle, provably summing to wall);
- `sentinel.PerfSentinel` — the loss watchdog's median+MAD machinery
  pointed at step/round latency, auto-dumping the flight ring on a
  sustained regression.

The contract that keeps this subsystem honest: ALL emission stays
outside jitted code. Telemetry-on steps are bitwise-identical to
telemetry-off — pinned by tests/test_telemetry.py AND by the
graft-check audit (telemetry-on engine / train.step specializations
lower to the same collective inventory with zero host callbacks), and
the emit methods sit on graft-check GR006 HOT_PATHS so a device sync
can never creep into per-round bookkeeping.
"""

from megatron_llm_tpu.telemetry.chipspec import ChipSpec, detect_chip
from megatron_llm_tpu.telemetry.costs import CostRecord, CostRegistry
from megatron_llm_tpu.telemetry.goodput import (
    GOODPUT_BUCKETS,
    GoodputLedger,
)
from megatron_llm_tpu.telemetry.prometheus import (
    DEFAULT_LATENCY_BUCKETS_MS,
    PROMETHEUS_CONTENT_TYPE,
    Histogram,
    histograms_from_prometheus,
    parse_prometheus,
    render_prometheus,
)
from megatron_llm_tpu.telemetry.recorder import FlightRecorder
from megatron_llm_tpu.telemetry.sentinel import PerfSentinel, RobustWindow
from megatron_llm_tpu.telemetry.trace import SpanTracer

__all__ = [
    "SpanTracer",
    "FlightRecorder",
    "Histogram",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "PROMETHEUS_CONTENT_TYPE",
    "render_prometheus",
    "parse_prometheus",
    "histograms_from_prometheus",
    "ChipSpec",
    "detect_chip",
    "CostRecord",
    "CostRegistry",
    "GoodputLedger",
    "GOODPUT_BUCKETS",
    "PerfSentinel",
    "RobustWindow",
]
