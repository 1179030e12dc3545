"""Unified observability layer: metrics + spans + HTTP endpoints.

Three planes, one package:

- :mod:`edl_tpu.obs.metrics` — process-local registry of counters,
  gauges and fixed-bucket histograms (``edl_<component>_<name>_<unit>``
  naming, lint-enforced);
- :mod:`edl_tpu.obs.trace` — ring-buffer span tracer exporting Chrome
  trace-event JSON per process (``EDL_TRACE_DIR``), merged across the
  job by :mod:`edl_tpu.obs.merge`;
- :mod:`edl_tpu.obs.http` — ``/metrics`` (Prometheus text) and
  ``/healthz`` (JSON) served from a daemon thread on every long-lived
  process (``EDL_OBS_PORT``), endpoints registered in the coordination
  store so ``tools/edl_top.py`` discovers every scrape target from the
  store alone;
- :mod:`edl_tpu.obs.events` — the crash-safe flight recorder
  (``EDL_FLIGHT_DIR``): append-only JSONL ring segments, one series per
  process, fsync'd on state transitions, survives SIGKILL;
- :mod:`edl_tpu.obs.goodput` — the per-process goodput ledger
  classifying every second of wall-clock into
  train/compile/data_wait/ckpt_save/ckpt_restore/restage/drain/stalled/
  down (``edl_goodput_seconds_total{state,cause}`` +
  ``edl_goodput_ratio``), merged job-wide by ``tools/edl_timeline.py``;
- :mod:`edl_tpu.obs.monitor` — the monitor plane: scrape-and-retain
  time series (``EDL_MONITOR_DIR`` ring segments), an SLO rule engine
  (threshold / rate / quantile-staleness / absence / restart detection
  with firing->resolved hysteresis), and alert records published to the
  store's ``alerts/{rule}`` keyspace (daemon:
  ``python -m tools.edl_monitord``);
- :mod:`edl_tpu.obs.profile` — the profiling plane: the roofline/peak
  cost model, live windowed-MFU / roofline / HBM gauges per train
  stage, store-driven on-demand ``jax.profiler``
  capture windows publishing ``profile/result/{pod}``, and the
  monitor's alert-triggered auto-capture action (CLI:
  ``python -m tools.edl_profile``);
- :mod:`edl_tpu.obs.archive` — the cross-run plane: every run (chaos
  scenario, bench, harness job) harvested into an indexed bundle under
  ``EDL_RUN_ARCHIVE`` with a manifest, env-knob snapshot, and scalar
  rollups, one crash-safe ``runs/index.jsonl`` line per run;
- :mod:`edl_tpu.obs.regress` — the regression sentinel: a declarative
  per-metric table (direction / tolerance / min-samples) judged against
  a rolling baseline of same-``(kind, backend, world)`` archived runs
  (CLI: ``python -m tools.edl_report`` — list/trend/diff/check).
"""

from edl_tpu.obs.metrics import (
    DURATION_BUCKETS,
    METRIC_NAME_RE,
    SIZE_BUCKETS,
    Counter,
    Gauge,
    GaugeBinding,
    Histogram,
    MetricsRegistry,
    bind_gauges,
    counter,
    default_registry,
    gauge,
    histogram,
    histogram_quantile,
)
from edl_tpu.obs.trace import SpanTracer, get_tracer, span
from edl_tpu.obs.events import FlightRecorder, get_recorder, read_segments
from edl_tpu.obs import goodput
from edl_tpu.obs import monitor
from edl_tpu.obs import profile
from edl_tpu.obs import archive
from edl_tpu.obs import regress
from edl_tpu.obs.http import (
    ObsServer,
    discover_endpoints,
    fetch_healthz,
    fetch_metrics,
    register_endpoint,
    start_from_env,
)

__all__ = [
    "DURATION_BUCKETS",
    "METRIC_NAME_RE",
    "SIZE_BUCKETS",
    "Counter",
    "FlightRecorder",
    "archive",
    "regress",
    "Gauge",
    "GaugeBinding",
    "Histogram",
    "MetricsRegistry",
    "ObsServer",
    "goodput",
    "bind_gauges",
    "SpanTracer",
    "counter",
    "default_registry",
    "discover_endpoints",
    "fetch_healthz",
    "fetch_metrics",
    "gauge",
    "get_recorder",
    "get_tracer",
    "histogram",
    "histogram_quantile",
    "monitor",
    "profile",
    "read_segments",
    "register_endpoint",
    "span",
    "start_from_env",
]
