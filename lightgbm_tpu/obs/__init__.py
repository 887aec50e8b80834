"""Unified telemetry: metrics registry + span tracer + resource
accounting + flight recorder.

One import surface for every instrumented layer::

    from ..obs import REGISTRY, span, timed, metrics_on, tracing_on
    from ..obs import flightrecorder, resources

* `REGISTRY` — process-global `MetricsRegistry` (counters, gauges,
  fixed-bucket histograms; Prometheus text export).
* `span(name, **tags)` — nested structured span (Chrome-trace/Perfetto
  export, JSONL stream, jax TraceAnnotation mirror); null when
  ``tpu_telemetry`` != trace.  Every span and event carries an ``id``
  and its ``parent_id``; `origin_ns()` is the clock origin of ``ts``.
* `timed(name)` — registry-backed stopwatch (the bench's segment timer).
* `configure` / `configure_from_config` — process-global policy from
  ``tpu_telemetry`` (off | metrics | trace), ``tpu_trace_dir`` and the
  ``tpu_obs_*`` params (histogram sample ring, flight-recorder depth
  and blackbox dump dir).
* `resources` — device HBM gauges, phase-tagged peak watermarks,
  process runtime stats (ISSUE 12).
* `flightrecorder` — the ALWAYS-ON bounded ring of recent spans/
  transitions dumped to ``blackbox-host<k>.json`` on crash/hang/
  SIGTERM (ISSUE 12).
* `modelhealth` — training reference profiles
  (``tpu_feature_profile:`` trailer) + the serving drift monitor:
  PSI / Jensen-Shannon over the binned representation (ISSUE 14).

See `obs.metrics`, `obs.trace`, `obs.resources` and
`obs.flightrecorder` for the full contracts.
"""

from . import flightrecorder, modelhealth, resources  # noqa: F401
from .metrics import (DEFAULT_SECONDS_BUCKETS, MetricsRegistry,  # noqa: F401
                      REGISTRY, histogram_quantile)
from .trace import (chrome_trace, configure, configure_from_config,  # noqa: F401
                    event, events, flush, metrics_on, mode, origin_ns,
                    reset_events, span, span_ended, timed, trace_dir,
                    tracing_on, write_chrome_trace)
