"""Phase timers — the TIMETAG subsystem analog, backed by the registry.

The reference accumulates per-phase wall time behind a compile-time flag
(reference src/treelearner/serial_tree_learner.cpp:21-48 init/hist/
find-split/split buckets, gpu_tree_learner.cpp:352-532 transfer timing,
linkers.h:169 network_time_).  Here every `PHASE` block feeds the
unified telemetry layer (`lightgbm_tpu.obs`):

* phase walls accumulate into the process-global registry as
  ``lgbm_phase_seconds_total{phase=...}`` / ``lgbm_phase_runs_total``
  whenever telemetry (`tpu_telemetry=metrics|trace`) is on or a probe
  called `enable()` — `summary()` reads the registry, so bench and the
  Prometheus export see the SAME numbers;
* under ``tpu_telemetry=trace`` each block is additionally a structured
  span (Chrome-trace/Perfetto export + xprof mirror via obs.span).

When everything is off a PHASE block costs one flag check.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

from ..obs import REGISTRY, span
from ..obs import metrics_on as _obs_metrics_on
from ..obs import resources as _resources

_enabled = False

_SECONDS = "lgbm_phase_seconds_total"
_RUNS = "lgbm_phase_runs_total"


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = on


def _record(name: str, seconds: float) -> None:
    REGISTRY.inc(_SECONDS, seconds,
                 help="accumulated wall seconds per lifecycle phase",
                 phase=name)
    REGISTRY.inc(_RUNS, 1, phase=name)


# phases whose wall bracket doubles as a device-memory watermark
# bracket (obs/resources.py phase_peak): the binning phase IS the
# ingest HBM phase — the chunked device matrix and key planes live
# inside it
_MEM_PHASE = {"binning": "ingest"}


@contextlib.contextmanager
def PHASE(name: str) -> Iterator[None]:
    """Accumulate wall time under `name` (no-op unless enabled); a span
    under tpu_telemetry=trace; a device-memory watermark bracket for
    the phases in `_MEM_PHASE`."""
    if not (_enabled or _obs_metrics_on()):
        yield
        return
    sp = span(name)
    mem_phase = _MEM_PHASE.get(name)
    mem = (_resources.phase_peak(mem_phase) if mem_phase
           else contextlib.nullcontext())
    t0 = time.perf_counter()
    try:
        with mem, sp:
            yield
    finally:
        _record(name, time.perf_counter() - t0)


def summary() -> Dict[str, float]:
    return {p: REGISTRY.value(_SECONDS, phase=p)
            for p in REGISTRY.label_values(_SECONDS, "phase")}


def reset() -> None:
    """Zero the phase accumulation (bench reuses the process).  The
    registry holds phases beside unrelated metric families, so only the
    phase families reset."""
    REGISTRY.clear_family(_SECONDS)
    REGISTRY.clear_family(_RUNS)
