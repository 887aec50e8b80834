"""Structured span tracer: nested host-side spans with monotonic walls,
Chrome-trace-event export (Perfetto-loadable), a per-host JSONL event
stream, and mirroring into jax profiler annotations.

Three telemetry modes, process-global (`configure`, wired from the
``tpu_telemetry`` / ``tpu_trace_dir`` params at learner/dataset/serving
init, or the LIGHTGBM_TPU_TELEMETRY / LIGHTGBM_TPU_TRACE_DIR env vars):

* ``off``     — default.  Every instrumentation site degenerates to one
  module-flag check; `span()` returns a shared null context manager
  (no generator, no allocation beyond the kwargs dict) so a
  100-iteration train regresses < 1% vs. the registry not existing at
  all (asserted by tests/test_telemetry.py).
* ``metrics`` — phase walls and counters flow into `obs.metrics.REGISTRY`
  but no spans are buffered.
* ``trace``   — additionally records nested spans (thread-local stack,
  thread/host/iteration tags), streams them as JSONL lines under
  ``tpu_trace_dir`` (``events-host<k>.jsonl``; incremental, so a dead
  run keeps everything up to the death), and mirrors each span into
  ``jax.profiler.TraceAnnotation`` so the SAME names appear inside
  xprof device traces.  `write_chrome_trace()` dumps the buffered spans
  as Chrome trace-event JSON (``trace-host<k>.json``) that loads
  directly in Perfetto; `tools/trace_merge.py` merges the per-host
  JSONL streams of a multihost run into one such file.

Telemetry NEVER touches PRNG streams or device values: model files are
bit-identical with tracing on or off.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from .metrics import REGISTRY

MODES = ("off", "metrics", "trace")

# module-level fast flags: the ONLY thing hot sites read when telemetry
# is off
_METRICS = False
_TRACE = False

_state_lock = threading.Lock()
_mode = "off"
_trace_dir = ""

# span buffer (Chrome export source); bounded so week-long runs cannot
# grow memory — drops are counted, never silent
_EVENT_CAP = 500_000
_events: List[Dict] = []
_events_lock = threading.Lock()
_dropped = 0

_tls = threading.local()

# perf_counter origin: every ts is µs since process telemetry start so
# Chrome/Perfetto timelines start near zero
_T0_NS = time.perf_counter_ns()

# span/event identifiers, process-wide (next() on a count is atomic)
_ids = itertools.count(1)

_stream_lock = threading.Lock()
_stream = None          # open JSONL file handle
_stream_path = ""

_NULL = contextlib.nullcontext()

_ANNOTATION = None      # cached jax.profiler.TraceAnnotation class


def _host_index() -> int:
    # lazy: the fault harness owns host-identity resolution (explicit
    # override > env > initialized jax backend > 0) and must never be
    # import-cycled or force backend init
    from ..utils import faultline

    return faultline.host_index()


def _annotation_cls():
    """jax.profiler.TraceAnnotation when jax is ALREADY imported (the
    tracer must never force a backend/module import), else None."""
    global _ANNOTATION
    if _ANNOTATION is not None:
        return _ANNOTATION
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return None
    try:
        _ANNOTATION = jax_mod.profiler.TraceAnnotation
    except AttributeError:  # pragma: no cover - exotic jax build
        return None
    return _ANNOTATION


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------
def configure(mode: Optional[str] = None,
              trace_dir: Optional[str] = None) -> None:
    """Set the process-global telemetry policy.  ``mode=None`` /
    ``trace_dir=None`` leave the respective setting untouched (the
    no-clobber convention `parallel.collective.configure` uses, so a
    Booster constructed without telemetry params never disarms a policy
    another layer armed)."""
    global _mode, _trace_dir, _METRICS, _TRACE
    with _state_lock:
        if mode is not None:
            m = str(mode).strip().lower()
            if m not in MODES:
                raise ValueError(
                    f"tpu_telemetry must be one of {MODES}, got {mode!r}")
            _mode = m
            _METRICS = m in ("metrics", "trace")
            _TRACE = m == "trace"
        if trace_dir is not None:
            _trace_dir = str(trace_dir)


def configure_from_config(config) -> None:
    """Apply the ``tpu_telemetry`` / ``tpu_trace_dir`` / ``tpu_obs_*``
    params from a Config.  The registry defaults ("" / 0) mean UNSET
    (leave the process policy); an explicit value — including "off" —
    really applies."""
    mode = str(config.tpu_telemetry).strip()
    tdir = str(config.tpu_trace_dir).strip()
    configure(mode=mode or None, trace_dir=tdir or None)
    from . import flightrecorder, metrics

    ring = int(config.tpu_obs_ring_samples)
    if ring > 0:
        metrics.set_sample_ring(ring)
    bb_events = int(config.tpu_obs_blackbox_events)
    bb_dir = str(config.tpu_obs_blackbox_dir).strip()
    flightrecorder.configure(events=bb_events if bb_events > 0 else None,
                             dump_dir=bb_dir or None)


def _env_init() -> None:
    mode = os.environ.get("LIGHTGBM_TPU_TELEMETRY", "").strip()
    tdir = os.environ.get("LIGHTGBM_TPU_TRACE_DIR", "").strip()
    if mode or tdir:
        configure(mode=mode or None, trace_dir=tdir or None)


def mode() -> str:
    return _mode


def trace_dir() -> str:
    return _trace_dir


def metrics_on() -> bool:
    """True under ``metrics`` or ``trace`` — the per-iteration hot-path
    gate for registry writes."""
    return _METRICS


def tracing_on() -> bool:
    return _TRACE


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def origin_ns() -> int:
    """The `time.perf_counter_ns()` reading every ``ts`` counts from: a
    reader puts a span on the host clock as ``origin_ns() / 1e9 + ts /
    1e6`` seconds of `time.perf_counter`."""
    return _T0_NS


def _now_us() -> float:
    return (time.perf_counter_ns() - _T0_NS) / 1e3


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _rec(kind: str, name: str, ph: str, ts: float, dur: float,
         span_id: int, parent_id: Optional[int], tags: Dict) -> Dict:
    return {"kind": kind, "name": name, "ph": ph, "ts": ts, "dur": dur,
            "id": span_id, "parent_id": parent_id,
            "host": _host_index(), "tid": threading.get_ident() % 100000,
            "tags": tags}


def _adopt(tags: Dict) -> Optional[int]:
    """Tag a new span with the name of the span this thread has open
    and its own depth; returns that parent's id (None at the root)."""
    st = _stack()
    if st:
        tags.setdefault("parent", st[-1].name)
    tags.setdefault("depth", len(st))
    return st[-1].id if st else None


class _Span:
    __slots__ = ("name", "tags", "t0", "id", "parent_id", "_ann")

    def __init__(self, name: str, tags: Dict):
        self.name = name
        self.tags = tags
        self.t0 = 0.0
        self.id = 0
        self.parent_id = None
        self._ann = None

    def __enter__(self) -> "_Span":
        self.id = next(_ids)
        self.parent_id = _adopt(self.tags)
        _stack().append(self)
        ann_cls = _annotation_cls()
        if ann_cls is not None:
            try:
                self._ann = ann_cls(self.name)
                self._ann.__enter__()
            except Exception:  # pragma: no cover - profiler unavailable
                self._ann = None
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc) -> None:
        dur = _now_us() - self.t0
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:  # pragma: no cover
                pass
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _record(_rec("span", self.name, "X", self.t0, dur, self.id,
                     self.parent_id, self.tags))


def span(_name: str, **tags):
    """A nested span context manager; the shared null CM when tracing is
    off (no per-call allocation beyond the kwargs dict).  The span-name
    parameter is underscored so tags may themselves be called ``name``."""
    if not _TRACE:
        return _NULL
    return _Span(_name, tags)


def event(_name: str, **fields) -> None:
    """One structured instant event (collective timeout, watchdog
    recovery, guard firing): an ``i``-phase Chrome event plus a JSONL
    line, recorded whenever TRACING is on.  Counters for these events
    live in the registry regardless of mode — this is the narrative
    record, not the count."""
    if not _TRACE:
        return
    st = _stack()
    _record(_rec("event", _name, "i", _now_us(), 0.0, next(_ids),
                 st[-1].id if st else None, fields))


def span_ended(_name: str, seconds: float, **tags) -> None:
    """A span that ends now and lasted `seconds`, for work whose length
    is only reported once it is over (a compile, by `jax.monitoring`).
    A child of whatever span this thread has open; no profiler mirror,
    since its start is already past."""
    if not _TRACE:
        return
    dur = seconds * 1e6
    _record(_rec("span", _name, "X", _now_us() - dur, dur, next(_ids),
                 _adopt(tags), tags))


@contextlib.contextmanager
def timed(name: str, metric: str = "lgbm_timed_seconds"):
    """Wall-clock a block into the registry (histogram `metric`, label
    ``name=``) and, under trace mode, a span.  The raw per-repeat walls
    read back via ``REGISTRY.histogram_samples`` — the bench's
    stopwatch replacement."""
    if not _METRICS:
        yield
        return
    sp = span(name)
    t0 = time.perf_counter()
    try:
        with sp:
            yield
    finally:
        # record in finally, like timer.PHASE: a raising block must not
        # leave the span recorded but the registry sample missing
        REGISTRY.observe(metric, time.perf_counter() - t0, name=name)


# ---------------------------------------------------------------------------
# recording / export
# ---------------------------------------------------------------------------
def _record(ev: Dict) -> None:
    global _dropped
    # mirror into the always-on flight recorder FIRST: the blackbox
    # ring is independently bounded, so a full trace buffer (the
    # _EVENT_CAP drop path below) must not silence it
    from . import flightrecorder

    flightrecorder.note(ev["kind"], ev["name"], **(ev["tags"] or {}))
    with _events_lock:
        if len(_events) >= _EVENT_CAP:
            _dropped += 1
            REGISTRY.inc("lgbm_trace_events_dropped_total")
            return
        _events.append(ev)
    if _trace_dir:
        _stream_write(ev)


def _stream_write(ev: Dict) -> None:
    global _stream, _stream_path
    line = json.dumps({
        "kind": ev["kind"], "name": ev["name"], "ts_us": round(ev["ts"], 3),
        "dur_us": round(ev["dur"], 3), "id": ev["id"],
        "parent_id": ev["parent_id"], "host": ev["host"],
        "tid": ev["tid"], "tags": ev["tags"],
    })
    with _stream_lock:
        path = os.path.join(_trace_dir,
                            f"events-host{_host_index()}.jsonl")
        try:
            if _stream is None or _stream_path != path:
                if _stream is not None:
                    _stream.close()
                os.makedirs(_trace_dir, exist_ok=True)
                _stream = open(path, "a")
                _stream_path = path
            _stream.write(line + "\n")
            _stream.flush()
        except OSError:  # pragma: no cover - disk full / perms
            pass


def events() -> List[Dict]:
    with _events_lock:
        return list(_events)


def reset_events() -> None:
    """Drop the buffered spans (tests / fresh profiling windows); the
    JSONL stream on disk is untouched."""
    global _dropped
    with _events_lock:
        _events.clear()
        _dropped = 0


def chrome_trace() -> Dict:
    """The buffered spans as a Chrome trace-event JSON object (Perfetto
    opens it directly; chrome://tracing too)."""
    host = _host_index()
    out = [{
        "name": "process_name", "ph": "M", "pid": host, "tid": 0,
        "args": {"name": f"lightgbm_tpu host {host}"},
    }]
    with _events_lock:
        evs = list(_events)
    for ev in evs:
        rec = {"name": ev["name"], "ph": ev["ph"],
               "ts": round(ev["ts"], 3), "pid": ev["host"],
               "tid": ev["tid"],
               "args": dict(ev["tags"], id=ev["id"],
                            parent_id=ev["parent_id"])}
        if ev["ph"] == "X":
            rec["dur"] = round(ev["dur"], 3)
        else:
            rec["s"] = "t"  # instant-event scope
        out.append(rec)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome_trace(path: Optional[str] = None) -> Optional[str]:
    """Dump the buffered spans as ``trace-host<k>.json`` under
    ``tpu_trace_dir`` (or an explicit path).  Returns the written path,
    or None when there is nowhere to write."""
    if path is None:
        if not _trace_dir:
            return None
        os.makedirs(_trace_dir, exist_ok=True)
        path = os.path.join(_trace_dir,
                            f"trace-host{_host_index()}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(chrome_trace(), f)
    os.replace(tmp, path)
    return path


def flush() -> None:
    """Flush/close the JSONL stream (end of train, interpreter exit)."""
    global _stream
    with _stream_lock:
        if _stream is not None:
            try:
                _stream.flush()
                _stream.close()
            except OSError:  # pragma: no cover
                pass
            _stream = None


_env_init()
