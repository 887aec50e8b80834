"""Retrace audit: a ledger of every XLA program this process brings up.

A program is born in three stages: JAX *traces* the function to a jaxpr,
*lowers* the jaxpr to an MLIR module, and *compiles* the module (or loads
the executable from the persistent cache).  Every process pays the first
two for every program, cache or no cache; only the third is what the
persistent cache saves.  The enemy is not one big program but the *zoo*:
every jit site that keys a new trace on a static argument or a fresh
closure silently multiplies the bill.

`ledger_jit` wraps a `jax.jit` site so each DISTINCT compiled program
(new entry in the jit's own executable cache) is recorded once with:

* the site name (one per wrapped jit call site),
* the first-call wall time (trace + lowering + XLA compile + first
  execution),
* a compact signature of the triggering call (static args + input
  shapes/dtypes), so `tools/perf_probe.py retrace` can attribute WHICH
  mode/shape variant added a program.

Overhead discipline: when the ledger is disabled (the default) the
wrapper costs one attribute check per call and computes nothing, and no
`jax.monitoring` listener is registered; when enabled, cache growth is
detected via the jit's own `_cache_size()` so no per-call signature
hashing happens on cache hits, and a call the jit cache answers fires
no stage event at all.  The wrapper is transparent: `_cache_size`,
`clear_cache`, etc. delegate to the underlying jitted callable; `trace`
and `lower` are its own methods, so that what they cost lands at the
site too.

While enabled the ledger listens to `jax.monitoring`, which reports all
three stages (`/jax/core/compile/jaxpr_trace_duration`,
`.../jaxpr_to_mlir_module_duration`, `.../backend_compile_duration`: a
scalar when a stage starts, its duration when it ends; the compilation
cache's own hit event, fired inside the third on the same thread, tells
a load from a compile).  Each stage event is charged to the `ledger_jit`
site whose call, `trace` or `lower` is in flight on that thread, or to
`"(none)"` outside any site (eager `jnp` ops, a bare `jax.jit`), with the
name JAX gives the function, and becomes

* a row of `births()` (`stage`: ``trace`` | ``lower`` | ``compile``;
  `compiles()` is the third stage's rows under their older keys);
* seconds and a count in ``lgbm_trace_seconds_total`` /
  ``lgbm_trace_programs_total`` ``{site}``, ``lgbm_lower_seconds_total``
  / ``lgbm_lower_programs_total`` ``{site}``, and
  ``lgbm_compile_seconds_total`` / ``lgbm_compile_programs_total``
  ``{site,cache}``;
* under ``tpu_telemetry=trace`` a span: ``program/trace`` and
  ``program/lower`` ``site= fun_name=``, ``compile`` ``site= fun_name=
  cache=``.

Stages nest: tracing a function traces every inner `jit` it calls (each
`jnp` function, each kernel body), a lowering rule may trace, a trace may
run an eager op to its compile.  JAX says when a stage starts, so every
event knows what encloses it on its thread.  A row's `seconds` is the
stage's wall and `self_s` that less the stage events it enclosed, `depth`
counts what encloses it (0: outermost); the trace and lower counters add
self seconds (summed over sites and stages they are a wall, not a multiple
of one) and count outermost events only.  The spans nest as the stages do,
under whatever `obs.span` was open; a trace directly inside a trace gets no
span of its own but is counted in the enclosing span's ``inner=`` tag.
`programs()`'s ``first_call_s`` is a call's wall (all three stages AND the
first execution).

The module-level `LEDGER` singleton is the process-wide audit surface:

    from lightgbm_tpu.utils.compile_ledger import LEDGER
    LEDGER.enable(); LEDGER.reset()
    ... train / predict / serve ...
    LEDGER.n_programs()        # the n_programs bench metric
    LEDGER.report()            # per-site breakdown
    LEDGER.births()            # every stage of every program, by site
    LEDGER.compiles()          # every program produced, by site
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import jax
from jax import monitoring

from .. import obs

# the jax.monitoring events the attribution reads (jax 0.9: dispatch.py
# log_elapsed_time around pjit.py's trace, interpreters/pxla.py's lowering
# and _cached_compilation; compiler.py compile_or_get_cached)
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# the counters of the first two stages (seconds, programs); the third's
# carry the cache's answer as a second label
_COUNTERS = {"trace": ("lgbm_trace_seconds_total",
                       "lgbm_trace_programs_total"),
             "lower": ("lgbm_lower_seconds_total",
                       "lgbm_lower_programs_total")}
NO_SITE = "(none)"

# per thread: the ledger_jit sites whose calls are in flight, the stages
# in flight, and whether the persistent cache answered the program now
# being produced
_tls = threading.local()


def _thread_list(name: str) -> list:
    st = getattr(_tls, name, None)
    if st is None:
        st = []
        setattr(_tls, name, st)
    return st


def _site_stack() -> List[str]:
    return _thread_list("sites")


class _Stage:
    """A stage in flight on a thread: the seconds of the stage events it
    has enclosed so far, how many of them it folded into its span, and
    that span (None with tracing off, and for a compile, whose span is
    made when its cache answer is known)."""
    __slots__ = ("stage", "inner_s", "inner", "span")

    def __init__(self, stage: str, span):
        self.stage = stage
        self.inner_s = 0.0
        self.inner = 0
        self.span = span


def _stage_stack() -> List[_Stage]:
    return _thread_list("stages")


def _describe_leaf(x: Any) -> str:
    """Compact aval-or-value description of one argument leaf."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    if isinstance(x, (bool, int, float, str, type(None))):
        return repr(x)
    if callable(x):
        return getattr(x, "__name__", "<fn>")
    return type(x).__name__


def _spec_leaf(x: Any) -> Any:
    """Array leaf -> ShapeDtypeStruct (re-lowerable after the original
    buffers are donated/freed); everything else passes through."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                    weak_type=bool(getattr(x, "weak_type",
                                                           False)))
    return x


def call_specs(args: tuple, kwargs: dict, static_argnums=(),
               static_argnames=()) -> tuple:
    """(args, kwargs) with every NON-STATIC array replaced by its
    ShapeDtypeStruct — the re-lowerable coordinates of one compiled
    program, captured BEFORE the call so donation cannot invalidate
    them.  Static args stay as their hashable values (a struct there
    would trace a different program)."""
    import jax.tree_util as jtu

    static_argnums = set(static_argnums or ())
    static_argnames = set(static_argnames or ())
    spec_args = tuple(
        a if i in static_argnums else jtu.tree_map(_spec_leaf, a)
        for i, a in enumerate(args))
    spec_kwargs = {
        k: (v if k in static_argnames else jtu.tree_map(_spec_leaf, v))
        for k, v in kwargs.items()}
    return spec_args, spec_kwargs


def call_signature(args: tuple, kwargs: dict) -> str:
    """One-line signature of a jit call: static values + array avals.

    Dict args (the grower's meta) list key=aval pairs so mode/shape
    variants are attributable from the retrace report alone."""
    parts: List[str] = []
    for a in args:
        if isinstance(a, dict):
            inner = ",".join(f"{k}={_describe_leaf(v)}"
                             for k, v in sorted(a.items(), key=lambda kv: kv[0]))
            parts.append("{" + inner + "}")
        elif isinstance(a, (tuple, list)):
            parts.append("(" + ",".join(_describe_leaf(v) for v in a) + ")")
        else:
            parts.append(_describe_leaf(a))
    for k in sorted(kwargs):
        parts.append(f"{k}={_describe_leaf(kwargs[k])}")
    return "(" + ", ".join(parts) + ")"


class CompileLedger:
    """Thread-safe registry of compiled programs across all wrapped sites."""

    def __init__(self):
        self._lock = threading.Lock()
        self._enabled = False
        self._capture = False
        self._programs: List[Dict] = []
        self._births: List[Dict] = []

    # -- control -------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, on: bool = True) -> None:
        """Switch the ledger, and with it the `jax.monitoring`
        listeners: none is registered while it is off."""
        on = bool(on)
        with self._lock:
            if on and not self._enabled:
                # a stage a switch-off cut short must not enclose what
                # this thread does next
                del _stage_stack()[:]
                monitoring.register_event_listener(self._on_event)
                monitoring.register_scalar_listener(self._on_start)
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
            elif self._enabled and not on:
                monitoring.unregister_event_listener(self._on_event)
                monitoring.unregister_scalar_listener(self._on_start)
                monitoring.unregister_event_duration_listener(
                    self._on_duration)
            self._enabled = on

    @property
    def capture_costs(self) -> bool:
        return self._capture

    def enable_capture(self, on: bool = True) -> None:
        """Additionally capture each new program's re-lowerable call
        specs so `analyze()` can attach its static cost/memory analysis
        (ISSUE 12).  Off by default: spec capture is cheap but not
        free, and only resource-accounting callers (bench,
        perf_probe mem) read it."""
        self._capture = bool(on)

    def reset(self) -> None:
        with self._lock:
            self._programs = []
            self._births = []

    # -- stage attribution (called by jax.monitoring) -------------------
    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            _tls.cache_hit = True

    def _on_start(self, event: str, _value, **kw) -> None:
        """A stage starts on this thread.  With tracing on, a trace or a
        lowering opens its span here, so that what it encloses is its
        child; a trace directly inside a trace is only counted."""
        stage = _STAGES.get(event)
        if stage is None:
            return
        stages = _stage_stack()
        sp = None
        if obs.tracing_on() and stage != "compile":
            if stage == "trace" and stages and stages[-1].stage == "trace":
                stages[-1].inner += 1
            else:
                sites = _site_stack()
                sp = obs.span("program/" + stage,
                              site=sites[-1] if sites else NO_SITE,
                              fun_name=str(kw.get("fun_name", "")))
                sp.__enter__()
        stages.append(_Stage(stage, sp))

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        stages = _stage_stack()
        # its own start, or none (a listener switched on in mid-stage)
        own = (stages.pop() if stages and stages[-1].stage == stage
               else _Stage(stage, None))
        depth = len(stages)
        if stages:
            stages[-1].inner_s += seconds
            if own.span is None:
                # a folded trace hands what it folded to the span above
                stages[-1].inner += own.inner
        self_s = max(0.0, seconds - own.inner_s)
        sites = _site_stack()
        site = sites[-1] if sites else NO_SITE
        fun_name = str(kw.get("fun_name", ""))
        row = {"site": site, "fun_name": fun_name, "stage": stage,
               "seconds": seconds, "self_s": self_s, "depth": depth}
        if stage == "compile":
            cache = row["cache"] = ("hit" if getattr(_tls, "cache_hit", False)
                                    else "miss")
            _tls.cache_hit = False
            obs.REGISTRY.inc("lgbm_compile_seconds_total", seconds,
                             help="seconds JAX spent producing programs, "
                                  "by ledger site and persistent-cache "
                                  "answer",
                             site=site, cache=cache)
            obs.REGISTRY.inc("lgbm_compile_programs_total", 1,
                             help="programs JAX produced (compiled or "
                                  "loaded)",
                             site=site, cache=cache)
            obs.span_ended("compile", seconds, site=site,
                           fun_name=fun_name, cache=cache)
        else:
            seconds_total, programs_total = _COUNTERS[stage]
            obs.REGISTRY.inc(seconds_total, self_s,
                             help="seconds JAX spent in this stage of a "
                                  "program's birth, less the stages it "
                                  "enclosed, by ledger site",
                             site=site)
            if depth == 0:
                obs.REGISTRY.inc(programs_total, 1,
                                 help="outermost events of this stage, by "
                                      "ledger site",
                                 site=site)
            if own.span is not None:
                if own.inner:
                    own.span.tags["inner"] = own.inner
                own.span.__exit__(None, None, None)
        with self._lock:
            self._births.append(row)

    def births(self) -> List[Dict]:
        """Every stage event JAX reported while enabled, in the order
        they ended (an enclosed event before the one that encloses it):
        `site`, `fun_name`, `stage` ("trace" | "lower" | "compile"),
        `seconds` (the stage's wall), `self_s` (that less the events it
        enclosed), `depth` (the stages in flight around it on its
        thread; 0: outermost) and, for a compile, `cache` ("hit" |
        "miss")."""
        with self._lock:
            return [dict(b) for b in self._births]

    def compiles(self) -> List[Dict]:
        """Every program JAX produced while enabled, in order: `site`,
        `fun_name`, `compile_s` (compile or cache load, no execution),
        `cache` ("hit" | "miss")."""
        with self._lock:
            return [{"site": b["site"], "fun_name": b["fun_name"],
                     "compile_s": b["seconds"], "cache": b["cache"]}
                    for b in self._births if b["stage"] == "compile"]

    # -- recording (called by LedgeredJit) ------------------------------
    def record(self, site: str, signature: str, wall_s: float,
               aot=None) -> None:
        with self._lock:
            self._programs.append({"site": site, "signature": signature,
                                   "first_call_s": wall_s,
                                   "t": time.time(), "_aot": aot})

    # -- reading --------------------------------------------------------
    def n_programs(self, site: Optional[str] = None) -> int:
        """Programs compiled while enabled (optionally for one site)."""
        with self._lock:
            if site is None:
                return len(self._programs)
            return sum(1 for p in self._programs if p["site"] == site)

    def programs(self) -> List[Dict]:
        with self._lock:
            return [{k: v for k, v in p.items() if k != "_aot"}
                    for p in self._programs]

    # -- static cost/memory analysis (ISSUE 12) -------------------------
    @staticmethod
    def _memory_default() -> bool:
        """memory_analysis needs a fresh AOT compile per program (jax
        gives no handle on the jit cache's own executable), so the
        auto policy pays it only where HBM numbers exist to read back;
        on CPU the table carries flops/bytes from the (compile-free)
        lowered analysis and None for the memory fields."""
        return jax.devices()[0].platform != "cpu"

    def analyze(self, memory: Optional[bool] = None) -> List[Dict]:
        """Attach each captured program's `cost_analysis()` (flops,
        bytes accessed — from the lowering, no compile) and, when
        `memory` (default: auto — True off-CPU), its compiled
        `memory_analysis()` (argument / output / temp / generated-code
        bytes).  Idempotent; failures record None per field rather than
        raising — a program that cannot re-lower (mesh-sharded specs,
        exotic statics) still keeps its ledger entry."""
        if memory is None:
            memory = self._memory_default()
        with self._lock:
            # re-analyze when memory is requested but a prior pass
            # (auto: memory=False on CPU) SKIPPED it — "mem" absent
            # means not yet attempted; "mem": None means a real attempt
            # FAILED and must not be re-paid (a failing re-lower would
            # otherwise re-run its AOT attempt on every call)
            todo = [p for p in self._programs
                    if p.get("_aot") is not None
                    and ("cost" not in p or (memory and "mem" not in p))]
        for p in todo:
            fn, spec_args, spec_kwargs = p["_aot"]
            cost = None
            lowered = None
            try:
                lowered = fn.lower(*spec_args, **spec_kwargs)
                ca = lowered.cost_analysis() or {}
                cost = {"flops": float(ca.get("flops", 0.0)),
                        "bytes_accessed": float(
                            ca.get("bytes accessed", 0.0))}
            except Exception:
                cost = None
            updates = {"cost": cost}
            if lowered is None:
                updates["mem"] = None          # can never re-lower
            elif memory:
                try:
                    ms = lowered.compile().memory_analysis()
                    updates["mem"] = {
                        "argument_bytes": int(ms.argument_size_in_bytes),
                        "output_bytes": int(ms.output_size_in_bytes),
                        "temp_bytes": int(ms.temp_size_in_bytes),
                        "alias_bytes": int(ms.alias_size_in_bytes),
                        "generated_code_bytes": int(
                            ms.generated_code_size_in_bytes),
                    }
                except Exception:
                    updates["mem"] = None      # attempted and failed
            with self._lock:
                p.update(updates)
        return self.programs()

    def cost_table(self, memory: Optional[bool] = None) -> List[Dict]:
        """Per-program cost rows for the bench JSON / perf_probe mem
        table: site, flops, bytes accessed, and the memory-analysis
        byte fields (None where unavailable — explicitly null on CPU
        rather than silently absent)."""
        rows = []
        for p in self.analyze(memory=memory):
            cost, mem = p.get("cost"), p.get("mem")
            rows.append({
                "site": p["site"],
                "signature": p["signature"][:160],
                "first_call_s": round(p["first_call_s"], 3),
                "flops": None if cost is None else cost["flops"],
                "bytes_accessed": (None if cost is None
                                   else cost["bytes_accessed"]),
                "argument_bytes": None if mem is None
                else mem["argument_bytes"],
                "output_bytes": None if mem is None
                else mem["output_bytes"],
                "temp_bytes": None if mem is None else mem["temp_bytes"],
                "generated_code_bytes": (None if mem is None
                                         else mem["generated_code_bytes"]),
            })
        return rows

    def report(self) -> List[Dict]:
        """Per-site rollup sorted by total first-call wall, descending."""
        agg: Dict[str, Dict] = {}
        for p in self.programs():
            a = agg.setdefault(p["site"], {"site": p["site"], "programs": 0,
                                           "first_call_s": 0.0,
                                           "signatures": []})
            a["programs"] += 1
            a["first_call_s"] += p["first_call_s"]
            a["signatures"].append(p["signature"])
        return sorted(agg.values(), key=lambda a: -a["first_call_s"])

    def format_report(self) -> str:
        lines = [f"{'site':<28s} {'programs':>8s} {'first-call s':>12s}"]
        total_n = total_s = 0
        for a in self.report():
            lines.append(f"{a['site']:<28s} {a['programs']:>8d} "
                         f"{a['first_call_s']:>12.2f}")
            total_n += a["programs"]
            total_s += a["first_call_s"]
        lines.append(f"{'TOTAL (n_programs)':<28s} {total_n:>8d} "
                     f"{total_s:>12.2f}")
        return "\n".join(lines)


LEDGER = CompileLedger()


class LedgeredJit:
    """`jax.jit` plus per-program ledger recording.

    New-program detection uses the jitted callable's own `_cache_size()`
    (the executable cache the jit keys on static args + avals), so the
    ledger can never disagree with what jax actually compiled.
    """

    def __init__(self, fn, site: Optional[str] = None, **jit_kwargs):
        self._fn = jax.jit(fn, **jit_kwargs)
        self.site = site or getattr(fn, "__name__", "<fn>")
        def _as_tuple(v):
            if v is None:
                return ()
            return (v,) if isinstance(v, (int, str)) else tuple(v)

        self._static_argnums = _as_tuple(jit_kwargs.get("static_argnums"))
        self._static_argnames = _as_tuple(
            jit_kwargs.get("static_argnames"))
        # serializes the (cache-size, call, cache-size) window while the
        # ledger is ENABLED: without it, a thread's cache-hit call that
        # overlaps another thread's compile observes the cache growing
        # and double-records the program.  The disabled path (default,
        # production serving) never touches the lock.
        self._lock = threading.Lock()

    def _capture_specs(self, args, kwargs):
        """Re-lowerable specs of one call, built only on the RARE
        new-program branch (never on cache hits — a per-call pytree
        walk under the lock would tax every timed loop the bench
        gates).  Safe AFTER the call: shape/dtype metadata stays
        readable on donated-and-deleted arrays."""
        if not LEDGER.capture_costs:
            return None
        try:
            specs = call_specs(args, kwargs, self._static_argnums,
                               self._static_argnames)
        except Exception:  # pragma: no cover - exotic pytree
            return None
        return (self._fn, *specs)

    def __call__(self, *args, **kwargs):
        if not LEDGER.enabled:
            return self._fn(*args, **kwargs)
        with self._lock:
            before = self._fn._cache_size()
            t0 = time.perf_counter()
            out = self._at_site(self._fn, args, kwargs)
            if self._fn._cache_size() > before:
                LEDGER.record(self.site, call_signature(args, kwargs),
                              time.perf_counter() - t0,
                              aot=self._capture_specs(args, kwargs))
        return out

    def _at_site(self, method, args, kwargs):
        """`method(*args, **kwargs)` with this site in flight on the
        thread, so that the ledger charges it what JAX does there."""
        if not LEDGER.enabled:
            return method(*args, **kwargs)
        sites = _site_stack()
        sites.append(self.site)
        try:
            return method(*args, **kwargs)
        finally:
            sites.pop()

    def trace(self, *args, **kwargs):
        """`jax.jit(fn).trace`, its stage events charged to this site."""
        return self._at_site(self._fn.trace, args, kwargs)

    def lower(self, *args, **kwargs):
        """`jax.jit(fn).lower`, its stage events charged to this site."""
        return self._at_site(self._fn.lower, args, kwargs)

    def __getattr__(self, name):
        # transparent delegation (_cache_size/clear_cache/eval_shape/...)
        return getattr(self._fn, name)


def closed_over_bytes(jitted, args: tuple, kwargs: dict,
                      lengths) -> int:
    """Bytes of the arrays a jitted function closes over (the constants
    of its program, a table among them if it is captured and not passed)
    that have an axis of one of `lengths`.  Traces `jitted` at `args`
    (arrays or `jax.ShapeDtypeStruct`s; the trace is the one its first
    call at these shapes uses) and compiles nothing."""
    lengths = set(lengths)
    consts = jitted.trace(*args, **kwargs).jaxpr.consts
    return sum(int(c.size) * c.dtype.itemsize for c in consts
               if lengths & set(getattr(c, "shape", ())))


def ledger_jit(fn=None, *, site: Optional[str] = None, **jit_kwargs):
    """Drop-in `jax.jit` replacement that records programs in LEDGER.

    Usable as a decorator (`@ledger_jit(site=..., static_argnames=...)`)
    or a call (`ledger_jit(f, site=...)`)."""
    if fn is None:
        def deco(f):
            return LedgeredJit(f, site=site, **jit_kwargs)
        return deco
    return LedgeredJit(fn, site=site, **jit_kwargs)
