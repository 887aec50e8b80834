"""Device-memory budgeting: preflight planning, OOM classification, and
the deterministic degradation ladder (ISSUE 15).

Until now HBM exhaustion was an unclassified ``XlaRuntimeError`` that
killed a run outright: no preflight warning, no recovery, no named
postmortem.  PR 12 made memory *observable* (per-program
``memory_analysis()`` on the CompileLedger, per-phase peak watermarks,
per-model HBM gauges); this module makes it an *enforced, recoverable
contract*:

* **classification** — `is_oom_error` recognizes the
  ``RESOURCE_EXHAUSTED`` / out-of-memory shapes jax surfaces
  (``XlaRuntimeError`` text is the only stable signal across jaxlib
  versions), and `oom_guard(site)` wraps every guarded device site so
  an allocation failure re-raises as a structured `DeviceOutOfMemory`
  naming the site — counted (``lgbm_oom_events_total{site=}``), noted
  in the flight recorder WITH a device-memory snapshot, and ready for
  the recovery machinery above it.  The guard also hosts the
  ``device_alloc`` fault-injection point (`utils/faultline.py`), whose
  ``oom`` action raises a realistic RESOURCE_EXHAUSTED-shaped error —
  chaos tests exercise exactly the classification path real OOMs take.
* **budget** — `budget_bytes(config)` resolves the enforced HBM budget:
  explicit ``tpu_hbm_budget_bytes``, else device capacity
  (``memory_stats()['bytes_limit']``) scaled by ``tpu_hbm_budget_frac``;
  None on backends that report nothing (CPU) — a missing number is
  never invented.  `serving_budget_bytes` is the serving twin
  (``serving_hbm_budget_bytes``, falling back to the training budget).
* **preflight planning** — `plan_training` itemizes the predictable HBM
  consumers from closed-form buffer models anchored to the LIVE learner
  buffers (binned matrix, the [L, G/P, B, 3] histogram pool, stats
  planes, score + donation buffers, packed forest, ingest/predict chunk
  scratch) into a `MemoryPlan` that either fits the budget or carries a
  named, itemized refusal table.  `ledger_cross_check` compares the
  plan against the CompileLedger's independent ``memory_analysis()``
  oracle where one exists.  `plan_model_load` is the serving-side twin:
  predicted packed-table + launch-scratch bytes BEFORE any upload, so
  the registry can refuse (HTTP 507) instead of warming into a crash.
* **degradation ladder** — `DegradationLadder` owns the deterministic,
  logged retry sequence a mid-train OOM descends after the PR-7
  iteration rollback: (1) halve ``tpu_ingest_chunk_rows`` /
  ``tpu_predict_chunk_rows`` (floor 4096), (2) switch
  ``tpu_hist_agg=psum`` -> ``scatter`` (the ~P x per-shard pool
  reduction, PR 5), (3) drop ``tpu_bucket_policy=wide`` -> ``fine``
  (smaller launch/ramp shapes, PR 6).  Every step is BITWISE-INVISIBLE
  — each knob is already proven to leave model bytes unchanged — so a
  run that settles after k steps produces a model file byte-identical
  to an undisturbed run at the settled configuration.  Exhaustion is a
  structured `MemoryLadderExhausted` that rides the existing
  final-checkpoint-flush + blackbox-dump path.

Nothing here ever forces a backend init, and classification never
swallows a non-OOM error: a ValueError stays a ValueError.
"""

from __future__ import annotations

import contextlib
import math
import re
from typing import Any, Dict, List, Optional, Tuple

from . import faultline

#: the guarded device sites `oom_guard` may name — one vocabulary shared
#: by the classifier, the metrics labels, the flight recorder, and the
#: chaos tests (the `device_alloc` faultline point fires at each)
OOM_SITES = ("train_step", "ingest_chunk", "predict_chunk",
             "score_replay", "registry_load", "registry_warmup",
             "serve_dispatch")

#: deterministic ladder floors: chunk shrinking never goes below these
#: (4096 rows is the smallest launch bucket the wide policy emits; the
#: binning kernel's own minimum is far lower and never the binding one)
CHUNK_FLOOR = 4096

#: ladder step vocabulary, in descent order; the final rung trades the
#: device-resident binned matrix for the streamed layout (ops/stream.py)
#: instead of raising MemoryLadderExhausted — slower, but the run
#: completes (and stays bitwise for int8/int16 precisions)
LADDER_STEPS = ("shrink_chunk_rows", "hist_agg_scatter",
                "bucket_policy_fine", "stream_layout")

_OOM_RE = re.compile(
    r"RESOURCE[ _]EXHAUSTED|out of memory|"
    r"failed to allocate|allocation (failure|failed)|"
    r"exceeds the memory capacity|insufficient memory",
    re.IGNORECASE)
# the bare acronym only as an upper-case whole word: a case-insensitive
# unanchored "OOM" would classify "no room left" / "zoom level" errors
_OOM_WORD_RE = re.compile(r"\bOOM\b")

#: exception TYPE names that may carry an OOM (jaxlib's runtime error
#: class moved modules across versions; the NAME is the stable part)
_RUNTIME_ERROR_NAMES = ("XlaRuntimeError", "JaxRuntimeError",
                        "RuntimeError", "InternalError",
                        "ResourceExhaustedError")


class DeviceOutOfMemory(RuntimeError):
    """A device allocation failure, classified and named.

    Carries the guarded `site` it surfaced at plus any diagnostics the
    site attached; `__cause__` is the raw backend error."""

    def __init__(self, message: str, site: str = "unknown",
                 info: Optional[Dict[str, Any]] = None):
        super().__init__(message)
        self.site = str(site)
        self.info = dict(info or {})


class MemoryLadderExhausted(DeviceOutOfMemory):
    """The degradation ladder ran out of bitwise-invisible steps.

    Raised after the failed iteration was rolled back, so the booster
    stays usable; `engine.train` flushes a final checkpoint and the
    flight recorder dumps the blackbox (with the memory snapshot) on
    the way out."""


class ServingMemoryExhausted(DeviceOutOfMemory):
    """A model load the serving HBM budget cannot admit (HTTP 507):
    the registry refused BEFORE uploading (or after eviction could not
    free enough), with the itemized plan in the message."""

    http_status = 507


def is_oom_error(exc: BaseException) -> bool:
    """Is `exc` a device out-of-memory?  Already-classified errors pass
    through; raw backend errors classify on the RESOURCE_EXHAUSTED /
    out-of-memory message shapes — jaxlib's error TYPES move between
    modules across versions, so the text is the stable signal.  A
    generic `faultline.FaultInjected` (the plain ``raise`` action)
    never classifies: only the ``oom`` action's realistic error does."""
    if isinstance(exc, DeviceOutOfMemory):
        return True
    if isinstance(exc, faultline.FaultInjected):
        return False
    if type(exc).__name__ not in _RUNTIME_ERROR_NAMES \
            and not isinstance(exc, (RuntimeError, MemoryError)):
        return False
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc)
    return bool(_OOM_RE.search(msg) or _OOM_WORD_RE.search(msg))


def memory_snapshot() -> Dict[str, Optional[int]]:
    """Best-effort device-memory numbers for diagnostics (all None on
    CPU): what the blackbox and the structured errors carry."""
    from ..obs import resources

    return {"hbm_bytes_in_use": resources.hbm_bytes_in_use(),
            "hbm_peak_bytes": resources.peak_hbm_bytes(),
            "hbm_capacity_bytes": device_capacity_bytes()}


def note_oom(site: str, exc: Optional[BaseException] = None,
             **info) -> None:
    """Record one classified OOM: counter + flight-recorder entry with
    the device-memory snapshot (the postmortem's first question is
    'how full was HBM' — answer it in the ring, not in a log grep)."""
    from ..obs import REGISTRY, flightrecorder

    REGISTRY.inc("lgbm_oom_events_total", site=str(site),
                 help="classified device out-of-memory errors per "
                      "guarded site")
    snap = {k: v for k, v in memory_snapshot().items() if v is not None}
    flightrecorder.note("oom", "device_oom", site=str(site),
                        error=(str(exc)[:160] if exc is not None else None),
                        **snap, **{k: str(v) for k, v in info.items()})


@contextlib.contextmanager
def oom_guard(site: str, **info):
    """Guard one device site: hosts the ``device_alloc`` fault point
    and re-raises any classified allocation failure as a structured
    `DeviceOutOfMemory` naming the site.  Non-OOM errors pass through
    untouched — classification must never mask a data error."""
    try:
        faultline.fire("device_alloc", site=site, **info)
        yield
    except DeviceOutOfMemory:
        raise  # already classified at an inner site: keep its name
    except Exception as exc:
        if not is_oom_error(exc):
            raise
        note_oom(site, exc, **info)
        raise DeviceOutOfMemory(
            f"device out of memory at {site!r}: {str(exc)[:200]}",
            site=site, info=info) from exc


# ---------------------------------------------------------------------------
# budget resolution
# ---------------------------------------------------------------------------
#: one-shot capacity memo ([] = not yet known): capacity is static per
#: process, and re-querying every device's memory_stats() on every
#: /healthz probe or locked eviction path would pay device round-trips
#: to re-derive a constant.  Only a DEFINITIVE answer is cached — an
#: empty device list (jax not initialized yet) stays uncached so the
#: first post-init call resolves correctly.
_capacity_memo: List[Optional[int]] = []


def device_capacity_bytes() -> Optional[int]:
    """Smallest per-device HBM capacity across reporting devices
    (``bytes_limit`` / ``bytes_reservable_limit``), or None (CPU).
    The MINIMUM is the binding constraint for replicated buffers."""
    if _capacity_memo:
        return _capacity_memo[0]
    from ..obs import resources

    if not resources._devices():
        return None  # backend not up: answer unknown, do NOT pin it
    vals: List[int] = []
    for s in resources.all_device_memory_stats():
        if s is None:
            continue
        v = s.get("bytes_limit", s.get("bytes_reservable_limit"))
        if v:
            vals.append(int(v))
    cap = min(vals) if vals else None
    _capacity_memo.append(cap)
    return cap


def budget_bytes(config) -> Optional[int]:
    """The enforced training HBM budget: ``tpu_hbm_budget_bytes`` when
    explicitly set, else device capacity x ``tpu_hbm_budget_frac``;
    None when neither resolves (no explicit bytes AND a non-reporting
    backend) — an explicit budget is honored even on CPU so the whole
    planner/ladder surface is testable anywhere."""
    explicit = int(config.get("tpu_hbm_budget_bytes", 0) or 0)
    if explicit > 0:
        return explicit
    cap = device_capacity_bytes()
    if cap is None:
        return None
    frac = float(config.get("tpu_hbm_budget_frac", 0.9) or 0.9)
    return int(cap * max(min(frac, 1.0), 0.01))


def serving_budget_bytes(config) -> Optional[int]:
    """The serving-registry HBM budget (packed model tables + launch
    scratch): ``serving_hbm_budget_bytes`` when set, else the training
    budget resolution above."""
    explicit = int(config.get("serving_hbm_budget_bytes", 0) or 0)
    if explicit > 0:
        return explicit
    return budget_bytes(config)


def publish_budget_gauge(budget: Optional[int], scope: str) -> None:
    """Expose the resolved budget as `lgbm_hbm_budget_bytes{scope=}`
    (nothing is published when no budget resolves — no fictional 0)."""
    if budget is None:
        return
    from ..obs import REGISTRY

    REGISTRY.set_gauge("lgbm_hbm_budget_bytes", int(budget),
                       help="enforced device-memory budget "
                            "(tpu_hbm_budget_* / serving_hbm_*)",
                       scope=str(scope))


# ---------------------------------------------------------------------------
# preflight planning
# ---------------------------------------------------------------------------
class MemoryPlan:
    """An itemized HBM prediction vs a budget.

    `fits` is True/False against a resolved budget, None when no budget
    exists (nothing to enforce).  `format_table()` renders the named
    itemization every refusal and every ``perf_probe mem`` read."""

    def __init__(self, components: Dict[str, int],
                 budget: Optional[int], scope: str):
        self.components = {k: int(v) for k, v in components.items()}
        self.budget = None if budget is None else int(budget)
        self.scope = str(scope)

    @property
    def total(self) -> int:
        return sum(self.components.values())

    @property
    def headroom(self) -> Optional[int]:
        return None if self.budget is None else self.budget - self.total

    @property
    def fits(self) -> Optional[bool]:
        return None if self.budget is None else self.total <= self.budget

    def format_table(self) -> str:
        width = max([len(k) for k in self.components] + [10])
        lines = [f"{'component':<{width}s} {'bytes':>14s}"]
        for name, b in sorted(self.components.items(),
                              key=lambda kv: -kv[1]):
            lines.append(f"{name:<{width}s} {b:>14,d}")
        lines.append(f"{'TOTAL':<{width}s} {self.total:>14,d}")
        if self.budget is not None:
            lines.append(f"{'budget':<{width}s} {self.budget:>14,d}")
            lines.append(f"{'headroom':<{width}s} {self.headroom:>14,d}")
        return "\n".join(lines)

    def refuse_message(self, what: str) -> str:
        return (f"{what} needs a predicted {self.total:,d} device bytes "
                f"against a {self.budget:,d}-byte {self.scope} HBM "
                f"budget (headroom {self.headroom:,d}); itemized plan:\n"
                f"{self.format_table()}")

    def to_dict(self) -> Dict:
        return {"components": dict(self.components), "total": self.total,
                "budget": self.budget, "headroom": self.headroom,
                "fits": self.fits, "scope": self.scope}


#: stats-plane layout per histogram precision: (rows, itemsize bytes)
#: — pack_stats emits [5, n] bf16 for hilo, [3, n] otherwise
#: (ops/histogram.py)
_STATS_PLANES = {"hilo": (5, 2), "bf16": (3, 2), "f32": (3, 4),
                 "f64": (3, 8), "int8": (3, 1), "int16": (3, 2)}


def _pool_bytes(learner, config) -> int:
    """The [L, G/P, B, 3] histogram pool's PER-DEVICE bytes.  Anchored
    to the live donated buffer when one exists (exact); the scatter
    aggregation leaves each data shard only its 1/P column slice."""
    pool = getattr(learner, "_pool", None)
    spec = getattr(learner, "_pool_spec", None)
    if pool is not None:
        total = int(pool.nbytes)
    elif spec is not None:
        shape, pdt, _sh = spec
        total = int(math.prod(shape)) * pdt.itemsize
    else:
        # pool lives inside the grow program (donation off / voting):
        # same closed form, from the learner's own padded axes
        from ..ops.grower import pool_dtype

        import jax.numpy as jnp

        L = int(learner.params.num_leaves)
        B = int(learner.meta_np["num_bin"].max()) if hasattr(
            learner, "meta_np") else 256
        total = (L * int(getattr(learner, "g_pad", 1)) * B * 3
                 * jnp.dtype(pool_dtype(learner.params.precision)).itemsize)
    d = max(int(getattr(learner, "d_shards", 1)), 1)
    agg = str(config.get("tpu_hist_agg", "auto") or "auto")
    eff = getattr(learner, "hist_agg", "psum")
    # a not-yet-applied scatter override still shrinks the PLAN — the
    # degrade preflight iterates config overrides before any rebuild
    scatter = (eff == "scatter") or (agg == "scatter" and d > 1)
    return total // (d if scatter and d > 1 else 1)


def packed_forest_bytes(num_trees: int, num_leaves: int) -> int:
    """Closed-form packed-forest table bytes (ops/predict.pack_trees):
    7 int32 node columns of width L-1, the [T, L] f32 leaf values, the
    init-node column, plus the (tiny) shared bitset pool word."""
    L = max(int(num_leaves), 2)
    per_tree = 7 * (L - 1) * 4 + L * 4 + 4
    return max(int(num_trees), 0) * per_tree + 4


def stream_config_blockers(config) -> List[str]:
    """Config-visible reasons the streamed layout (ops/stream.py) cannot
    serve this run — shared by the auto layout selection and the OOM
    ladder's final rung, so neither proposes a layout the streamed
    learner would reject at construction.  Dataset-derived blockers
    (categorical columns discovered by auto detection) are caught by
    select_layout when train_data is in hand, and loudly by the learner
    otherwise."""
    reasons = []
    try:
        from ..parallel.strategies import resolve_tree_learner

        strategy = resolve_tree_learner(
            str(config.get("tree_learner", "serial")))
    except Exception:
        strategy = str(config.get("tree_learner", "serial"))
    if strategy != "serial":
        reasons.append(f"tree_learner={strategy}")
    if float(config.get("tpu_sparse_threshold", 0.0) or 0.0) > 0.0:
        reasons.append("sparse COO storage (tpu_sparse_threshold)")
    if str(config.get("forcedsplits_filename", "") or ""):
        reasons.append("forced splits")
    if float(config.get("feature_fraction_bynode", 1.0) or 1.0) < 1.0:
        reasons.append("feature_fraction_bynode")
    coupled = [float(v) for v in
               config.get("cegb_penalty_feature_coupled", []) or []]
    lazy = [float(v) for v in
            config.get("cegb_penalty_feature_lazy", []) or []]
    if (any(v != 0.0 for v in coupled) or any(v != 0.0 for v in lazy)
            or float(config.get("cegb_penalty_split", 0.0) or 0.0) != 0.0):
        reasons.append("CEGB penalties")
    if str(config.get("categorical_feature", "") or ""):
        reasons.append("categorical features")
    return reasons


def select_layout(config, train_data=None) -> str:
    """Resolve ``tpu_stream_mode`` to the concrete training layout:
    "resident" or "streamed".

    Explicit modes are honored as-is (a streamed pin that the streamed
    learner cannot serve raises there, loudly).  auto keeps the classic
    resident layout unless (a) the run is streamable and (b) the
    closed-form binned-matrix estimate would eat more than half the
    enforced HBM budget — the matrix is the dominant resident and the
    plan's other components (pool, stats planes, scores, scratch) need
    the rest."""
    mode = str(config.get("tpu_stream_mode", "auto") or "auto").lower()
    if mode == "streamed":
        return "streamed"
    if mode == "resident":
        return "resident"
    if mode != "auto":
        raise ValueError("tpu_stream_mode must be auto|resident|streamed,"
                         f" got {mode!r}")
    if stream_config_blockers(config):
        return "resident"
    budget = budget_bytes(config)
    if budget is None or train_data is None:
        return "resident"
    try:
        if train_data.feature_arrays()["is_categorical"].any():
            return "resident"
        n = int(train_data.num_data)
        F = int(train_data.num_features)
        item = 1 if int(train_data.feature_arrays()["num_bin"].max()) \
            <= 256 else 4
    except Exception:
        return "resident"
    if n * F * item > budget // 2:
        return "streamed"
    return "resident"


def plan_training(config, learner, num_class: int) -> MemoryPlan:
    """Itemized pre-iteration-0 HBM prediction for one training run,
    anchored to the LIVE learner buffers where they exist (the binned
    matrix and donated pool components are exact — the planner-vs-array
    tests pin that) and closed-form elsewhere."""
    d = max(int(getattr(learner, "d_shards", 1)), 1)
    n_pad = int(getattr(learner, "n_pad", 0))
    k = max(int(num_class), 1)
    comps: Dict[str, int] = {}
    bins_t = getattr(learner, "bins_t", None)
    streamed = (bool(getattr(learner, "stream_layout", False))
                or str(config.get("tpu_stream_mode", "auto")) == "streamed")
    if streamed:
        # streamed layout: the matrix stays host-resident; the device
        # cost is TWO double-buffered block slots.  Live host blocks are
        # exact; a pending rebuild into streamed (the ladder's final
        # rung re-plans BEFORE the learner is reconstructed) estimates
        # the slot closed-form from the same sizing rule the learner
        # will use
        blocks = getattr(learner, "_host_blocks", None)
        if blocks:
            slot = max(int(b.nbytes) for b in blocks)
        else:
            from ..ops.stream import resolve_stream_rows

            per_row = (int(bins_t.nbytes) // max(n_pad, 1)
                       if bins_t is not None
                       else max(int(getattr(learner, "g_pad", 1)), 1))
            rows = resolve_stream_rows(
                int(config.get("tpu_stream_block_rows", 0) or 0), n_pad,
                per_row,
                int(config.get("tpu_block_rows", 0) or 0) or 16384,
                budget_bytes(config))
            slot = rows * per_row
        comps["stream_slots"] = 2 * slot
    elif bins_t is not None:
        comps["binned_matrix"] = int(bins_t.nbytes) // d
    comps["histogram_pool"] = _pool_bytes(learner, config)
    precision = str(getattr(learner.params, "precision", "hilo"))
    planes, item = _STATS_PLANES.get(precision, (3, 4))
    comps["stats_planes"] = planes * n_pad * item // d
    n_rows = int(getattr(learner, "n", n_pad))
    # live scores + the pre-donation copy the fused step snapshots
    donate = 2 if getattr(learner, "_donate", False) else 1
    comps["score_buffers"] = k * n_rows * 4 * donate
    # row -> leaf partition state ([n] int32 per class pass)
    comps["row_partition"] = n_pad * 4 // d
    # packed forest for score replay / valid updates over the full run
    comps["packed_forest"] = packed_forest_bytes(
        int(config.get("num_iterations", 100)) * k,
        int(config.get("num_leaves", 31)))
    F = int(getattr(learner, "num_features", 0)) or 1
    # chunked ingest scratch: (hi, lo) key planes + the out matrix
    ingest_chunk = int(config.get("tpu_ingest_chunk_rows", 65536))
    comps["ingest_scratch"] = ingest_chunk * F * 9
    # chunked predict scratch: [chunk, F] int32 bins + [k, chunk] f32
    predict_chunk = int(config.get("tpu_predict_chunk_rows", 65536))
    comps["predict_scratch"] = predict_chunk * (F * 4 + k * 4)
    return MemoryPlan(comps, budget_bytes(config), "training")


def plan_model_load(booster, config) -> Optional[MemoryPlan]:
    """Serving-side preflight: predicted device bytes of loading one
    model — packed table bytes from the HOST pack (nothing uploaded
    yet) plus the per-launch bins/score scratch of the largest warmed
    bucket.  None when the model has no device path to plan."""
    from ..config import parse_tristate

    drv = booster._driver
    drv._materialize()
    if drv._pred_context() is None or booster.num_trees() == 0:
        return None
    # an explicit tpu_predict_device=false stays a walker-only entry
    # (ModelEntry.device_on mirrors this): it uploads nothing, so
    # planning packed bytes for it would refuse — and evict real
    # device-backed models for — a load that costs zero HBM
    if parse_tristate(booster.params.get("tpu_predict_device",
                                         "auto")) == "false":
        return None
    pf = drv._packed_forest()       # host pack only; upload is lazy
    host = pf._host or {}
    count = pf._count
    # quantized serving tables (ISSUE 19): price what will actually
    # land on each device — the preflight and the registry's post-load
    # accounting must agree, or a bf16/int16 load would be refused
    # against its f32 size
    precision = str(config.get("serving_table_precision", "f32"))
    if precision != "f32" and host:
        from ..ops.predict import quantize_tables

        host = quantize_tables(
            {k: (v if k == "cat_words" else v[:count])
             for k, v in host.items()}, precision)
        count = -1  # already sliced above
    table_bytes = 0
    for key, arr in host.items():
        view = arr if (key == "cat_words" or count < 0) else arr[:count]
        table_bytes += int(view.nbytes)
    comps = {"packed_tables": table_bytes}
    chunk = drv.predict_chunk_rows()
    rows = min(int(config.get("serving_max_batch_rows", 4096)), chunk)
    F = int(booster.num_feature())
    k = max(int(drv.num_tree_per_iteration), 1)
    comps["launch_scratch"] = rows * (F * 4 + k * 4)
    return MemoryPlan(comps, serving_budget_bytes(config), "serving")


def ledger_cross_check(plan: MemoryPlan, site: str = "grower"
                       ) -> Optional[Dict]:
    """Cross-check the plan against the CompileLedger's independent
    ``memory_analysis()`` oracle (ISSUE 12): the largest captured
    program whose site contains `site` must have argument bytes no
    larger than the plan total plus slack (XLA counts the same buffers
    from the other side).  Returns the comparison dict, or None when no
    analyzed program exists (capture off / nothing compiled)."""
    from .compile_ledger import LEDGER

    rows = [r for r in LEDGER.cost_table(memory=True)
            if site in r["site"] and r.get("argument_bytes") is not None]
    if not rows:
        return None
    biggest = max(rows, key=lambda r: r["argument_bytes"])
    return {"site": biggest["site"],
            "ledger_argument_bytes": int(biggest["argument_bytes"]),
            "ledger_temp_bytes": biggest.get("temp_bytes"),
            "plan_total": plan.total,
            "covered": plan.total >= int(biggest["argument_bytes"])}


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------
class DegradationLadder:
    """The deterministic, logged descent a classified OOM retries down.

    `next_step(config)` returns ``(step_name, param_overrides)`` for
    the next applicable step — or None when exhausted.  The order is
    fixed (chunk shrink to the floor, then the scatter aggregation
    switch, then unfusing the frontier megakernel, then the fine bucket
    policy) so two runs hitting OOM at the
    same point settle at the SAME configuration; every knob is
    bitwise-invisible to model bytes (PRs 3/5/6 prove each), which is
    what makes the settled model byte-identical to an undisturbed run
    at the settled config."""

    def __init__(self):
        self.steps_taken: List[Tuple[str, Dict[str, Any]]] = []

    def next_step(self, config) -> Optional[Tuple[str, Dict[str, Any]]]:
        step = self._propose(config)
        if step is not None:
            self.steps_taken.append(step)
        return step

    def _propose(self, config) -> Optional[Tuple[str, Dict[str, Any]]]:
        ingest = int(config.get("tpu_ingest_chunk_rows", 65536))
        predict = int(config.get("tpu_predict_chunk_rows", 65536))
        overrides: Dict[str, Any] = {}
        if ingest > CHUNK_FLOOR:
            overrides["tpu_ingest_chunk_rows"] = max(ingest // 2,
                                                     CHUNK_FLOOR)
        if predict > CHUNK_FLOOR:
            overrides["tpu_predict_chunk_rows"] = max(predict // 2,
                                                      CHUNK_FLOOR)
        if overrides:
            return "shrink_chunk_rows", overrides
        learner_kind = str(config.get("tree_learner", "serial"))
        sharded = (learner_kind in ("data", "data_parallel", "voting",
                                    "voting_parallel", "data_feature",
                                    "feature_data",
                                    "data_feature_parallel")
                   and int(config.get("num_machines", 1)) > 1)
        if sharded and str(config.get("tpu_hist_agg", "auto")) == "psum":
            # 'auto' already resolves to scatter on a real data axis —
            # only an explicit psum pin has this step to give
            return "hist_agg_scatter", {"tpu_hist_agg": "scatter"}
        if str(config.get("tpu_bucket_policy", "wide")) == "wide":
            return "bucket_policy_fine", {"tpu_bucket_policy": "fine"}
        # the last rung: give up device residency of the binned matrix
        # and stream it from host RAM (ops/stream.py).  Only under
        # tpu_stream_mode=auto (an explicit resident pin — or an
        # already-streamed run — has nothing left to give) and only when
        # the configuration is streamable; NOT bitwise-invisible for
        # float histogram precisions (the int precisions stay bitwise —
        # int32 block sums are associative)
        if (str(config.get("tpu_stream_mode", "auto")) == "auto"
                and not stream_config_blockers(config)):
            return "stream_layout", {"tpu_stream_mode": "streamed"}
        return None

    def describe(self) -> List[str]:
        return [name for name, _ in self.steps_taken]


def note_ladder_step(site: str, step: str, overrides: Dict[str, Any],
                     recovery: bool = True) -> None:
    """One ladder descent: counters + a flight-recorder transition (the
    blackbox of a struggling run shows every step it took).

    recovery=False (preflight degrade) counts only the step — no OOM
    happened, so the recoveries counter (documented as rollback-and-
    retry events) must not tick."""
    from ..obs import REGISTRY, flightrecorder

    if recovery:
        REGISTRY.inc("lgbm_oom_recoveries_total", site=str(site),
                     help="OOM recoveries: rollbacks that descended "
                          "the degradation ladder and retried")
    REGISTRY.inc("lgbm_oom_ladder_steps_total", step=str(step),
                 help="degradation-ladder steps taken, by step name")
    flightrecorder.note("oom", "ladder_step", site=str(site), step=step,
                        **{k: str(v) for k, v in overrides.items()})
