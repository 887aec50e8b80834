"""Benchmark: Higgs-shaped GBDT training throughput on one TPU chip.

Mirrors the reference's headline benchmark (BASELINE.md: Higgs, 500 trees,
255 leaves, lr=0.1 — 238.5 s on 2x E5-2670v3, i.e. 2.096 boosting iters/s).
The real Higgs dataset cannot be fetched here (no egress), so the data is a
seeded synthetic with Higgs dimensions (1M rows x 28 dense features) and a
nonlinear separable structure; histogram/split work depends only on shape,
bins, and leaf count, so iters/sec is comparable.

A run that finds no TPU fails (non-zero exit, nothing printed) — unless the
caller exported JAX_PLATFORMS=cpu itself, in which case the whole record is
nested under the key "cpu" and no field appears under a device metric's
name.  One process: JAX is first touched here and no child is started.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import json
import os
import sys
import time

import numpy as np

N_FEATURES = 28
WARMUP_ITERS = 3
BASELINE_ITERS_PER_SEC = 500.0 / 238.5  # reference Higgs CPU (BASELINE.md)


def make_data(n, f, seed=42):
    # real data preferred when present: LIGHTGBM_TPU_BENCH_DATA points at
    # a labels-first CSV/TSV (e.g. the real HIGGS.csv) — both frameworks
    # then train on identical rows and the AUC half of the north-star
    # metric becomes directly comparable (tools/auc_parity.py)
    real = os.environ.get("LIGHTGBM_TPU_BENCH_DATA", "")
    if real:
        if not os.path.exists(real):
            raise FileNotFoundError(
                f"LIGHTGBM_TPU_BENCH_DATA={real!r} does not exist — "
                "refusing to silently fall back to synthetic data")
        # pandas' C parser is ~20x np.loadtxt and streams nrows — at
        # HIGGS scale (11M rows) loadtxt would dominate bench startup
        import pandas as pd

        raw = pd.read_csv(real, header=None, nrows=n, comment="#",
                          sep="," if real.endswith(".csv") else r"\s+",
                          dtype=np.float64).to_numpy()
        if raw.ndim != 2:
            raw = raw.reshape(1, -1)
        if raw.shape[1] < f + 1:
            raise ValueError(
                f"{real}: {raw.shape[1]} columns, need label + {f} "
                "features")
        y, X = raw[:, 0].astype(np.float64), raw[:, 1:1 + f]
        return np.ascontiguousarray(X, np.float64), y
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=(f,))
    logits = (X[:, :8] ** 2 - 1.0).sum(axis=1) * 0.3 + X @ w * 0.5
    y = (logits + rng.logistic(size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


def hist_rows_per_sec(bins_np, num_bins, precision, reps=3):
    """Histogram-kernel rows/s at `precision` over an already-binned
    matrix: times the root-histogram contraction (build_histogram_t, the
    same op the grower's hot loop runs per round) on whatever backend is
    active."""
    import jax
    from lightgbm_tpu.ops.histogram import (bench_hist_operands,
                                            build_histogram_t)

    block = min(16384, bins_np.shape[0])
    bins_tb, stats, n_use = bench_hist_operands(bins_np, precision, block)
    fn = jax.jit(lambda b, s: build_histogram_t(b, s, num_bins, precision))
    jax.block_until_ready(fn(bins_tb, stats))  # compile
    rates = []
    for _ in range(max(reps, 3)):
        t0 = time.time()
        jax.block_until_ready(fn(bins_tb, stats))
        rates.append(n_use / max(time.time() - t0, 1e-9))
    return rates


def spread(rates):
    """(median, min) of a repeat series — every timed metric reports its
    own variance (VERDICT item 7) instead of a single unqualified
    number."""
    return float(np.median(rates)), float(np.min(rates))


def run(n_rows, num_leaves, max_bin, bench_iters):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.booster import Booster

    t_data = time.time()
    X, y = make_data(n_rows, N_FEATURES)
    data_s = time.time() - t_data

    # telemetry (ISSUE 10): every timed segment below routes through the
    # metrics registry (obs.timed / phase histograms) instead of ad-hoc
    # stopwatches, so the numbers the bench prints are the numbers a
    # Prometheus scrape of the same run would see.  BENCH_TELEMETRY=
    # trace additionally writes a Chrome trace under BENCH_TRACE_DIR.
    from lightgbm_tpu import obs

    if os.environ.get("BENCH_TELEMETRY") or obs.mode() == "off":
        bench_mode = os.environ.get("BENCH_TELEMETRY", "metrics")
        if bench_mode == "off":
            # the bench READS its segment walls back from the registry,
            # so metrics is its floor — "off" would IndexError at the
            # first readback
            bench_mode = "metrics"
        obs.configure(mode=bench_mode,
                      trace_dir=os.environ.get("BENCH_TRACE_DIR") or None)

    # ingest phase split (sketch = bin finding, binning = value->bin,
    # layout = the learner's device-layout step, captured below after
    # Booster construction) — accumulated in the registry as
    # lgbm_phase_seconds_total{phase=...}
    from lightgbm_tpu.utils import timer as phase_timer

    phase_timer.reset()
    t_bin = time.time()
    ds = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    ds.construct()
    if ds._inner._ingest_bins is not None:
        # device ingest dispatches async; the honest rows/s number
        # waits for the binned matrix to actually exist
        jax.block_until_ready(ds._inner._ingest_bins)
    bin_s = time.time() - t_bin
    ingest_rows_per_sec = n_rows / max(bin_s, 1e-9)
    n_eval = min(50000, n_rows)
    X_eval = X[:n_eval].copy()
    del X

    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "max_bin": max_bin,
              # the benchmark pins its exact shape by default: no bucket
              # padding (tpu_shape_buckets trades ~1/buckets throughput for
              # compile-cache hits across DIFFERENT datasets, which a
              # fixed-shape benchmark never needs).  BENCH_SHAPE_BUCKETS=32
              # measures the shipping bucketed default instead, so the
              # configuration users actually get also has a perf record.
              "tpu_shape_buckets": int(os.environ.get(
                  "BENCH_SHAPE_BUCKETS", 0))}
    # retrace audit: every ledgered jit site records its compiled
    # programs, so the round carries n_programs beside compile_s — a
    # future PR that doubles the program zoo fails the regression note
    # loudly instead of silently inflating the compile tail
    from lightgbm_tpu.utils.compile_ledger import LEDGER

    LEDGER.enable()
    # ISSUE 12: capture each program's re-lowerable specs so the round
    # carries a per-program cost table (flops / bytes accessed; HBM
    # byte fields where a backend reports them) beside n_programs
    LEDGER.enable_capture()
    LEDGER.reset()
    from lightgbm_tpu.obs import resources

    resources.reset_phase_peaks()
    bst = Booster(params=params, train_set=ds)
    # snapshot ingest phases NOW: later valid-set constructs would
    # double-count sketch/binning
    phases = dict(phase_timer.summary())
    def _segments(tag, k=3):
        """The last k registry-recorded walls for one bench segment.
        The readback REFUSES a truncated ring shorter than the request:
        a silently under-counted repeat series would publish a median
        over the wrong repeats (ISSUE 12 satellite)."""
        samples, truncated = obs.REGISTRY.histogram_samples(
            "lgbm_timed_seconds", with_truncated=True, name=tag)
        if truncated and len(samples) < k:
            raise RuntimeError(
                f"bench segment {tag!r}: sample ring truncated below "
                f"the {k} requested repeats — raise tpu_obs_ring_samples")
        return samples[-k:]

    with obs.timed("bench/compile"):
        for _ in range(WARMUP_ITERS):
            bst.update()
        jax.block_until_ready(bst._driver.train_scores.scores)
    compile_s = _segments("bench/compile", 1)[0]
    n_programs_train = LEDGER.n_programs()

    # >=3 timed segments so the headline carries its own variance
    # (median beside min); segments hold >=2 iters so the per-segment
    # sync doesn't serialize every single dispatch
    seg_iters = max(round(bench_iters / 3), 2)
    for _ in range(3):
        with obs.timed("bench/train_segment"):
            for _ in range(seg_iters):
                bst.update()
            jax.block_until_ready(bst._driver.train_scores.scores)
    seg_walls = _segments("bench/train_segment")
    seg_rates = [seg_iters / max(w, 1e-9) for w in seg_walls]
    train_s = sum(seg_walls)
    bench_iters = 3 * seg_iters
    iters_per_sec, iters_per_sec_min = spread(seg_rates)
    # snapshot the TRAIN peak NOW: peak_bytes_in_use is a process-
    # lifetime high-water mark with no reset, so reading it after the
    # predict/serve sections would attribute their peaks to training
    train_peak_hbm_bytes = resources.peak_hbm_bytes()

    # prediction throughput: full-forest raw predict rows/s on the path
    # the configuration would actually use (device bin-space traversal on
    # TPU, native walker otherwise)
    bst.predict(X_eval, raw_score=True)  # warm (pack + compile)
    for _ in range(3):
        with obs.timed("bench/predict"):
            bst.predict(X_eval, raw_score=True)
    pred_rates = [n_eval / max(w, 1e-9) for w in _segments("bench/predict")]
    predict_rows_per_sec, predict_rows_per_sec_min = spread(pred_rates)
    # sanity AUC BEFORE the eval-overhead block: its extra update() calls
    # would otherwise make the recorded train_auc describe a model
    # trained more than bench_iters iterations
    pred = bst.predict(X_eval)

    # serving throughput: closed-loop hammer through the registry +
    # micro-batcher (lightgbm_tpu/serving) over the same booster —
    # measures the path a long-lived inference service actually runs
    # (warmup'd row buckets, coalesced launches), not bare predict
    from lightgbm_tpu.serving import ServingSession

    serve_rows = min(4096, n_eval)
    serve_threads, serve_reqs = 4, 8
    sess = ServingSession(params={
        "serving_max_batch_rows": serve_rows, "verbosity": -1})
    sess.load("bench", booster=bst)  # packs + warms every row bucket
    Xs = X_eval[:serve_rows]

    serve_errors = []

    def _hammer():
        try:
            for _ in range(serve_reqs):
                sess.predict("bench", Xs, raw_score=True)
        except Exception as exc:  # surfaced below: a dead thread must
            serve_errors.append(exc)  # not silently inflate the number

    import threading as _threading

    serve_rates = []
    for _ in range(3):
        workers = [_threading.Thread(target=_hammer)
                   for _ in range(serve_threads)]
        t_serve = time.time()
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        serve_s = max(time.time() - t_serve, 1e-9)
        if serve_errors:
            raise serve_errors[0]
        serve_rates.append(serve_threads * serve_reqs * serve_rows
                           / serve_s)
    serve_rows_per_sec, serve_rows_per_sec_min = spread(serve_rates)
    serve_p99_ms = sess.stats()["latency_p99_ms"]
    # ISSUE 12: what this model costs resident in the registry — the
    # packed device-table bytes the serve_model_hbm_bytes gauge tracks
    serve_model_hbm_bytes = int(sess.registry.resolve("bench").hbm_bytes)

    # drift-monitor overhead (ISSUE 14): the same entry-level predict
    # loop with the sampled drift accumulator enabled vs disabled, one
    # scrape (absorb + PSI/JS) amortized per window — the number the
    # <1% telemetry gate bounds for the OFF configuration, published so
    # bench_diff can watch the ON cost too.  min-of-3 windows per arm
    # to wash container stalls
    entry = sess.registry.resolve("bench")
    drift_reps = 10
    Xd = X_eval[:min(512, serve_rows)]

    def _drift_wall():
        t0 = time.time()
        for _ in range(drift_reps):
            entry.predict(Xd, raw_score=True)
        if entry.drift is not None:
            entry.drift.snapshot()
        return time.time() - t0

    entry.predict(Xd, raw_score=True)  # warm
    monitor, entry.drift = entry.drift, None
    off_wall = min(_drift_wall() for _ in range(3))
    entry.drift = monitor
    on_wall = min(_drift_wall() for _ in range(3))
    # clamped at 0: a negative measurement is container noise, and
    # bench_diff's relative gate needs a sane baseline sign
    drift_overhead_pct = max(100.0 * (on_wall - off_wall)
                             / max(off_wall, 1e-9), 0.0)
    sess.close()

    # overload-ramp goodput (ISSUE 11): paced open-loop load at ~4x the
    # closed-loop rate above, smaller requests so admission/batching do
    # real work — serve_goodput_rows_per_sec is the accepted-rows
    # throughput UNDER overload (sheds absorbing the excess), and
    # serve_shed_pct the fraction refused with 429/503/504 instead of
    # queueing into timeout collapse
    import importlib.util as _ilu

    _sb_spec = _ilu.spec_from_file_location(
        "_serve_bench", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools", "serve_bench.py"))
    _sb = _ilu.module_from_spec(_sb_spec)
    _sb_spec.loader.exec_module(_sb)
    ramp_rows = min(256, serve_rows)
    sess2 = ServingSession(params={
        "serving_max_batch_rows": serve_rows, "verbosity": -1})
    sess2.load("bench", booster=bst)
    ramp_qps = 4.0 * serve_rows_per_sec / max(ramp_rows, 1)
    r_ok, r_shed, r_err, r_dt = _sb.run_paced_counted(
        sess2, "bench", X_eval[:ramp_rows], ramp_rows, serve_threads,
        ramp_qps, 4.0,
        deadline_ms=4.0 * float(sess2.config.serving_slo_ms))
    if r_err:
        raise RuntimeError(f"serve ramp surfaced {r_err} errors to "
                           "accepted requests")
    offered = max(r_ok + r_shed + r_err, 1)
    serve_goodput_rows_per_sec = r_ok * ramp_rows / max(r_dt, 1e-9)
    serve_shed_pct = 100.0 * r_shed / offered
    sess2.close()

    # per-iteration valid-eval overhead the training loop pays when early
    # stopping is on: LIVE update+eval iterations (per-tree valid scoring
    # + materialize + metric fetch) minus the plain training it/s above —
    # timing eval_valid() alone after training would miss the incremental
    # device tree-scoring this path exists to speed up
    vd = ds.create_valid(X_eval, label=y[:n_eval])
    bst.add_valid(vd, "valid")
    bst.update()
    bst.eval_valid()  # warm (replay + compile)
    jax.block_until_ready(bst._driver.train_scores.scores)
    eval_walls = []
    for _ in range(3):
        t_eval = time.time()
        bst.update()
        bst.eval_valid()
        jax.block_until_ready(bst._driver.train_scores.scores)
        eval_walls.append(time.time() - t_eval)
    eval_med, _ = spread(eval_walls)
    eval_ms_per_iter = max(eval_med - train_s / bench_iters, 0.0) * 1e3

    # robustness cost (ISSUE 7): interval-checkpointed training vs plain
    # training over equal segments -> checkpoint_overhead_pct, plus the
    # wall to rebuild a training booster from the newest bundle
    # (resume_s) — tracked beside the perf metrics so fault tolerance
    # never silently taxes the hot loop
    import shutil as _shutil
    import tempfile as _tempfile

    from lightgbm_tpu.utils.checkpoint import (CheckpointManager,
                                               restore_checkpoint,
                                               save_checkpoint)

    ck_iters = max(seg_iters, 2)
    t0 = time.time()
    for _ in range(ck_iters):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    plain_s = max(time.time() - t0, 1e-9)
    ck_dir = _tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        manager = CheckpointManager(ck_dir, keep=2)
        t0 = time.time()
        for _ in range(ck_iters):
            bst.update()
            save_checkpoint(bst, manager)
        jax.block_until_ready(bst._driver.train_scores.scores)
        ck_s = max(time.time() - t0, 1e-9)
        checkpoint_overhead_pct = max(ck_s - plain_s, 0.0) / plain_s * 100.0
        t0 = time.time()
        bst_resumed = Booster(params=params, train_set=ds)
        restore_checkpoint(bst_resumed, manager)
        resume_s = time.time() - t0
        del bst_resumed

        # ISSUE 8: elastic resume — the same bundle restored onto a
        # DIFFERENT shard topology (2-way data mesh when the backend has
        # the devices; degenerates to same-topology resume on 1 device,
        # still timing the elastic validation path)
        p_el = dict(params)
        if len(jax.devices()) >= 2:
            p_el.update(tree_learner="data", num_machines=2)
        t0 = time.time()
        bst_el = Booster(params=p_el, train_set=ds)
        restore_checkpoint(bst_el, manager)
        resume_elastic_s = time.time() - t0
        del bst_el

        # ISSUE 8: watchdog recovery — injected collective hang ->
        # structured timeout -> final-checkpoint flush -> rebuild +
        # resume + one boosting iteration (the full degrade-and-recover
        # cycle a hung peer costs)
        from lightgbm_tpu.parallel.collective import CollectiveTimeout
        from lightgbm_tpu.parallel.metric_sync import sync_sums
        from lightgbm_tpu.utils import faultline as _faultline
        from lightgbm_tpu.utils.checkpoint import flush_checkpoint

        _faultline.reset()
        _faultline.arm("collective_sync", action="hang")
        t0 = time.time()
        try:
            sync_sums([1.0])
        except CollectiveTimeout:
            pass
        _faultline.reset()
        flush_checkpoint(bst, manager)
        bst_rec = Booster(params=params, train_set=ds)
        restore_checkpoint(bst_rec, manager)
        bst_rec.update()
        jax.block_until_ready(bst_rec._driver.train_scores.scores)
        collective_timeout_recovery_s = time.time() - t0
        del bst_rec
    finally:
        _shutil.rmtree(ck_dir, ignore_errors=True)

    # ISSUE 15: OOM recovery — injected RESOURCE_EXHAUSTED at the next
    # guarded train-step allocation -> atomic rollback -> one
    # degradation-ladder step -> settled completion, timed end to end.
    # Classification keys on the error SHAPE, which the injection
    # reproduces, so the number is real on every backend
    from lightgbm_tpu.utils import faultline as _fl
    from lightgbm_tpu.utils import membudget as _membudget

    _fl.reset()
    t0 = time.time()
    _fl.arm("device_alloc", action="oom", at=1)
    bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    oom_recovery_s = time.time() - t0
    _fl.reset()

    # headroom between the enforced HBM budget and the observed train
    # peak (null on CPU like the other memory_stats-derived fields: no
    # capacity report means no budget resolves)
    _budget = _membudget.budget_bytes(bst._driver.config)
    hbm_budget_headroom_bytes = (
        None if _budget is None or train_peak_hbm_bytes is None
        else int(_budget) - int(train_peak_hbm_bytes))

    # ISSUE 16: out-of-core streaming — rows-beyond-HBM scaling curve.
    # Train the streamed layout on 1x/2x/4x of a base row count with the
    # SAME stream block size throughout: the 1x point stands in for "at
    # the resident cap", 2x/4x are datasets the resident layout could
    # not hold.  stream_rows_per_sec is the 4x point (the headline
    # out-of-core number); stream_overlap_pct is the fraction of the
    # estimated H2D copy wall hidden behind histogram contractions,
    # accumulated across every timed tree
    stream_base = max(min(n_rows // 4, 65_536), 8192)
    stream_iters = 2
    stream_scaling = {}
    stream_overlap_est = stream_overlap_hidden = 0.0
    stream_rows_per_sec = 0.0
    X_st, y_st = make_data(4 * stream_base, N_FEATURES, seed=7)
    for scale in (1, 2, 4):
        ns = stream_base * scale
        p_st = {"objective": "binary", "num_leaves": num_leaves,
                "max_bin": max_bin, "verbosity": -1,
                "tpu_stream_mode": "streamed",
                "tpu_stream_block_rows": max(stream_base // 2, 4096)}
        ds_st = lgb.Dataset(X_st[:ns], label=y_st[:ns], params=p_st)
        bst_st = Booster(params=p_st, train_set=ds_st)
        bst_st.update()                         # warm compiles
        wall = 0.0
        for _ in range(stream_iters):
            bst_st.update()
            s = bst_st._driver.learner.stream_stats
            wall += s["tree_wall_s"]
            stream_overlap_est += s["copy_est_s"]
            stream_overlap_hidden += (s["overlap_pct"] / 100.0
                                      * s["copy_est_s"])
        stream_scaling[f"{scale}x"] = round(
            ns * stream_iters / max(wall, 1e-9), 0)
        stream_rows_per_sec = stream_scaling[f"{scale}x"]
        del bst_st, ds_st
    del X_st, y_st
    stream_overlap_pct = (100.0 * stream_overlap_hidden
                          / max(stream_overlap_est, 1e-12))

    # histogram-kernel throughput at the quantized vs shipping precision:
    # rows bounded so the probe stays a footnote next to the training loop
    hist_rows = min(n_rows, 262144)
    hist_bins = bst._driver.learner.num_bins
    bins_np = np.asarray(ds._inner.bins[:hist_rows])
    hist_int8, hist_int8_min = spread(
        hist_rows_per_sec(bins_np, hist_bins, "int8"))
    hist_hilo, hist_hilo_min = spread(
        hist_rows_per_sec(bins_np, hist_bins, "hilo"))
    n_programs = LEDGER.n_programs()
    ledger_sites = {a["site"]: a["programs"] for a in LEDGER.report()}

    # ISSUE 12: resource accounting — peak device bytes (None on CPU:
    # the backend reports no memory_stats, and a null beats a fiction),
    # phase watermarks, and the per-program static cost table (flops /
    # bytes-accessed everywhere; HBM byte fields where the backend
    # reports, i.e. auto-skipped on CPU)
    res = resources.bench_resource_metrics(
        LEDGER, train_peak=train_peak_hbm_bytes)

    # sanity: the model must actually learn (pred captured above, at
    # exactly bench_iters + warmup iterations)
    from lightgbm_tpu.models.metrics import AUCMetric
    from lightgbm_tpu.config import Config
    m = AUCMetric(Config())

    class _MD:
        label = y[:n_eval].astype(np.float32)
        weight = None
    m.init(_MD, n_eval)
    eps = 1e-9
    margin = (np.log(np.clip(pred, eps, 1 - eps))
              - np.log(np.clip(1 - pred, eps, 1 - eps)))
    auc = m.eval(margin[None, :], None)

    out = {
        "metric": "higgs1m_boosting_iters_per_sec",
        "value": round(iters_per_sec, 3),
        "unit": f"iters/s ({n_rows} rows, 28 feats, {num_leaves} leaves, "
                f"{max_bin} bins)",
        # a ratio against the full-size baseline is only honest on the
        # baseline's own problem shape (Higgs-1M, 255 leaves, 255 bins)
        "vs_baseline": (round(iters_per_sec / BASELINE_ITERS_PER_SEC, 3)
                        if (n_rows >= 1_000_000 and num_leaves == 255
                            and max_bin == 255) else 0.0),
        "train_auc": round(float(auc), 4),
        # every timed metric: median of >=3 repeats, worst repeat beside
        # it (the _min twin) so each record carries its own variance
        "timing_repeats": 3,
        "iters_per_sec_min": round(iters_per_sec_min, 3),
        "predict_rows_per_sec": round(predict_rows_per_sec, 0),
        "predict_rows_per_sec_min": round(predict_rows_per_sec_min, 0),
        "serve_rows_per_sec": round(serve_rows_per_sec, 0),
        "serve_rows_per_sec_min": round(serve_rows_per_sec_min, 0),
        "serve_p99_ms": round(serve_p99_ms, 1),
        "serve_goodput_rows_per_sec": round(serve_goodput_rows_per_sec, 0),
        "serve_shed_pct": round(serve_shed_pct, 1),
        "drift_overhead_pct": round(drift_overhead_pct, 1),
        "eval_ms_per_iter": round(eval_ms_per_iter, 1),
        "checkpoint_overhead_pct": round(checkpoint_overhead_pct, 2),
        "resume_s": round(resume_s, 2),
        "resume_elastic_s": round(resume_elastic_s, 2),
        "collective_timeout_recovery_s": round(
            collective_timeout_recovery_s, 2),
        # ISSUE 15: injected mid-train OOM -> settled completion wall,
        # and budget-vs-peak headroom (null on CPU, no budget resolves)
        "oom_recovery_s": round(oom_recovery_s, 2),
        "hbm_budget_headroom_bytes": hbm_budget_headroom_bytes,
        "hist_int8_rows_per_sec": round(hist_int8, 0),
        "hist_int8_rows_per_sec_min": round(hist_int8_min, 0),
        "hist_hilo_rows_per_sec": round(hist_hilo, 0),
        "hist_hilo_rows_per_sec_min": round(hist_hilo_min, 0),
        # ISSUE 18: per-iteration grow wall
        "grow_iter_ms": round(1000.0 * train_s / max(bench_iters, 1), 2),
        "ingest_rows_per_sec": round(ingest_rows_per_sec, 0),
        # ISSUE 16: out-of-core streaming — throughput at 4x the base
        # row count, overlap achieved, and the full scaling curve
        "stream_rows_per_sec": stream_rows_per_sec,
        "stream_overlap_pct": round(stream_overlap_pct, 1),
        "stream_scaling_rows_per_sec": stream_scaling,
        "bench_iters": bench_iters,
        "data_gen_s": round(data_s, 1),
        "binning_s": round(bin_s, 1),
        "sketch_s": round(phases.get("sketch", 0.0), 2),
        "bin_s": round(phases.get("binning", 0.0), 2),
        "layout_s": round(phases.get("layout", 0.0), 2),
        "compile_s": round(compile_s, 1),
        # compiled XLA programs recorded by the ledgered jit sites: the
        # train+warmup lifecycle count, then the whole round (predict +
        # serve shapes included)
        "n_programs_train": n_programs_train,
        "n_programs": n_programs,
        "ledger_sites": ledger_sites,
        # ISSUE 12: device memory/cost accounting.  Fields derived from
        # device memory_stats (train/phase peaks, program memory bytes)
        # are explicitly null on CPU — "not measurable here", not
        # "missing"; serve_model_hbm_bytes (packed-table bytes on
        # whatever backend holds them — host RAM on CPU) and the cost
        # table's flops/bytes_accessed are real numbers everywhere.
        # Train peak snapshotted right after the train segments, before
        # predict/serve could raise the process high-water mark
        "train_peak_hbm_bytes": res["train_peak_hbm_bytes"],
        "phase_peak_hbm_bytes": res["phase_peak_hbm_bytes"],
        "serve_model_hbm_bytes": serve_model_hbm_bytes,
        "program_costs": res["program_costs"],
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
    if params["tpu_shape_buckets"]:
        out["tpu_shape_buckets"] = params["tpu_shape_buckets"]
    if out["platform"] != "tpu":
        # not a device measurement: keep every field out from under the
        # device metrics' names
        out = {"platform": out["platform"], out["platform"]: out}
    if obs.tracing_on():
        obs.write_chrome_trace()
        obs.flush()
    print(json.dumps(out))


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    # JAX's own behaviour when libtpu fails to initialise is to warn and
    # continue on CPU, so the platform is checked before any work
    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get(
            "JAX_PLATFORMS", "").strip().lower() != "cpu":
        sys.exit(f"bench.py: no TPU (jax platform is {platform!r}); export "
                 "JAX_PLATFORMS=cpu yourself for a CPU run")
    run(int(os.environ.get("BENCH_ROWS", 1_000_000)),
        int(os.environ.get("BENCH_LEAVES", 255)),
        int(os.environ.get("BENCH_BINS", 255)),
        int(os.environ.get("BENCH_ITERS", 25)))


if __name__ == "__main__":
    main()
