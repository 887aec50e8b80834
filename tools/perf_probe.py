"""TPU perf sweep: histogram impl x split batch x block size.

Run on the real chip when tuning the grower:
    python tools/perf_probe.py                  # default sweep
    K=25 BLOCK=8192 IMPL=pallas2 N=1000000 python tools/perf_probe.py one

Reports ms/tree and train AUC for each configuration at the bench shape
(Higgs-1M: 28 features, 255 leaves, 255 bins), so quality regressions
from batching show up next to the throughput numbers.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_data(n, f=28, seed=42):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=(f,))
    logits = (X[:, :8] ** 2 - 1.0).sum(axis=1) * 0.3 + X @ w * 0.5
    y = (logits + rng.logistic(size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


_DS_CACHE = {}


def _exc_inline(exc, limit=400):
    """One-line failure description for keep-going sweeps.

    The old truncation (`str(exc)[:120]`) routinely cut a jax trace-time
    error before the part that names the failing primitive, and NEVER
    showed the `__cause__` chain — a Mosaic lowering rejection surfaces
    as a generic XlaRuntimeError whose cause carries the real story.
    Keep the exception CLASS of every link in the chain plus the first
    line of each message."""
    parts = []
    seen = set()
    e = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        msg = str(e).strip()
        first = msg.splitlines()[0] if msg else ""
        parts.append(f"{type(e).__name__}: {first}" if first
                     else type(e).__name__)
        e = e.__cause__
    return " <- ".join(parts)[:limit]


def run_one(X, y, k, block, impl, iters=8, leaves=255, bins=255,
            partition="select", precision="hilo", ramp=False, alpha=0.0):
    import lightgbm_tpu as lgb
    import jax
    from sklearn.metrics import roc_auc_score

    # bin once per (data, label, bins): sweep iterations reuse the Dataset
    ds_key = (id(X), id(y), bins)
    if ds_key not in _DS_CACHE:
        _DS_CACHE[ds_key] = lgb.Dataset(X, label=y, params={"max_bin": bins})
    ds = _DS_CACHE[ds_key]
    bst = lgb.Booster(params={
        "objective": "binary", "num_leaves": leaves, "learning_rate": 0.1,
        "min_data_in_leaf": 20, "max_bin": bins, "tpu_split_batch": k,
        "tpu_block_rows": block, "tpu_hist_impl": impl,
        "tpu_partition_impl": partition,
        "tpu_hist_precision": precision,
        "tpu_split_batch_alpha": alpha,
        # exact shapes: sweep numbers must stay byte-comparable with the
        # round-3 3.14 it/s record and bench.py's pinned configuration
        "tpu_shape_buckets": 0,
        "tpu_ramp": ramp}, train_set=ds)
    t0 = time.time()
    bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    ms = (time.time() - t0) / iters * 1e3
    auc = roc_auc_score(y, bst.predict(X, raw_score=True))
    return ms, compile_s, auc


def sweep(X, y, configs, iters=6, reraise=False):
    """Run a list of config dicts through run_one, printing one line each.

    reraise=True (the single-config "one" mode) propagates failures with
    the full traceback instead of the sweep's keep-going truncation.
    """
    for cfg in configs:
        label = " ".join(f"{k}={v}" for k, v in cfg.items())
        try:
            ms, cs, auc = run_one(X, y, cfg.get("k", 25),
                                  cfg.get("block", 16384),
                                  cfg.get("impl", "xla"), iters=iters,
                                  partition=cfg.get("part", "select"),
                                  precision=cfg.get("prec", "hilo"),
                                  ramp=cfg.get("ramp", False),
                                  alpha=cfg.get("alpha", 0.0))
            print(f"{label}: {ms:6.0f} ms/tree ({1000/ms:5.2f} it/s) "
                  f"compile {cs:5.0f}s auc {auc:.4f}", flush=True)
        except Exception as exc:
            if reraise:
                raise
            print(f"{label}: FAILED {_exc_inline(exc)}", flush=True)


def run_predict_sweep(X, y, rounds=50, leaves=255, bins=255):
    """Prediction-throughput sweep: full-forest raw predict rows/s for
    the device bin-space predictor across row-chunk sizes, next to the
    native walker and the per-iteration valid-eval overhead.

        N=1000000 ROUNDS=50 python tools/perf_probe.py predict
    """
    import lightgbm_tpu as lgb

    ds = lgb.Dataset(X, label=y, params={"max_bin": bins})
    bst = lgb.Booster(params={
        "objective": "binary", "num_leaves": leaves, "learning_rate": 0.1,
        "max_bin": bins, "tpu_shape_buckets": 0,
        "tpu_predict_device": "true"}, train_set=ds)
    t0 = time.time()
    for _ in range(rounds):
        bst.update()
    bst._driver._materialize()
    print(f"trained {rounds} iters in {time.time() - t0:.0f}s "
          f"({bst.num_trees()} trees)", flush=True)
    n = X.shape[0]

    def timed(fn, reps=3):
        fn()  # warm (compile + pack)
        t = time.time()
        for _ in range(reps):
            fn()
        return (time.time() - t) / reps

    # device='cpu' pins the baseline to the native OMP walker — with
    # tpu_predict_device='true' an unqualified predict would route the
    # device path and the comparison would measure it against itself
    s = timed(lambda: bst.predict(X, raw_score=True, device="cpu"))
    print(f"native walker:           {n / s:12.0f} rows/s", flush=True)
    for chunk in (8192, 32768, 65536, 131072, 262144):
        bst.params["tpu_predict_chunk_rows"] = chunk
        # predict_raw_device reads the DRIVER's config (frozen at Booster
        # construction), not the handle's params dict
        bst._driver.config.params["tpu_predict_chunk_rows"] = chunk
        s = timed(lambda: bst.predict(X, raw_score=True, device="tpu"))
        print(f"device chunk={chunk:<7d}     {n / s:12.0f} rows/s",
              flush=True)
    # per-iteration eval overhead: LIVE update+eval iterations (the
    # incremental device tree-score pass + materialize + metric fetch)
    # against plain update iterations — a post-training eval_valid()
    # would only time the score fetch
    import jax

    def train_loop(with_eval, iters=3):
        t = time.time()
        for _ in range(iters):
            bst.update()
            if with_eval:
                bst.eval_valid()
        bst._driver._materialize()
        jax.block_until_ready(bst._driver.train_scores.scores)
        return (time.time() - t) / iters

    n_eval = min(50_000, n)
    # baseline BEFORE the valid set attaches: once added, every update's
    # materialize pays the per-tree valid scoring, which belongs on the
    # with_eval side of the subtraction
    bst.update()  # warm
    base = train_loop(False)
    vd = ds.create_valid(X[:n_eval].copy(), label=y[:n_eval])
    bst.add_valid(vd, "valid")
    bst.update()
    bst.eval_valid()  # warm the replay + eval compiles
    with_eval = train_loop(True)
    print(f"valid eval ({n_eval} rows): "
          f"{max(with_eval - base, 0.0) * 1e3:8.1f} ms/iter overhead "
          f"(train {base * 1e3:.0f} -> train+eval {with_eval * 1e3:.0f})",
          flush=True)


def run_hist_sweep(X, y, bins=255, reps=4):
    """Histogram-kernel rows/s sweep: precision (hilo/f32/int16/int8) x
    impl (xla/pallas2) x block size, on the grower's own batched
    contraction (build_histogram_batched_t, K=25 slots), plus the
    auto-selection table `tpu_hist_impl=auto` would pick per precision.

        N=1000000 python tools/perf_probe.py hist
    """
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.learner import TPUTreeLearner
    from lightgbm_tpu.ops.histogram import (bench_hist_operands,
                                            build_histogram_batched_t)

    on_tpu = jax.devices()[0].platform == "tpu"
    ds = lgb.Dataset(X, label=y, params={"max_bin": bins})
    ds.construct()
    bins_np = np.asarray(ds._inner.bins)
    n_all, F = bins_np.shape
    B = bins + 1
    K = 25
    rng = np.random.default_rng(0)

    def one(precision, impl, block):
        # pallas off-TPU runs the interpreter — cap the rows handed to
        # the helper at ONE block so the sweep finishes; the printed
        # rows/s is still labeled per-config
        n_cap = n_all if (on_tpu or impl == "xla") \
            else min(n_all, max(4096, block))
        if n_cap < block:
            raise ValueError(f"need >= {block} rows, have {n_cap}")
        bins_tb, stats, n_use = bench_hist_operands(
            bins_np[:n_cap], precision, block)
        nb = n_use // block
        leaf_b = jnp.asarray(
            rng.integers(0, K, size=n_use).astype(np.int32)
            .reshape(nb, block))
        slots = jnp.arange(K, dtype=jnp.int32)
        fn = jax.jit(lambda b, s, l: build_histogram_batched_t(
            b, s, l, slots, B, precision, impl=impl))
        jax.block_until_ready(fn(bins_tb, stats, leaf_b))  # compile
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn(bins_tb, stats, leaf_b))
        return n_use * reps / max(time.time() - t0, 1e-9), n_use

    blocks = {"xla": (8192, 16384), "pallas2": (4096, 8192)}
    for precision in ("hilo", "f32", "int16", "int8"):
        for impl in ("xla", "pallas2"):
            for block in blocks[impl]:
                label = f"prec={precision:<5s} impl={impl:<7s} block={block}"
                try:
                    rps, n_use = one(precision, impl, block)
                    print(f"{label}: {rps:14.0f} rows/s ({n_use} rows)",
                          flush=True)
                except Exception as exc:
                    print(f"{label}: FAILED {_exc_inline(exc)}", flush=True)

    print("\nauto-selection (tpu_hist_impl=auto on this backend):",
          flush=True)
    for precision in ("hilo", "f32", "int16", "int8"):
        cfg = Config({"objective": "binary", "num_leaves": 255,
                      "max_bin": bins, "tpu_hist_precision": precision})
        impl, block = TPUTreeLearner._resolve_hist_impl(cfg, B, precision)
        print(f"  {precision:<5s} -> impl={impl} block={block}", flush=True)


def run_ingest_sweep(X, y, bins=255):
    """Ingest-throughput sweep: Dataset construct rows/s for the host
    binning path next to the device kernel across chunk sizes, with the
    sketch (bin finding) phase split out.

        N=1000000 python tools/perf_probe.py ingest
    """
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import timer as phase_timer

    n = X.shape[0]

    def once(mode, chunk):
        phase_timer.enable(True)
        phase_timer.reset()
        t0 = time.time()
        ds = lgb.Dataset(X, label=y, params={
            "max_bin": bins, "tpu_ingest_device": mode,
            "tpu_ingest_chunk_rows": chunk})
        ds.construct()
        if ds._inner._ingest_bins is not None:
            jax.block_until_ready(ds._inner._ingest_bins)
        wall = time.time() - t0
        ph = dict(phase_timer.summary())
        phase_timer.enable(False)
        return wall, ph.get("sketch", 0.0), ph.get("binning", 0.0)

    s, sk, bn = once("false", 65536)
    print(f"host binning:            {n / s:12.0f} rows/s "
          f"(sketch {sk:5.2f}s bin {bn:5.2f}s)", flush=True)
    for chunk in (16384, 32768, 65536, 131072, 262144):
        s, sk, bn = once("true", chunk)
        print(f"device chunk={chunk:<7d}    {n / s:12.0f} rows/s "
              f"(sketch {sk:5.2f}s bin {bn:5.2f}s)", flush=True)


def run_comm_sweep(shard_counts, reps=10, host_counts=(1,)):
    """Histogram-aggregation sweep: psum (all-reduce) vs psum_scatter
    (reduce-scatter) wall time over (hosts, shards, F, B, K, precision),
    with the predicted per-shard receive bytes split into ICI and DCN
    legs printed next to the measured wall so the scatter win stays
    legible even on the CPU container (where the "collective" is a
    memcpy and the wall mostly tracks bytes touched).  The collectives
    ride the unified (hosts, data, feature) topology — `axis_psum` /
    `axis_psum_scatter` over the ROW_AXES pair, exactly the grower's
    aggregation path — so the sweep measures what training runs.  The
    hierarchical ring model (parallel/mesh.py tiered_* helpers) splits
    the receive bytes: the intra-host ring moves full-payload legs over
    ICI while the cross-host ring moves 1/d-sized legs over DCN; total
    scatter bytes equal the flat ring at every (h, d) factorization, so
    growing the hosts axis re-labels legs without adding traffic.  The
    array is the grower's aggregation payload: the [K, F, B, 3]
    smaller-child histograms in the accumulation dtype (int32 for
    int8/int16, f32 for hilo/f32).

        SHARDS=2,4,8 HOSTS=1,2 python tools/perf_probe.py comm
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel.mesh import (tiered_allreduce_recv_bytes,
                                            tiered_reduce_scatter_recv_bytes)
    from jax import shard_map
    from lightgbm_tpu.parallel.topology import (ROW_AXES, axis_psum,
                                                axis_psum_scatter,
                                                make_topology)

    devices = jax.devices()
    rng = np.random.default_rng(0)
    print(f"{len(devices)} {devices[0].platform} devices; per-shard "
          "receive bytes predicted by the tiered ring cost model "
          "(parallel/mesh.py): ICI = intra-host ring over full payload, "
          "DCN = cross-host ring over the 1/devices-per-host slice",
          flush=True)
    header = (f"{'hosts':>5s} {'shards':>6s} {'F':>5s} {'B':>4s} {'K':>3s} "
              f"{'prec':>5s} {'payload':>9s} "
              f"{'psum ICI':>9s} {'psum DCN':>9s} "
              f"{'scat ICI':>9s} {'scat DCN':>9s} "
              f"{'psum ms':>8s} {'scatter ms':>10s} {'ratio':>6s}")
    print(header, flush=True)
    for hosts in host_counts:
        for p in shard_counts:
            if p > len(devices):
                print(f"{hosts:5d} {p:6d}  SKIP (only {len(devices)} "
                      "devices)", flush=True)
                continue
            if p % hosts != 0:
                print(f"{hosts:5d} {p:6d}  SKIP ({p} shards not divisible "
                      f"by {hosts} hosts)", flush=True)
                continue
            d_local = p // hosts
            topo = make_topology(num_data_shards=p, num_feature_shards=1,
                                 num_hosts=hosts, devices=devices)
            mesh = topo.mesh
            for F, B, K in ((32, 64, 16), (32, 256, 25), (256, 256, 25)):
                # pad F to the shard count like the learner does
                Fp = -(-F // p) * p
                for prec in ("int8", "hilo"):
                    dt = (jnp.int32 if prec in ("int8", "int16")
                          else jnp.float32)
                    h = jnp.asarray(
                        rng.integers(0, 1000, size=(K, Fp, B, 3)), dtype=dt)
                    nbytes = h.size * h.dtype.itemsize

                    def f_psum(x):
                        return axis_psum(x, ROW_AXES)

                    def f_scat(x):
                        return axis_psum_scatter(x, ROW_AXES,
                                                 scatter_dimension=1,
                                                 tiled=True)

                    fns = {}
                    fns["psum"] = jax.jit(shard_map(
                        f_psum, mesh=mesh, in_specs=P(), out_specs=P(),
                        check_vma=False))
                    fns["scatter"] = jax.jit(shard_map(
                        f_scat, mesh=mesh, in_specs=P(),
                        out_specs=P(None, ROW_AXES), check_vma=False))
                    walls = {}
                    for name, fn in fns.items():
                        jax.block_until_ready(fn(h))  # compile
                        t0 = time.time()
                        for _ in range(reps):
                            out = fn(h)
                        jax.block_until_ready(out)
                        walls[name] = (time.time() - t0) / reps * 1e3
                    ar_ici, ar_dcn = tiered_allreduce_recv_bytes(
                        nbytes, hosts, d_local)
                    rs_ici, rs_dcn = tiered_reduce_scatter_recv_bytes(
                        nbytes, hosts, d_local)
                    mb = 1.0 / (1024 * 1024)
                    print(f"{hosts:5d} {p:6d} {Fp:5d} {B:4d} {K:3d} "
                          f"{prec:>5s} {nbytes * mb:8.1f}M "
                          f"{ar_ici * mb:8.1f}M {ar_dcn * mb:8.1f}M "
                          f"{rs_ici * mb:8.1f}M {rs_dcn * mb:8.1f}M "
                          f"{walls['psum']:8.2f} {walls['scatter']:10.2f} "
                          f"{walls['psum'] / max(walls['scatter'], 1e-9):6.2f}",
                          flush=True)


def run_retrace(n=20000, f=10, leaves=31, bins=63, iters=3):
    """Retrace audit: run a canonical train + retrain + predict + serve
    lifecycle with the CompileLedger enabled and print, per phase, how
    many XLA programs were compiled and where (per-site breakdown with
    call signatures) — the tool that attributes compile_s growth to the
    jit site/mode variant that caused it.

        N=20000 python tools/perf_probe.py retrace
    """
    import lightgbm_tpu as lgb
    from lightgbm_tpu.booster import Booster
    from lightgbm_tpu.serving import ServingSession
    import jax
    from lightgbm_tpu.utils.compile_ledger import LEDGER

    X, y = make_data(n, f=f)
    p = {"objective": "binary", "num_leaves": leaves, "max_bin": bins,
         "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1}
    LEDGER.enable()
    LEDGER.reset()
    phases = []

    def phase(label):
        phases.append((label, LEDGER.n_programs()))

    ds = lgb.Dataset(X, label=y, params=p)
    bst = Booster(params=p, train_set=ds)
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    phase(f"ingest + train ({iters} iters)")

    # the retrace-elimination contract: an identical second training run
    # reuses every cached executable but the Booster's own learner.pre /
    # learner.post (closures of the Booster, one pair each; the labels
    # are their arguments) —
    # any OTHER program compiled here is a regression (a jit site keyed
    # on a fresh closure or static value)
    ds2 = lgb.Dataset(X, label=y, params=p)
    bst2 = Booster(params=p, train_set=ds2)
    for _ in range(iters):
        bst2.update()
    jax.block_until_ready(bst2._driver.train_scores.scores)
    phase("second identical train")

    for sz in (1, 100, 4096, min(n, 20000)):
        # tpu_predict_device pinned: 'auto' on a CPU host vetoes to the
        # native walker and the sweep would audit zero device launches
        bst.predict(X[:sz], raw_score=True, device="tpu",
                    tpu_predict_device="true")
    phase("predict sweep (1..n rows)")

    sess = ServingSession(params={"serving_max_batch_rows": 4096,
                                  "verbosity": -1})
    sess.load("a", booster=bst)
    sess.load("b", booster=bst2)  # same-shaped: must add ZERO programs
    sess.predict("a", X[:100])
    sess.predict("b", X[:100])
    sess.close()
    phase("serve (2 same-shaped models)")

    prev = 0
    print(f"{'phase':<36s} {'new programs':>12s}")
    for label, count in phases:
        print(f"{label:<36s} {count - prev:>12d}", flush=True)
        prev = count
    print()
    print(LEDGER.format_report(), flush=True)
    if os.environ.get("RETRACE_SIGNATURES"):
        for prog in LEDGER.programs():
            print(f"  {prog['site']:<24s} {prog['first_call_s']:7.2f}s "
                  f"{prog['signature'][:120]}", flush=True)
    return dict(phases), LEDGER.n_programs()


def run_trace(n=100_000, iters=3, leaves=255, bins=255):
    """Unified profiling entry point (ISSUE 10; absorbs the old
    tools/profile_step.py): train a few boosting iterations under
    tpu_telemetry=trace, write the Chrome-trace JSON (open in Perfetto
    or chrome://tracing) + the JSONL event stream under TRACE_DIR, and
    print the span summary table (count / total / mean per name).
    XPROF=1 additionally wraps the timed iterations in
    jax.profiler.start_trace and prints the xprof op tables — the
    device-side complement (the telemetry span names appear inside it
    via TraceAnnotation/named_scope mirroring).

        N=1000000 ITERS=3 [XPROF=1] python tools/perf_probe.py trace
    """
    import glob

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    import shutil

    trace_dir = os.environ.get("TRACE_DIR", "/tmp/lgbm_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    obs.configure(mode="trace", trace_dir=trace_dir)
    X, y = make_data(n)

    ds = lgb.Dataset(X, label=y, params={"max_bin": bins})
    bst = lgb.Booster(params={
        "objective": "binary", "num_leaves": leaves, "learning_rate": 0.1,
        "min_data_in_leaf": 20, "max_bin": bins,
        # match the BENCH program exactly (bench.py pins buckets off):
        # the point is attributing ITS ms/tree, not the bucketed
        # variant's
        "tpu_shape_buckets": 0,
        **json.loads(os.environ.get("EXTRA", "{}"))}, train_set=ds)
    for _ in range(2):  # compile + warm
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    obs.reset_events()  # profile the WARM loop, not the compile tail

    xprof = os.environ.get("XPROF", "") not in ("", "0")
    if xprof:
        jax.profiler.start_trace(trace_dir)
    t0 = time.time()
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    wall = time.time() - t0
    if xprof:
        jax.profiler.stop_trace()
    print(f"{iters} iters in {wall:.2f}s = {iters / wall:.3f} it/s")

    path = obs.write_chrome_trace()
    obs.flush()
    print(f"chrome trace: {path} (load in Perfetto)")

    # span summary: where the host-side wall actually went
    agg = {}
    for ev in obs.events():
        if ev["kind"] != "span":
            continue
        cnt, tot = agg.get(ev["name"], (0, 0.0))
        agg[ev["name"]] = (cnt + 1, tot + ev["dur"])
    print(f"\n{'span':<28s} {'count':>6s} {'total ms':>10s} {'mean ms':>9s}")
    for name, (cnt, tot) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
        print(f"{name:<28s} {cnt:>6d} {tot / 1e3:>10.1f} "
              f"{tot / cnt / 1e3:>9.2f}", flush=True)

    if not xprof:
        return
    # device-side op breakdown via xprof (the old profile_step tail)
    xplanes = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    print("xplane files:", xplanes)
    if not xplanes:
        return
    try:
        from xprof.convert import raw_to_tool_data as r
    except ImportError as exc:
        # the raw trace is still on disk for manual tensorboard use
        print(f"xprof unavailable ({exc}); raw trace kept at {trace_dir}")
        return
    for tool in ("framework_op_stats", "hlo_op_profile", "op_profile"):
        try:
            data, _ = r.xspace_to_tool_data(xplanes, tool, {})
            out = f"{trace_dir}/{tool}.out"
            mode = "wb" if isinstance(data, bytes) else "w"
            with open(out, mode) as f:
                f.write(data)
            print(f"wrote {out} ({len(data)} bytes)")
        except Exception as exc:
            print(f"{tool}: {type(exc).__name__}: {str(exc)[:120]}")


def run_mem(n=20000, f=10, leaves=31, bins=63, iters=3):
    """Device memory/cost accounting (ISSUE 12): run a canonical
    train + predict + serve lifecycle with the CompileLedger's cost
    capture armed and print, per compiled program, its static
    memory_analysis (argument/output/temp/generated-code bytes) and
    cost_analysis (FLOPs, bytes accessed) — plus live device
    memory_stats, the phase-tagged peak watermarks, and the big named
    buffers (histogram pool, packed forest) called out by name.

    Works on ANY backend: on CPU the device gauges read "n/a" but the
    per-program table still carries real FLOPs/bytes (and the memory
    fields via a forced AOT recompile of each small probe program).

        N=20000 python tools/perf_probe.py mem
    """
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.booster import Booster
    from lightgbm_tpu.obs import resources
    from lightgbm_tpu.serving import ServingSession
    from lightgbm_tpu.utils.compile_ledger import LEDGER

    obs.configure(mode="metrics")        # arm the phase watermarks
    LEDGER.enable()
    LEDGER.enable_capture()
    LEDGER.reset()
    resources.reset_phase_peaks()

    X, y = make_data(n, f=f)
    p = {"objective": "binary", "num_leaves": leaves, "max_bin": bins,
         "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = Booster(params=p, train_set=ds)
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    bst.predict(X[:4096], raw_score=True, device="tpu",
                tpu_predict_device="true")
    sess = ServingSession(params={"serving_max_batch_rows": 1024,
                                  "verbosity": -1})
    sess.load("m", booster=bst)
    sess.predict("m", X[:64])
    serve_hbm = sess.registry.resolve("m").hbm_bytes
    sess.close()

    mb = 1.0 / (1024 * 1024)
    # ---- live device gauges ----
    print("device memory (memory_stats):")
    devs = jax.devices()
    any_stats = False
    for d, st in zip(devs, resources.all_device_memory_stats()):
        if st is None:
            print(f"  {d}: n/a ({d.platform} backend reports no "
                  "memory_stats)")
        else:
            any_stats = True
            print(f"  {d}: in_use {st.get('bytes_in_use', 0) * mb:.1f}M"
                  f"  peak {st.get('peak_bytes_in_use', 0) * mb:.1f}M")
    # ---- phase watermarks ----
    peaks = resources.phase_peaks()
    if peaks:
        print("phase peak watermarks:")
        for phase, b in sorted(peaks.items(), key=lambda kv: -kv[1]):
            print(f"  {phase:<14s} {b * mb:10.1f}M")
    elif not any_stats:
        print("phase peak watermarks: n/a (no device memory_stats)")

    # ---- named buffers ----
    learner = bst._driver.learner
    pool = getattr(learner, "_pool", None)
    donated = bool(getattr(learner, "_donate", False))
    if pool is not None:
        print(f"histogram pool [L, G/P, B, 3]: shape {tuple(pool.shape)} "
              f"{pool.dtype} = {pool.nbytes * mb:.1f}M"
              f"{' (donated, rewritten in place)' if donated else ''}")
    total, _ = bst._driver._model_subset(-1)
    tables = bst._driver._packed_forest().device(total)
    pf_bytes = sum(int(v.nbytes) for v in tables.values())
    print(f"packed forest ({total} trees): {pf_bytes * mb:.2f}M across "
          f"{len(tables)} tables; serving entry gauge "
          f"{serve_hbm * mb:.2f}M")
    scores = bst._driver.train_scores.scores
    print(f"score buffer: shape {tuple(scores.shape)} {scores.dtype} = "
          f"{scores.nbytes * mb:.2f}M"
          f"{' (donated at the step boundary)' if donated else ''}")

    # ---- per-program static cost table ----
    rows = LEDGER.cost_table(memory=True)  # force AOT analysis on CPU too
    print(f"\nper-program cost table ({len(rows)} programs):")
    print(f"{'site':<26s} {'MFLOPs':>9s} {'acc MB':>8s} {'arg MB':>8s} "
          f"{'out MB':>8s} {'tmp MB':>8s} {'code KB':>8s}")

    def fmt(v, scale, width=8, prec=2):
        return (f"{'n/a':>{width}s}" if v is None
                else f"{v * scale:>{width}.{prec}f}")

    for r in sorted(rows, key=lambda r: -(r["temp_bytes"] or 0)):
        print(f"{r['site']:<26s} "
              f"{fmt(r['flops'], 1e-6, 9)} {fmt(r['bytes_accessed'], mb)} "
              f"{fmt(r['argument_bytes'], mb)} {fmt(r['output_bytes'], mb)} "
              f"{fmt(r['temp_bytes'], mb)} "
              f"{fmt(r['generated_code_bytes'], 1 / 1024)}", flush=True)
    return rows


def run_faults(n=4000, f=6, iters=5):
    """Chaos sweep (ISSUE 7): arm every fault-injection point against
    every relevant handling mode and print one outcome line each — the
    operational proof that an injected device error, torn checkpoint
    write, NaN gradient, or serving-dispatch failure ends in a usable
    booster / recovered checkpoint / breaker-guarded fallback rather
    than a dead run.

        N=4000 python tools/perf_probe.py faults
    """
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.booster import Booster
    from lightgbm_tpu.serving import ServingSession
    from lightgbm_tpu.utils import faultline
    from lightgbm_tpu.utils.checkpoint import CheckpointManager
    from lightgbm_tpu.utils.log import LightGBMError

    X, y = make_data(n, f=f)
    base_params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                   "learning_rate": 0.1, "min_data_in_leaf": 20,
                   "verbosity": -1}

    def outcome(point, mode, text):
        print(f"{point:<18s} {mode:<6s} {text}", flush=True)

    print(f"{'point':<18s} {'mode':<6s} outcome", flush=True)

    # grow_step x guard modes: a NaN-poisoned iteration under each policy
    for mode in ("off", "warn", "raise", "skip"):
        faultline.reset()
        p = dict(base_params, tpu_guard_numerics=mode)
        bst = Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
        faultline.arm("grow_step", action="poison", at=2)
        try:
            for _ in range(iters):
                bst.update()
            finite = bool(np.isfinite(
                bst.predict(X[:64], raw_score=True)).all())
            skips = bst._driver._guard_skips_total
            outcome("grow_step/poison", mode,
                    f"trained {bst.current_iteration()} iters, "
                    f"predict finite={finite}, skipped={skips}")
        except LightGBMError as exc:
            usable = bool(np.isfinite(
                bst.predict(X[:64], raw_score=True)).all())
            outcome("grow_step/poison", mode,
                    f"raised LightGBMError ({str(exc)[:40]}...), "
                    f"booster usable={usable}")

    # grow_step raise: injected device error -> rollback -> retrain
    faultline.reset()
    bst = Booster(params=dict(base_params),
                  train_set=lgb.Dataset(X, label=y, params=base_params))
    faultline.arm("grow_step", action="raise", at=3)
    errors = 0
    while bst.current_iteration() < iters:
        try:
            bst.update()
        except faultline.FaultInjected:
            errors += 1
    outcome("grow_step/raise", "-",
            f"{errors} injected error(s) rolled back, retrained to "
            f"{bst.current_iteration()} iters")

    # h2d_copy raise: device predict falls to an exception the caller
    # sees; the booster itself stays intact
    faultline.reset()
    faultline.arm("h2d_copy", action="raise")
    try:
        bst.predict(X[:256], raw_score=True, device="tpu",
                    tpu_predict_device="true")
        outcome("h2d_copy/raise", "-", "NOT reached (no device launch)")
    except faultline.FaultInjected:
        faultline.reset()
        ok = bool(np.isfinite(bst.predict(X[:64], raw_score=True)).all())
        outcome("h2d_copy/raise", "-",
                f"predict raised, booster usable={ok}")

    # checkpoint_write truncate: torn bundle is skipped, prior one loads
    faultline.reset()
    d = tempfile.mkdtemp(prefix="faults-ckpt-")
    try:
        bst.save_checkpoint(d)
        good = CheckpointManager(d).latest_iteration()
        bst.update()
        faultline.arm("checkpoint_write", action="truncate")
        bst.save_checkpoint(d)
        loaded = CheckpointManager(d).load_latest()
        outcome("checkpoint_write", "trunc",
                f"torn bundle skipped, recovered iteration="
                f"{loaded[0] if loaded else None} (good={good})")
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # serve_dispatch raise: breaker opens, walker serves, probe closes
    faultline.reset()
    sess = ServingSession(params={"serving_max_batch_rows": 512,
                                  "verbosity": -1,
                                  "serving_breaker_failures": 2,
                                  "serving_breaker_cooldown_ms": 50.0})
    sess.load("m", booster=bst)
    faultline.arm("serve_dispatch", action="raise", times=10)
    for _ in range(3):
        sess.predict("m", X[:64], raw_score=True)
    st = sess.stats()
    time.sleep(0.08)
    faultline.reset()
    sess.predict("m", X[:64], raw_score=True)
    st2 = sess.stats()
    outcome("serve_dispatch", "raise",
            f"fallbacks={st['device_fallbacks']} "
            f"opened={st['breaker_open']} "
            f"probes={st2['breaker_halfopen_probes']} "
            f"final={[m['breaker'] for m in sess.models()]}")
    sess.close()

    # ---- device_alloc oom x guarded site (ISSUE 15): a classified
    # RESOURCE_EXHAUSTED at each guarded allocation site must recover
    # (ladder / chunk shrink / walker failover) or surface structured
    from lightgbm_tpu.obs import REGISTRY
    from lightgbm_tpu.utils import membudget

    def oom_count(metric, **labels):
        return int(REGISTRY.value(metric, **labels))

    # train_step: rollback -> ladder step -> bitwise retry
    faultline.reset()
    p = dict(base_params)
    bst_o = Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    bst_o.update()
    faultline.arm("device_alloc", action="oom", at=1)
    bst_o.update()
    outcome("device_alloc/oom", "train",
            f"recovered to {bst_o.current_iteration()} iters, "
            f"recoveries={oom_count('lgbm_oom_recoveries_total', site='train_step')} "
            f"ladder={bst_o._driver._mem_ladder.describe()}")

    # predict_chunk: chunk shrink -> identical output
    faultline.reset()
    native = bst_o.predict(X[:512], raw_score=True)
    faultline.arm("device_alloc", action="oom", at=1)
    dev = bst_o.predict(X[:512], raw_score=True, device="tpu",
                        tpu_predict_device="true")
    outcome("device_alloc/oom", "pred",
            f"recovered, outputs equal={bool(np.allclose(native, dev))}")

    # ingest_chunk: binning chunk shrink -> bit-identical bins
    faultline.reset()
    pi = dict(base_params, tpu_ingest_device="true", tpu_ingest_min_rows=1,
              tpu_ingest_chunk_rows=2048)
    faultline.arm("device_alloc", action="oom", at=1)
    ds_i = lgb.Dataset(X, label=y, params=pi)
    ds_i.construct()
    faultline.reset()
    ds_h = lgb.Dataset(X, label=y, params=base_params)
    ds_h.construct()
    same = bool(np.array_equal(np.asarray(ds_i._inner.bins),
                               np.asarray(ds_h._inner.bins)))
    outcome("device_alloc/oom", "ingest",
            f"recovered via chunk shrink, bins bit-identical={same}")

    # serve_dispatch: walker failover, zero errors to the caller
    faultline.reset()
    sess_o = ServingSession(params={"verbosity": -1})
    sess_o.load("m", booster=bst_o)
    faultline.arm("device_alloc", action="oom", times=2)
    ok = bool(np.isfinite(np.asarray(
        sess_o.predict("m", X[:64], raw_score=True))).all())
    st_o = sess_o.stats()
    outcome("device_alloc/oom", "serve",
            f"served={ok} dispatch_oom={st_o['dispatch_oom']} "
            f"fallbacks={st_o['device_fallbacks']}")
    faultline.reset()
    sess_o.close()

    # ladder exhaustion: structured error, usable booster
    faultline.arm("device_alloc", action="oom", times=1000)
    try:
        bst_o.update()
        outcome("device_alloc/oom", "exh", "NOT reached (no exhaustion)")
    except membudget.MemoryLadderExhausted as exc:
        faultline.reset()
        usable = bool(np.isfinite(
            bst_o.predict(X[:64], raw_score=True)).all())
        outcome("device_alloc/oom", "exh",
                f"MemoryLadderExhausted at {exc.site!r}, booster "
                f"usable={usable}")
    faultline.reset()


def run_faults_multihost(hosts=2, iters=4, n=1200):
    """Distributed chaos sweep (ISSUE 8): a (point x armed-host x
    live-host) grid over a SIMULATED host group, one outcome line per
    cell — the operational proof that (a) a fault armed for host k at
    absolute call-index i fires on host k and ONLY host k (the
    reproducibility contract multihost chaos runs need), and (b) every
    addressed fault degrades to a flushed checkpoint + bitwise resume
    instead of a hung group.

        HOSTS=2 python tools/perf_probe.py faults --multihost
    """
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.parallel.collective import (CollectiveTimeout,
                                                  HostDropped,
                                                  guarded_collective)
    from lightgbm_tpu.utils import faultline
    from lightgbm_tpu.utils.checkpoint import CheckpointManager

    X, y = make_data(n, f=6)
    base = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
            "learning_rate": 0.1, "min_data_in_leaf": 20,
            "verbosity": -1, "tpu_collective_timeout_s": 5.0}

    def outcome(point, h_armed, h_live, text):
        print(f"{point:<18s} armed=h{h_armed} live=h{h_live} {text}",
              flush=True)

    print(f"{'point':<18s} {'armed':<8s} {'live':<7s} outcome", flush=True)

    for point, action, exc_type in (
            ("collective_sync", "hang", CollectiveTimeout),
            ("host_drop", "raise", HostDropped)):
        for h_armed in range(hosts):
            for h_live in range(hosts):
                faultline.reset()
                faultline.set_host_index(h_live)
                d = tempfile.mkdtemp(prefix="mh-faults-")
                try:
                    p = dict(base, tpu_checkpoint_dir=d)
                    ds = lgb.Dataset(X, label=y, params=p)
                    dv = lgb.Dataset(X[:256], label=y[:256],
                                     reference=ds, params=p)
                    # the metric sync is one collective per iteration:
                    # absolute call-index 3 = iteration 3's eval
                    faultline.arm(point, action=action, at=3,
                                  absolute=True, host=h_armed)
                    try:
                        bst = lgb.train(p, ds, num_boost_round=iters,
                                        valid_sets=[dv],
                                        verbose_eval=False,
                                        keep_training_booster=True)
                        it = bst.current_iteration()
                        tag = ("UNEXPECTED clean run"
                               if h_armed == h_live else "not addressed")
                        outcome(point, h_armed, h_live,
                                f"{tag} -> trained {it} iters clean")
                    except exc_type as exc:
                        faultline.set_host_index(h_live)
                        faultline.disarm()
                        got = CheckpointManager(d).load_latest()
                        ck_it = got[0] if got else None
                        ds2 = lgb.Dataset(X, label=y, params=p)
                        bst2 = lgb.train(p, ds2, num_boost_round=iters,
                                         resume=True, verbose_eval=False,
                                         keep_training_booster=True)
                        outcome(point, h_armed, h_live,
                                f"{type(exc).__name__} at call 3 -> "
                                f"checkpoint@{ck_it} flushed, resumed "
                                f"to {bst2.current_iteration()} iters")
                finally:
                    faultline.reset()
                    shutil.rmtree(d, ignore_errors=True)

    # binning_allgather: single-process ingest never reaches the
    # multihost allgather, so the point is demonstrated at the transport
    # wrapper — same watchdog, same addressing
    for h_armed in range(hosts):
        for h_live in range(hosts):
            faultline.reset()
            faultline.set_host_index(h_live)
            faultline.arm("binning_allgather", action="hang",
                          host=h_armed)
            try:
                guarded_collective(lambda: "mappers",
                                   name="mapper_exchange",
                                   point="binning_allgather", local=True)
                outcome("binning_allgather", h_armed, h_live,
                        "not addressed -> mapper exchange completed")
            except CollectiveTimeout:
                outcome("binning_allgather", h_armed, h_live,
                        "CollectiveTimeout -> bin finding aborted "
                        "cleanly")
            finally:
                faultline.reset()


def run_drift_probe(n=20000, reps=30):
    """Serving drift-monitor overhead (ISSUE 14): sweep
    `serving_drift_sample_rows` x batch size and print the per-predict
    wall beside the monitor-off baseline.  The <1% gate the telemetry
    suite enforces applies to the OFF row (sample_rows=0: no monitor is
    constructed at all); the enabled rows show what sampling actually
    costs — the tap is a bounded row copy, the absorb (binning + PSI)
    runs once per scrape and is amortized over `reps` predicts here,
    exactly like a Prometheus scrape interval would."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import ServingSession

    X, y = make_data(n, f=10)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "max_bin": 63, "verbosity": -1}, ds,
                    num_boost_round=20)
    batches = [64, 512, 4096]
    base = {}
    print(f"{'sample_rows':>12} {'batch':>6} {'ms/predict':>11} "
          f"{'overhead_pct':>13}  (absorb amortized over {reps} predicts)")
    for sample_rows in (0, 64, 256, 1024):
        sess = ServingSession(params={
            "serving_max_batch_rows": 4096,
            "serving_drift_sample_rows": sample_rows,
            # the probe replays one fixed row block, which IS a
            # drifted stream statistically — silence the PSI warning,
            # this sweep measures overhead, not drift
            "serving_drift_psi_warn": 1e9, "verbosity": -1})
        sess.load("probe", booster=bst)
        entry = sess.registry.resolve("probe")
        for batch in batches:
            Xb = X[:batch]
            entry.predict(Xb)                       # warm path + jit
            t0 = time.time()
            for _ in range(reps):
                entry.predict(Xb)
            if entry.drift is not None:
                entry.drift.snapshot()              # one scrape's absorb
            ms = (time.time() - t0) / reps * 1e3
            if sample_rows == 0:
                base[batch] = ms
            over = (100.0 * (ms - base[batch]) / base[batch]
                    if base.get(batch) else 0.0)
            flag = "  <1% gate" if sample_rows == 0 else ""
            print(f"{sample_rows:>12} {batch:>6} {ms:>11.3f} "
                  f"{over:>12.1f}%{flag}")
        sess.close()


def run_stream_sweep(n=200_000, f=28, iters=5, leaves=63, bins=255):
    """Out-of-core streaming sweep (ISSUE 16): stream block rows x
    double-buffering x GOSS fractions.  Prints the H2D copy wall beside
    the histogram wall and the achieved overlap ratio — the number the
    double-buffer exists to maximize.  GOSS rows show how much copy
    traffic gradient-based block sampling removes (its models are NOT
    bitwise vs the full stream; the bitwise rows are goss=off)."""
    import lightgbm_tpu as lgb

    X, y = make_data(n, f=f)
    block_rows = [int(s) for s in
                  os.environ.get("STREAM_ROWS", "16384,65536,262144")
                  .split(",")]
    goss = [(0.0, 0.0), (0.2, 0.1)]
    print(f"streamed training: n={n} f={f} iters={iters} "
          f"leaves={leaves} bins={bins}")
    print(f"{'rows/block':>10} {'dbuf':>5} {'goss':>9} {'ms/tree':>9} "
          f"{'h2d_ms':>8} {'hist_ms':>8} {'overlap':>8} "
          f"{'skip':>5} {'Mrows/s':>8}")
    for rows in block_rows:
        for dbuf in (True, False):
            for top, other in goss:
                p = {"objective": "binary", "num_leaves": leaves,
                     "max_bin": bins, "verbosity": -1,
                     "tpu_stream_mode": "streamed",
                     "tpu_stream_block_rows": rows,
                     "tpu_stream_double_buffer": dbuf,
                     "tpu_stream_goss_top": top,
                     "tpu_stream_goss_other": other}
                ds = lgb.Dataset(X, label=y, params=p)
                bst = lgb.Booster(params=p, train_set=ds)
                bst.update()                    # warm compiles
                tot = dict(tree=0.0, h2d=0.0, hist=0.0, est=0.0,
                           hidden=0.0, skip=0.0)
                for _ in range(iters):
                    bst.update()
                    s = bst._driver.learner.stream_stats
                    tot["tree"] += s["tree_wall_s"]
                    tot["h2d"] += s["h2d_wall_s"]
                    tot["hist"] += s["hist_wall_s"]
                    tot["est"] += s["copy_est_s"]
                    tot["hidden"] += (s["overlap_pct"] / 100.0
                                      * s["copy_est_s"])
                    tot["skip"] += s["blocks_skipped"]
                overlap = (100.0 * tot["hidden"] / tot["est"]
                           if tot["est"] else 0.0)
                gs = f"{top}/{other}" if top else "off"
                mrows = n * iters / tot["tree"] / 1e6
                print(f"{rows:>10} {str(dbuf):>5} {gs:>9} "
                      f"{tot['tree'] / iters * 1e3:>9.1f} "
                      f"{tot['h2d'] / iters * 1e3:>8.1f} "
                      f"{tot['hist'] / iters * 1e3:>8.1f} "
                      f"{overlap:>7.1f}% "
                      f"{tot['skip'] / iters:>5.1f} {mrows:>8.2f}")


def main():
    # probe crashes must never drop a blackbox dump beside the sources
    # the probe is usually run from; an explicit env/param still wins
    import tempfile
    os.environ.setdefault("LIGHTGBM_TPU_BLACKBOX_DIR",
                          tempfile.gettempdir())
    arg = sys.argv[1] if len(sys.argv) > 1 else ""
    if arg == "drift":
        run_drift_probe(n=int(os.environ.get("N", 20000)),
                        reps=int(os.environ.get("REPS", 30)))
        return
    if arg == "faults":
        if "--multihost" in sys.argv[2:]:
            run_faults_multihost(hosts=int(os.environ.get("HOSTS", 2)),
                                 iters=int(os.environ.get("ITERS", 4)))
            return
        run_faults(n=int(os.environ.get("N", 4000)),
                   iters=int(os.environ.get("ITERS", 5)))
        return
    if arg == "stream":
        run_stream_sweep(n=int(os.environ.get("N", 200_000)),
                         f=int(os.environ.get("F", 28)),
                         iters=int(os.environ.get("ITERS", 5)),
                         leaves=int(os.environ.get("LEAVES", 63)),
                         bins=int(os.environ.get("BINS", 255)))
        return
    if arg == "mem":
        run_mem(n=int(os.environ.get("N", 20000)),
                leaves=int(os.environ.get("LEAVES", 31)),
                bins=int(os.environ.get("BINS", 63)),
                iters=int(os.environ.get("ITERS", 3)))
        return
    if arg == "retrace":
        run_retrace(n=int(os.environ.get("N", 20000)),
                    leaves=int(os.environ.get("LEAVES", 31)),
                    bins=int(os.environ.get("BINS", 63)),
                    iters=int(os.environ.get("ITERS", 3)))
        return
    if arg == "trace":
        run_trace(n=int(os.environ.get("N", 100_000)),
                  iters=int(os.environ.get("ITERS", 3)),
                  leaves=int(os.environ.get("LEAVES", 255)),
                  bins=int(os.environ.get("BINS", 255)))
        return
    if arg == "comm":
        # no dataset needed.  Runs on the devices jax has: the chips of
        # the host for real ICI numbers, or — exported by the caller —
        # JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_
        # count=8 for a virtual mesh (byte counts only, no timing claim)
        shard_counts = [int(s) for s in
                        os.environ.get("SHARDS", "2,4,8").split(",")]
        host_counts = [int(s) for s in
                       os.environ.get("HOSTS", "1").split(",")]
        run_comm_sweep(shard_counts, host_counts=host_counts)
        return
    n = int(os.environ.get("N", 1_000_000))
    X, y = make_data(n)
    if arg == "hist":
        run_hist_sweep(X, y, bins=int(os.environ.get("BINS", 255)))
        return
    if arg == "ingest":
        run_ingest_sweep(X, y, bins=int(os.environ.get("BINS", 255)))
        return
    if arg == "predict":
        run_predict_sweep(X, y, rounds=int(os.environ.get("ROUNDS", 50)),
                          leaves=int(os.environ.get("LEAVES", 255)),
                          bins=int(os.environ.get("BINS", 255)))
        return
    if arg == "one":
        sweep(X, y, [dict(k=int(os.environ.get("K", 25)),
                          block=int(os.environ.get("BLOCK", 16384)),
                          impl=os.environ.get("IMPL", "xla"),
                          part=os.environ.get("PARTITION", "select"),
                          prec=os.environ.get("PRECISION", "hilo"),
                          ramp=os.environ.get("RAMP", "") == "1",
                          alpha=float(os.environ.get("ALPHA", 0.0)))],
              iters=8, reraise=True)
        return
    sweep(X, y, [dict(impl=i, k=k, block=b)
                 for i, b in (("xla", 16384), ("pallas2", 8192))
                 for k in (16, 25)], iters=5)


if __name__ == "__main__":
    main()
