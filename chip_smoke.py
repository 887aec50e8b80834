"""First-contact smoke: the main path, once, on the chip.

    python chip_smoke.py                 # needs a TPU; fails without one
    python chip_smoke.py --dry-run-cpu   # same legs, toy size, CPU rehearsal

Drives `lgb.Dataset` -> `lgb.Booster(...).update()` -> `Booster.predict` ->
`ServingSession.predict` through the public entry points at shipping
defaults, at the full width of the model the repo claims (Higgs-shaped dense
binary: 1,000,000 rows x 28 features, num_leaves=255, max_bin=255; seeded
synthetic rows from `bench.make_data`), and checks each leg by the repo's own
means.  One process: JAX is first touched here and no child that uses JAX is
started.  Any failed check or exception is a traceback and a non-zero exit.

The last line of standard output of a passing run is one JSON object,
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
A run that finds no TPU exits non-zero BEFORE doing any work and prints no
result.  `--dry-run-cpu` is the only way the script runs without a chip; it
stamps every line `DRYRUN platform=cpu` and is never the default.

The times printed here are what one smoke run saw, compile included; they
are not benchmark numbers.
"""

import json
import os
import sys
import time
from importlib import metadata

DRY = "--dry-run-cpu" in sys.argv[1:]
if DRY:
    # the same four-device mesh the multichip leg wants, on the host CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = " ".join(
        [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
        + ["--xla_force_host_platform_device_count=4"])

import jax  # noqa: E402
import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
N_FEATURES = 28
# full width on the chip; a toy of the same shape for the CPU rehearsal
ROWS, LEAVES, BINS = (8192, 15, 63) if DRY else (1_000_000, 255, 255)
PREDICT_ROWS = 2048 if DRY else 50_000
WARM_ITERS, TIMED_ITERS, QUANT_ITERS = 3, 5, 3
# train AUC after WARM+TIMED iterations: the chip printed 0.8317 at full
# width (PR 21) and the toy rehearsal prints 0.82; a model that learned
# nothing sits at 0.5
AUC_FLOOR = 0.80
# tree 0's leaf values against a host recount of the same rows (binary
# logloss from the constant init score): histogram rounding alone moves a
# value by ~1e-5; the chip's first hilo run, which had lost the lo half of
# every stat, had a leaf at -5359.6 where the recount says -0.18 (PR 21)
LEAF_TOL = 1e-3
# a four-way shard's peak as a share of the one-chip int8 run's own peak.
# The chip showed 0.61 (PR 21), not the quarter a program sharded through
# and through would give (PERF.md, open questions); a run that shards
# nothing shows 1.0 and fails this bound
SHARD_PEAK_SHARE = 0.7
RUNS_LOG = os.path.join(HERE, "chiprun_out", "chip_smoke.jsonl")
# shipping defaults for everything not named here
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "max_bin": BINS,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1}
# on the chip the defaults route Booster.predict to the device walker; the
# CPU rehearsal has to ask for it
DEVICE_PREDICT = {"tpu_predict_device": "true"} if DRY else {}


def say(msg: str) -> None:
    print(("DRYRUN platform=cpu " if DRY else "") + msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def counter_total(name: str) -> float:
    from lightgbm_tpu import obs

    return sum(v for k, v in obs.REGISTRY.snapshot().items()
               if k.split("{")[0] == name and not isinstance(v, dict))


def mem(device) -> tuple:
    """(bytes_in_use, peak_bytes_in_use) as the device reports them now."""
    stats = device.memory_stats() or {}
    return (int(stats.get("bytes_in_use", 0)),
            int(stats.get("peak_bytes_in_use", 0)))


def train_auc(bst, y) -> float:
    """Train AUC from the live train scores, by the repo's own metric."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.metrics import AUCMetric

    class _MD:
        label = np.asarray(y, np.float32)
        weight = None

    m = AUCMetric(Config())
    m.init(_MD, len(y))
    scores = np.asarray(bst._driver.train_scores.scores)[:, :len(y)]
    check(np.isfinite(scores).all(), "train scores are not all finite")
    return float(m.eval(scores, None))


def first_tree_recount(bst, X, y, lr):
    """Tree 0 against the rows it was grown from, counted on the host: every
    leaf's row count, and its value as -lr * sum(g) / sum(h) over those
    rows plus the boost-from-average bias.  Returns the worst value error.
    The AUC floor cannot see a few absurd leaves; this can."""
    tree = trees_of(bst)[0]
    leaf = bst.predict(X, pred_leaf=True, num_iteration=1,
                       device="cpu").reshape(len(X)).astype(np.int64)
    n = np.bincount(leaf, minlength=tree.num_leaves)
    check(np.array_equal(n, tree.leaf_count[:tree.num_leaves]),
          "tree 0 leaf counts differ from a host recount of the train rows")
    p = float(np.mean(y))
    pos = np.bincount(leaf, weights=y, minlength=tree.num_leaves)
    want = (np.log(p / (1.0 - p))
            - lr * (n * p - pos) / (n * p * (1.0 - p)))
    err = np.abs(tree.leaf_value[:tree.num_leaves] - want)
    worst = int(np.argmax(err))
    check(err[worst] <= LEAF_TOL,
          f"tree 0 leaf {worst} ({n[worst]} rows) has value "
          f"{tree.leaf_value[worst]:.6g}, host recount {want[worst]:.6g}")
    return float(err[worst])


def split_fields(tree):
    """One tree's records as the model keeps them."""
    ni, nl = tree.num_leaves - 1, tree.num_leaves
    return {"feature": tree.split_feature[:ni],
            "threshold_bin": tree.threshold_in_bin[:ni],
            "decision_type": tree.decision_type[:ni],
            "left_child": tree.left_child[:ni],
            "right_child": tree.right_child[:ni],
            "internal_count": tree.internal_count[:ni],
            "gain": tree.split_gain[:ni],
            "leaf_count": tree.leaf_count[:nl],
            "leaf_value": tree.leaf_value[:nl]}


def trees_of(bst):
    bst._driver._materialize()
    return list(bst._driver.models)


def boost(ds, params, warm, timed):
    """Booster at `params`: `warm` iterations (compile included) then
    `timed`, each window ending in block_until_ready."""
    import lightgbm_tpu as lgb

    bst = lgb.Booster(params=params, train_set=ds)
    t0 = time.time()
    for _ in range(warm):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    warm_s = time.time() - t0
    t0 = time.time()
    for _ in range(timed):
        bst.update()
    jax.block_until_ready(bst._driver.train_scores.scores)
    timed_s = time.time() - t0
    trees = trees_of(bst)
    check(len(trees) == warm + timed,
          f"{len(trees)} trees after {warm + timed} iterations")
    leaves = sorted({t.num_leaves for t in trees})
    check(leaves == [LEAVES], f"tree leaf counts {leaves}, want {LEAVES}")
    return bst, warm_s, timed_s


def leg_train(ctx):
    from lightgbm_tpu.utils import membudget

    bst, warm_s, timed_s = boost(ctx["ds"], PARAMS, WARM_ITERS, TIMED_ITERS)
    lp = bst._driver.learner.params
    check(lp.hist_impl == ("xla" if DRY else "pallas2"),
          f"tpu_hist_impl=auto resolved to {lp.hist_impl!r} at hilo")
    check(lp.precision == "hilo", f"precision {lp.precision!r}")
    check(lp.partition_impl == ("select" if DRY else "kernel"),
          f"tpu_partition_impl=auto resolved to {lp.partition_impl!r} on a "
          "dense numerical table")
    auc = train_auc(bst, ctx["y"])
    check(auc >= AUC_FLOOR, f"train AUC {auc:.4f} under floor {AUC_FLOOR}")
    leaf_err = first_tree_recount(bst, ctx["X"], ctx["y"],
                                  PARAMS["learning_rate"])
    plan = membudget.plan_training(bst._driver.config, bst._driver.learner, 1)
    _, peak = mem(jax.devices()[0])
    ooms = counter_total("lgbm_oom_events_total")
    ladder = counter_total("lgbm_oom_ladder_steps_total")
    check(ooms == 0 and ladder == 0,
          f"oom events {ooms}, ladder steps {ladder}")
    ctx.update(bst=bst, compile_wall_s=warm_s)
    say(f"train: ok impl={lp.hist_impl} partition={lp.partition_impl} "
        f"precision={lp.precision} "
        f"block_rows={lp.block_rows} trees={WARM_ITERS + TIMED_ITERS}x"
        f"{LEAVES} leaves auc={auc:.4f} "
        f"tree0_max_leaf_value_err_vs_host_recount={leaf_err:.2e} "
        f"cold_compile_plus_{WARM_ITERS}_iters_s={warm_s:.1f} "
        f"{TIMED_ITERS}_iters_s={timed_s:.2f} "
        f"peak_bytes_in_use={peak} planned_bytes={plan.total} "
        f"budget_bytes={plan.budget} oom_events=0 ladder_steps=0")


def leg_quantized(ctx):
    dev0 = jax.devices()[0]
    resident, peak_before = mem(dev0)
    bst, warm_s, _ = boost(ctx["ds"], {**PARAMS, "tpu_hist_precision": "int8"},
                           QUANT_ITERS, 0)
    # this run's own high-water: the dataset and the hilo booster (kept for
    # the predict and serve legs) were resident from start to end, so it is
    # the new peak above them — when the peak moved at all
    _, peak = mem(dev0)
    own_peak = peak - resident if peak > peak_before else None
    lp = bst._driver.learner.params
    check(lp.hist_impl == ("xla" if DRY else "pallas2"),
          f"tpu_hist_impl=auto resolved to {lp.hist_impl!r} at int8")
    check(lp.precision == "int8", f"precision {lp.precision!r}")
    auc = train_auc(bst, ctx["y"])
    check(auc > 0.6, f"int8 train AUC {auc:.4f}")
    ctx["int8_own_peak"] = own_peak
    say(f"quantized: ok impl={lp.hist_impl} precision=int8 "
        f"trees={QUANT_ITERS}x{LEAVES} leaves auc={auc:.4f} "
        f"cold_compile_plus_{QUANT_ITERS}_iters_s={warm_s:.1f} "
        f"peak_bytes_in_use={peak} resident_at_start={resident} "
        f"own_peak_bytes={own_peak if own_peak is not None else 'not separable'}")


def leg_predict(ctx):
    from lightgbm_tpu.utils.compile_ledger import LEDGER

    bst, X = ctx["bst"], ctx["X_eval"]
    before = LEDGER.n_programs("predict.class_scores")
    t0 = time.time()
    dev = bst.predict(X, raw_score=True, **DEVICE_PREDICT)
    dev_s = time.time() - t0
    check(LEDGER.n_programs("predict.class_scores") > before,
          "Booster.predict compiled no device forest walk: it did not "
          "take the device path")
    host = bst.predict(X, raw_score=True, device="cpu")
    check(dev.shape == host.shape == (len(X),), f"shape {dev.shape}")
    check(np.isfinite(dev).all(), "device predictions not finite")
    err = float(np.max(np.abs(dev - host)))
    check(err <= 1e-6, f"device vs host walker max abs diff {err:.3g}")
    ctx["raw_eval"] = dev
    say(f"predict: ok rows={len(X)} path=device max_abs_diff_vs_host="
        f"{err:.2e} first_call_s={dev_s:.1f} host_walker="
        f"{ctx['host_walker']}")


def leg_serve(ctx):
    from lightgbm_tpu.serving import ServingSession

    bst, X = ctx["bst"], ctx["X_eval"]
    sess = ServingSession(params={"verbosity": -1})
    try:
        t0 = time.time()
        sess.load("smoke", booster=bst)
        load_s = time.time() - t0
        entry = sess.registry.resolve("smoke")
        check(entry.device_on, "serving entry is not on the device path")
        worst = 0.0
        for rows in [X[256 * i:256 * (i + 1)] for i in range(8)] + [X[:4096]]:
            got = sess.predict("smoke", rows, raw_score=True)
            want = bst.predict(rows, raw_score=True, **DEVICE_PREDICT)
            check(got.shape == want.shape, f"shape {got.shape}")
            worst = max(worst, float(np.max(np.abs(got - want))))
        check(worst <= 1e-6, f"serving vs Booster.predict diff {worst:.3g}")
        stats = sess.stats()
        bad = {k: stats[k] for k in ("device_fallbacks", "dispatch_failovers",
                                     "replica_failovers") if stats[k]}
        check(not bad, f"serving left the device path: {bad}")
        say(f"serve: ok requests=8x256+1x{min(4096, len(X))} replicas="
            f"{len(entry.replicas)} max_abs_diff_vs_predict={worst:.2e} "
            f"load_and_warmup_s={load_s:.1f} device_fallbacks=0 "
            f"dispatch_failovers=0 replica_failovers=0")
    finally:
        sess.close()


def leg_multichip(ctx):
    """Four chips in one process: tree_learner=data over a 4-device mesh at
    int8, against a one-chip int8 run, and one serving replica per chip."""
    devices = jax.devices()
    if len(devices) < 4:
        say(f"multichip: skipped ({len(devices)} device)")
        return
    from lightgbm_tpu.serving import ServingSession

    # the claim tpu_hist_agg=scatter makes: int32 histograms are
    # associative, so every record of every tree is bit-identical to the
    # one-chip run's.  As in the repo's own shard-count sweeps
    # (tests/test_sharded_agg.py, tests/test_topology.py) both runs turn
    # tpu_quant_refit_leaves off: that refit is the one f32 psum whose
    # shard order reaches the model
    int8 = {**PARAMS, "tpu_hist_precision": "int8",
            "tpu_quant_refit_leaves": False}
    one, _, _ = boost(ctx["ds"], int8, QUANT_ITERS, 0)
    one_trees = trees_of(one)
    one_text = one.model_to_string().split("\nparameters:")[0]
    del one
    bst, warm_s, _ = boost(
        ctx["ds"], {**int8, "tree_learner": "data", "num_machines": 4},
        QUANT_ITERS, 0)
    learner = bst._driver.learner
    check(learner.hist_agg == "scatter", f"hist_agg {learner.hist_agg!r}")
    shards = learner.bins_t.addressable_shards
    on = {s.device for s in shards}
    check(len(shards) == 4 and len(on) == 4,
          f"bins_t has {len(shards)} shards on {len(on)} devices")
    for i, (t1, t4) in enumerate(zip(one_trees, trees_of(bst))):
        f1, f4 = split_fields(t1), split_fields(t4)
        diff = [k for k in f1 if not np.array_equal(f1[k], f4[k])]
        check(not diff, f"tree {i} differs from the one-chip run in {diff}")
    check(bst.model_to_string().split("\nparameters:")[0] == one_text,
          "four-chip model text differs from the one-chip run's")
    # device 0 also ran every one-chip leg; the other three have held only
    # this run's shards (and a serving replica of a few trees), so their
    # peaks are the sharded footprint, set beside the one-chip int8 run's
    # own high-water from the quantized leg
    resident, peaks = zip(*(mem(d) for d in devices[:4]))
    own = ctx["int8_own_peak"]
    if not DRY:
        check(all(p > 0 for p in peaks), f"per-device peaks {peaks}")
        check(own is not None, "the one-chip int8 peak was not separable")
        check(max(peaks[1:]) <= SHARD_PEAK_SHARE * own,
              f"per-device peaks {peaks} vs the one-chip int8 run's {own}")
    # one replica per chip is the default placement on an accelerator; on
    # CPU the default is one replica, so the rehearsal asks for four
    sess = ServingSession(params={"verbosity": -1,
                                  **({"serving_devices": 4} if DRY else {})})
    try:
        sess.load("smoke4", booster=bst)
        entry = sess.registry.resolve("smoke4")
        on = {r.device for r in entry.replicas}
        check(len(entry.replicas) == 4 and len(on) == 4,
              f"{len(entry.replicas)} serving replicas on {len(on)} devices")
        rows = ctx["X_eval"][:256]
        want = bst.predict(rows, raw_score=True, **DEVICE_PREDICT)
        for r in entry.replicas:
            got = entry.predict(rows, raw_score=True, device_index=r.index)
            err = float(np.max(np.abs(got - want)))
            check(err <= 1e-6, f"replica {r.index} diff {err:.3g}")
        stats = sess.stats()
        bad = {k: stats[k] for k in ("device_fallbacks", "replica_failovers")
               if stats[k]}
        check(not bad, f"fleet serving left the device path: {bad}")
    finally:
        sess.close()
    say(f"multichip: ok devices=4 hist_agg=scatter bins_shards=4 "
        f"trees_bit_identical_to_one_chip={QUANT_ITERS}/{QUANT_ITERS} "
        f"model_text=identical peak_bytes_in_use={list(peaks)} "
        f"bytes_in_use_after_training={list(resident)} "
        f"one_chip_int8_own_peak_bytes={own} "
        + ("" if DRY else
           f"shard_peak_share={max(peaks[1:]) / own:.2f} ")
        + f"serving_replicas={len(entry.replicas)} "
        f"cold_compile_plus_{QUANT_ITERS}_iters_s={warm_s:.1f}")


def setup() -> dict:
    """Print what the run stands on, build the dataset once, return the
    context the legs share."""
    sys.path.insert(0, HERE)
    import lightgbm_tpu as lgb
    from bench import make_data
    from lightgbm_tpu import native
    from lightgbm_tpu.utils.compile_ledger import LEDGER

    dev0 = jax.devices()[0]
    cache_dir = str(jax.config.jax_compilation_cache_dir)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"device: platform={dev0.platform} device_kind={dev0.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"jaxlib={metadata.version('jaxlib')} "
        f"libtpu={metadata.version('libtpu')}")
    say(f"compile cache: dir={cache_dir} entries_at_start={entries} "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '<unset>')}")
    LEDGER.enable()

    # the host walker the predict leg checks against: the OpenMP library is
    # built from src/capi on first use (g++, no JAX) when it is not there
    had_lib = os.path.exists(os.path.join(HERE, "build",
                                          "lib_lightgbm_tpu.so"))
    t0 = time.time()
    host_walker = ("numpy(native-library-absent)"
                   if native.native_lib() is None
                   else "native(present-before-the-run)" if had_lib
                   else f"native(built-here-in-{time.time() - t0:.1f}s)")
    say(f"host walker: {host_walker}")

    t0 = time.time()
    X, y = make_data(ROWS, N_FEATURES)
    ds = lgb.Dataset(X, label=y, params={"max_bin": BINS})
    ds.construct()
    if ds._inner._ingest_bins is not None:
        jax.block_until_ready(ds._inner._ingest_bins)
    say(f"dataset: rows={ROWS} features={N_FEATURES} max_bin={BINS} "
        f"ingest={'device' if ds._inner._ingest_bins is not None else 'host'}"
        f" make_and_bin_s={time.time() - t0:.1f}")
    return {"ds": ds, "X": X, "y": y, "X_eval": X[:PREDICT_ROWS].copy(),
            "cache_dir": cache_dir, "cache_entries": entries,
            "host_walker": host_walker}


def main() -> int:
    dev0 = jax.devices()[0]
    # JAX's own behaviour when libtpu fails to initialise is to warn and
    # carry on with the CPU, so the platform is asserted before any work
    if dev0.platform != ("cpu" if DRY else "tpu"):
        print(f"chip_smoke: jax platform is {dev0.platform!r}, not 'tpu' — "
              "no accelerator, nothing was run", file=sys.stderr)
        return 1
    ctx = setup()
    for leg in (leg_train, leg_quantized, leg_predict, leg_serve,
                leg_multichip):
        leg(ctx)

    if not DRY:
        # the compile cache at work: this run's cold-compile wall beside the
        # previous run's (two runs in one chip command share the cache)
        prev = None
        if os.path.exists(RUNS_LOG):
            with open(RUNS_LOG) as f:
                lines = f.read().splitlines()
            prev = json.loads(lines[-1]) if lines else None
        run = {"t": round(time.time()), "device_kind": dev0.device_kind,
               "count": len(jax.devices()),
               "compile_wall_s": round(ctx["compile_wall_s"], 1),
               "cache_entries_at_start": ctx["cache_entries"],
               "cache_dir": ctx["cache_dir"]}
        say(f"compile wall: this_run_s={run['compile_wall_s']} "
            f"cache_entries_at_start={run['cache_entries_at_start']} "
            "previous_run_s="
            + (f"{prev['compile_wall_s']} (cache entries at its start: "
               f"{prev['cache_entries_at_start']})" if prev
               else "none recorded"))
        os.makedirs(os.path.dirname(RUNS_LOG), exist_ok=True)
        with open(RUNS_LOG, "a") as f:
            f.write(json.dumps(run) + "\n")
    say(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
