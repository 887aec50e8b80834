"""Fused frontier growth (ISSUE 18): the grow megakernel, device-resident
split search, the row-partition kernel, and the persisted autotuner.

1. **bitwise sweep** — int8/int16 models from tpu_hist_impl=fused (the
   megakernel's in-kernel split scan + device split records) are
   BYTE-IDENTICAL to the unfused xla composition: serial, 2/4 data
   shards, the resident AND the streamed layout, and with the pallas
   row-partition kernel (tpu_partition_impl=kernel).  int32 histogram
   accumulation is associative and the in-kernel scan runs the same
   elementwise f32 gain math as select(), so equality is exact, not
   approximate.
2. **device records vs host select()** — the [2K, F, 8] per-feature
   best records the kernel emits equal pack_pf_records of the host
   per_feature_best_split run on the same histograms, field for field.
3. **compile-ledger gate** — fusion SHRINKS (never grows) the training
   program zoo: n_programs with fused on <= the unfused count.
4. **autotune profile** — tune-mode measures + persists, load-mode
   resolves the same winners into _resolve_hist_impl, a missing bucket
   falls back to heuristics, and a profile from another topology raises
   AutotuneStaleProfile instead of quietly applying wrong winners.
5. **memory-pressure interaction** — the degradation ladder owns a
   fused_unfuse rung (fused -> pallas2 + host select) ordered between
   the scatter switch and the fine bucket policy; an injected OOM during
   a fused training descends it and completes byte-identical to an
   undisturbed run, and plan_training itemizes the fused record/parent
   buffers plus the autotune probe scratch.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.config import Config
from lightgbm_tpu.models.learner import TPUTreeLearner
from lightgbm_tpu.ops import split as SP
from lightgbm_tpu.ops.fused import fused_hist_scan, fused_supported
from lightgbm_tpu.ops.histogram import (bench_hist_operands,
                                        build_histogram_batched_t)
from lightgbm_tpu.utils import autotune, faultline, membudget
from lightgbm_tpu.utils.compile_ledger import LEDGER

PRECS = ("int8", "int16")

SPLIT_KW = dict(l1=0.0, l2=1.0, max_delta_step=0.0, min_data_in_leaf=1.0,
                min_sum_hessian=1e-3, min_gain_to_split=0.0)


def _problem(n=4096, f=10, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _train_text(X, y, prec, impl, rounds=5, **extra):
    p = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
         "min_data_in_leaf": 5, "verbosity": -1, "tpu_block_rows": 512,
         "tpu_hist_precision": prec, "tpu_hist_impl": impl,
         "tpu_quant_refit_leaves": False, **extra}
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    bst = lgb.train(p, ds, num_boost_round=rounds)
    return bst.model_to_string().split("\nparameters:")[0]


@pytest.fixture(scope="module")
def xy():
    return _problem()


@pytest.fixture(scope="module")
def xla_ref(xy):
    X, y = xy
    return {prec: _train_text(X, y, prec, "xla") for prec in PRECS}


# ---------------------------------------------------------------------------
# 1. fused-vs-unfused bitwise model sweep
# ---------------------------------------------------------------------------
class TestFusedBitwise:
    @pytest.mark.parametrize("prec", PRECS)
    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_resident_bitwise(self, xy, xla_ref, prec, shards):
        # fused == unfused AT EACH shard count.  (Serial-vs-sharded
        # equality is a separate, int8-only property — int16 quantized
        # rows are not sharding-invariant — pinned in test_quantized.)
        X, y = xy
        extra = ({} if shards == 1
                 else {"tree_learner": "data", "num_machines": shards})
        ref = (xla_ref[prec] if shards == 1
               else _train_text(X, y, prec, "xla", **extra))
        assert _train_text(X, y, prec, "fused", **extra) == ref

    @pytest.mark.parametrize("prec", PRECS)
    def test_streamed_bitwise(self, xy, prec):
        # streamed-vs-streamed: the streamed layout's quantization walks
        # rows in host-block order, so its models legitimately differ
        # from resident ones — the fusion claim is fused == unfused
        # WITHIN each layout
        X, y = xy
        ref = _train_text(X, y, prec, "xla", tpu_stream_mode="streamed")
        assert _train_text(X, y, prec, "fused",
                           tpu_stream_mode="streamed") == ref

    @pytest.mark.parametrize("prec", PRECS)
    def test_kernel_partition_bitwise(self, xy, xla_ref, prec):
        assert _train_text(X=xy[0], y=xy[1], prec=prec, impl="fused",
                           tpu_partition_impl="kernel") == xla_ref[prec]

    def test_kernel_partition_rejects_uncovered_modes(self, xy):
        # categorical splits keep the select-family lowerings; the
        # row-partition kernel must refuse loudly, not mis-route rows
        X, y = xy
        Xc = np.column_stack([np.abs(X[:, 0] * 3).astype(np.int32) % 4,
                              X[:, 1:]])
        p = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
             "min_data_in_leaf": 5, "verbosity": -1,
             "tpu_hist_precision": "int8", "tpu_hist_impl": "fused",
             "tpu_partition_impl": "kernel"}
        ds = lgb.Dataset(Xc, label=y, params={"max_bin": 63},
                         categorical_feature=[0])
        with pytest.raises(Exception, match="tpu_partition_impl=kernel"):
            lgb.train(p, ds, num_boost_round=2)

    def test_fused_degrades_outside_its_envelope(self, xy):
        # an unsupported mode (float precision: no in-kernel int scan)
        # degrades to the perfeature hist + host select INSIDE the same
        # grow program — same model as pallas2, no error
        X, y = xy
        assert (_train_text(X, y, "hilo", "fused")
                == _train_text(X, y, "hilo", "pallas2"))
        assert fused_supported("hilo") is not None
        assert fused_supported("int8") is None
        assert fused_supported("int8", has_cat=True) is not None


# ---------------------------------------------------------------------------
# 2. device split records vs the host select() oracle
# ---------------------------------------------------------------------------
class TestDeviceRecordsOracle:
    @pytest.mark.parametrize("precision", PRECS)
    def test_records_match_host_scan(self, precision):
        rng = np.random.default_rng(11)
        n, F, B, block, K = 1024, 6, 16, 128, 3
        bins_np = rng.integers(0, B, size=(n, F)).astype(np.uint8)
        bins_tb, stats, n_use = bench_hist_operands(bins_np, precision,
                                                    block)
        nb = n_use // block
        leaf = jnp.asarray(rng.integers(0, K, size=n_use)
                           .astype(np.int32).reshape(nb, block))
        slots = jnp.arange(K, dtype=jnp.int32)
        small = build_histogram_batched_t(bins_tb, stats, leaf, slots, B,
                                          precision, impl="xla")
        parent = small * 2 + jnp.flip(small, axis=0)
        C = 2 * K
        ctx_np = np.zeros((C + 1, 8), np.float32)
        ctx_np[:C, 0] = 3.0 + np.arange(C)          # sum_g
        ctx_np[:C, 1] = 7.0 + np.arange(C)          # sum_h
        ctx_np[:C, 2] = 64.0                        # count
        ctx_np[:C, 3] = -1e30
        ctx_np[:C, 4] = 1e30
        ctx_np[:C, 5] = (np.arange(C) % 2).astype(np.float32)
        ctx_np[C, :3] = (0.5, 0.25, 1.0)            # qscale
        meta_i = jnp.zeros((F, 8), jnp.int32).at[:, 0].set(B)
        meta_f = jnp.ones((F, 8), jnp.float32)

        hist, recs = fused_hist_scan(
            bins_tb, stats, leaf, slots, parent, jnp.asarray(ctx_np),
            meta_i, meta_f, B, precision, split_kw=SPLIT_KW)
        np.testing.assert_array_equal(np.asarray(hist), np.asarray(small))

        qs = jnp.asarray(ctx_np[C, :3])
        for j in range(C):
            k = j % K
            hs = small[k] if ctx_np[j, 5] > 0 else parent[k] - small[k]
            # compiled, as the kernel's scan is: op by op the host would
            # round a multiply-add twice where compiled code fuses it
            pf = jax.jit(functools.partial(
                SP.per_feature_best_split, **SPLIT_KW))(
                hs, ctx_np[j, 0], ctx_np[j, 1], ctx_np[j, 2],
                meta_i[:, 0], meta_i[:, 1], meta_i[:, 2], meta_i[:, 3],
                meta_f[:, 0], meta_f[:, 1],
                min_constraint=ctx_np[j, 3], max_constraint=ctx_np[j, 4],
                acc_scale=qs)
            expect = SP.pack_pf_records(pf)
            np.testing.assert_array_equal(np.asarray(recs[j]),
                                          np.asarray(expect),
                                          err_msg=f"child {j}")
            # and against the scan run op by op on the host, so that the
            # oracle is not compiled code alone: the same thresholds and
            # directions, the sums and gains to a few f32 roundings (one
            # multiply-add rounded twice is 2^-23 = 1.2e-7 relative)
            eager = SP.pack_pf_records(SP.per_feature_best_split(
                hs, ctx_np[j, 0], ctx_np[j, 1], ctx_np[j, 2],
                meta_i[:, 0], meta_i[:, 1], meta_i[:, 2], meta_i[:, 3],
                meta_f[:, 0], meta_f[:, 1],
                min_constraint=ctx_np[j, 3], max_constraint=ctx_np[j, 4],
                acc_scale=qs, **SPLIT_KW))
            np.testing.assert_allclose(np.asarray(recs[j]),
                                       np.asarray(eager), rtol=1e-6,
                                       atol=0, err_msg=f"child {j}, eager")
            # unpack round-trips the exact fields select() consumes
            back = SP.unpack_pf_records(recs[j])
            np.testing.assert_array_equal(np.asarray(back.gain),
                                          np.asarray(pf.gain))
            np.testing.assert_array_equal(np.asarray(back.threshold),
                                          np.asarray(pf.threshold))

    def test_auto_is_a_rule_never_a_probe(self, monkeypatch):
        # auto resolves from (platform, precision) alone: no kernel runs
        # to decide it, fused is explicit-only, int16 stays on xla
        cfg = Config({"objective": "binary", "num_leaves": 255})

        def resolve(prec):
            return TPUTreeLearner._resolve_hist_impl(cfg, 255, prec)[0]

        for prec in ("hilo", "int8", "int16"):
            assert resolve(prec) == "xla"            # cpu

        class _Tpu:
            platform = "tpu"

        monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Tpu()])
        assert resolve("hilo") == "pallas2"
        assert resolve("int8") == "pallas2"
        assert resolve("int16") == "xla"
        assert resolve("f32") == "xla"


# ---------------------------------------------------------------------------
# 3. compile-ledger gate: fusion shrinks, never grows, the program zoo
# ---------------------------------------------------------------------------
class TestCompileLedgerGate:
    def test_fusion_does_not_grow_program_zoo(self):
        X, y = _problem(n=2048, f=8, seed=3)
        counts = {}
        for impl in ("xla", "fused"):
            LEDGER.enable()
            LEDGER.reset()
            try:
                _train_text(X, y, "int8", impl, rounds=3)
                counts[impl] = LEDGER.n_programs()
            finally:
                LEDGER.enable(False)
                LEDGER.reset()
        assert counts["fused"] <= counts["xla"], (
            "fused frontier grew the program zoo: "
            f"{counts['fused']} programs vs {counts['xla']} unfused — "
            "the megakernel must live INSIDE the existing grow sites")


# ---------------------------------------------------------------------------
# 4. autotune profile: round-trip, fallback, stale refusal
# ---------------------------------------------------------------------------
class TestAutotuneProfile:
    def test_tune_round_trip_resolves_into_auto(self, tmp_path):
        path = str(tmp_path / "prof.json")
        cfg = Config({"objective": "binary", "tpu_autotune": "tune",
                      "tpu_autotune_profile": path})
        entry = autotune.resolve_autotune(cfg, 8192, 8, 64, "int8")
        assert entry is not None and os.path.exists(path)
        assert entry["hist_impl"] in ("xla", "pallas2", "fused")
        cfg2 = Config({"objective": "binary", "tpu_autotune": "load",
                       "tpu_autotune_profile": path})
        entry2 = autotune.resolve_autotune(cfg2, 8192, 8, 64, "int8")
        assert entry2["hist_impl"] == entry["hist_impl"]
        assert entry2["block_rows"] == entry["block_rows"]
        impl, block = TPUTreeLearner._resolve_hist_impl(
            cfg2, 64, "int8", tuned=entry2)
        assert impl == entry2["hist_impl"]
        assert block == entry2["block_rows"]

    def test_missing_bucket_in_load_mode_falls_back(self, tmp_path):
        path = str(tmp_path / "empty.json")
        autotune.save_profile(path, {
            "version": autotune.PROFILE_VERSION,
            **autotune.backend_fingerprint(), "entries": {}})
        cfg = Config({"objective": "binary", "tpu_autotune": "load",
                      "tpu_autotune_profile": path})
        assert autotune.resolve_autotune(cfg, 8192, 8, 64, "int8") is None
        # heuristics still apply: CPU auto resolves xla
        impl, block = TPUTreeLearner._resolve_hist_impl(cfg, 64, "int8",
                                                        tuned=None)
        assert impl == "xla"

    @pytest.mark.parametrize("mutate", [
        {"platform": "tpu"},
        {"device_count": 1024},
        {"version": -5},
    ])
    def test_stale_profile_refused(self, tmp_path, mutate):
        path = str(tmp_path / "stale.json")
        prof = {"version": autotune.PROFILE_VERSION,
                **autotune.backend_fingerprint(),
                "entries": {"r8192_f8_b64": {"hist_impl": "fused",
                                             "block_rows": 8192,
                                             "precision": "int8"}}}
        prof.update(mutate)
        autotune.save_profile(path, prof)
        cfg = Config({"objective": "binary", "tpu_autotune": "load",
                      "tpu_autotune_profile": path})
        with pytest.raises(autotune.AutotuneStaleProfile):
            autotune.resolve_autotune(cfg, 8192, 8, 64, "int8")

    def test_small_dataset_tune_clamps_or_falls_back(self, tmp_path):
        # regression: every candidate block used to exceed a small
        # dataset's rows -> 'no viable candidate' RuntimeError killed
        # the training run.  Now blocks clamp to the largest pow2 the
        # rows fill (3000 rows -> measured winner), and a dataset too
        # tiny for even the floor degrades to heuristics with a logged
        # warning instead of raising
        cfg = Config({"objective": "binary", "tpu_autotune": "tune",
                      "tpu_autotune_profile": str(tmp_path / "s.json")})
        entry = autotune.resolve_autotune(cfg, 3000, 10, 64, "int8")
        assert entry is not None and entry["block_rows"] <= 2048
        cfg2 = Config({"objective": "binary", "tpu_autotune": "tune",
                       "tpu_autotune_profile": str(tmp_path / "t.json")})
        assert autotune.resolve_autotune(cfg2, 300, 10, 16,
                                         "int8") is None
        assert not os.path.exists(str(tmp_path / "t.json"))

    def test_tuned_never_overrides_explicit_config(self):
        cfg = Config({"objective": "binary", "tpu_hist_impl": "xla",
                      "tpu_block_rows": 2048})
        impl, block = TPUTreeLearner._resolve_hist_impl(
            cfg, 64, "int8",
            tuned={"hist_impl": "fused", "block_rows": 8192})
        assert (impl, block) == ("xla", 2048)

    def test_learner_training_with_profile_stays_bitwise(self, xy,
                                                         xla_ref,
                                                         tmp_path):
        # end to end: tune writes the profile during learner init, the
        # tuned winners change only SPEED knobs — model bytes match the
        # plain xla reference exactly
        X, y = xy
        path = str(tmp_path / "train_prof.json")
        text = _train_text(X, y, "int8", "auto", tpu_autotune="tune",
                           tpu_autotune_profile=path)
        assert os.path.exists(path)
        assert text == xla_ref["int8"]


# ---------------------------------------------------------------------------
# 5. memory-pressure interaction
# ---------------------------------------------------------------------------
class TestMemoryPressure:
    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        faultline.reset()
        yield
        faultline.reset()

    def test_ladder_owns_fused_unfuse_rung(self):
        assert "fused_unfuse" in membudget.LADDER_STEPS
        cfg = Config({"objective": "binary", "tpu_hist_impl": "fused",
                      "tpu_ingest_chunk_rows": membudget.CHUNK_FLOOR,
                      "tpu_predict_chunk_rows": membudget.CHUNK_FLOOR})
        lad = membudget.DegradationLadder()
        step, over = lad.next_step(cfg)
        assert step == "fused_unfuse"
        assert over == {"tpu_hist_impl": "pallas2"}
        # an auto impl never unpins (it re-resolves per backend)
        cfg2 = Config({"objective": "binary",
                       "tpu_ingest_chunk_rows": membudget.CHUNK_FLOOR,
                       "tpu_predict_chunk_rows": membudget.CHUNK_FLOOR})
        step2, _ = membudget.DegradationLadder().next_step(cfg2)
        assert step2 == "bucket_policy_fine"

    def test_oom_during_fused_step_descends_bitwise(self):
        X, y = _problem(n=800, f=6, seed=0)
        base = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
                "min_data_in_leaf": 5, "verbosity": -1,
                "tpu_hist_precision": "int8", "tpu_hist_impl": "fused",
                "tpu_quant_refit_leaves": False,
                "tpu_ingest_chunk_rows": membudget.CHUNK_FLOOR,
                "tpu_predict_chunk_rows": membudget.CHUNK_FLOOR}
        ds = lgb.Dataset(X, label=y, params=dict(base))
        ref = lgb.train(dict(base), ds, num_boost_round=4,
                        keep_training_booster=True)
        ref_text = ref.model_to_string().split("\nparameters:")[0]
        bst = Booster(params=dict(base),
                      train_set=lgb.Dataset(X, label=y, params=dict(base)))
        for it in range(4):
            if it == 2:
                faultline.arm("device_alloc", action="oom", at=1)
            bst.update()
        steps = bst._driver._mem_ladder.describe()
        assert steps == ["fused_unfuse"], steps
        assert str(bst._driver.config.tpu_hist_impl) == "pallas2"
        assert (bst.model_to_string().split("\nparameters:")[0]
                == ref_text)

    def test_plan_itemizes_fused_and_autotune_scratch(self, tmp_path):
        X, y = _problem(n=800, f=6, seed=0)
        p = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
             "min_data_in_leaf": 5, "verbosity": -1,
             "tpu_hist_precision": "int8", "tpu_hist_impl": "fused",
             "tpu_autotune": "load",
             "tpu_autotune_profile": str(tmp_path / "none.json")}
        bst = Booster(params=p,
                      train_set=lgb.Dataset(X, label=y, params=p))
        bst.update()
        plan = membudget.plan_training(bst._driver.config,
                                       bst._driver.learner, 1)
        assert plan.components["fused_records"] > 0
        assert plan.components["fused_parent_hist"] > 0
        assert plan.components["autotune_scratch"] > 0
