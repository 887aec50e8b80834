"""Shape-stable programs (ROADMAP item 3 / ISSUE 6): the compile ledger,
grower memoization, buffer donation, and the launch-shape bucket policy.

The contract under test:

* a canonical binary train + predict + serve lifecycle on the default
  configuration compiles an EXACT, small set of ledgered programs;
* re-running an identical training in-process compiles nothing new (the
  grower/strategy memoization reuses the jitted executables) EXCEPT the
  Booster's own `learner.pre` / `learner.post` pair, closures over the
  objective's scalars that every Booster traces for itself (on the
  ledger since ISSUE 24; since ISSUE 32 the labels are their arguments,
  so a new dataset of the shape loads them from the persistent cache:
  tests/test_sharded_step.py);
* while enabled, the ledger charges every program JAX produces to the
  site whose call was in flight, and says whether the persistent cache
  answered it;
* buffer donation (tpu_donate_buffers) is bit-invisible: model files are
  identical with donation on or off, serial and sharded, and the int8
  cross-shard-count bitwise guarantee survives with donation enabled
  (the existing slow shard sweeps in test_sharded_agg/test_quantized now
  run WITH donation by default — this file keeps a fast 1/2-shard gate);
* the serving registry dedupes warmup across same-shaped models: loading
  a second model with an equal warm signature adds ZERO compiled
  programs (asserted on the predict kernel's own jit cache);
* the `wide` bucket policy produces strictly fewer launch shapes than
  `fine`, through the ONE shared ladder in ops/predict.py;
* `tools/perf_probe.py retrace` (the tier-1 retrace smoke at the bottom)
  keeps the lifecycle's n_programs under a hard bound, so a PR that
  doubles the program zoo fails loudly instead of silently inflating
  compile_s.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.models.learner import TPUTreeLearner
from lightgbm_tpu.ops.grower import (GrowerParams, canonical_params,
                                     make_grower, mode_flags_np)
from lightgbm_tpu.ops.predict import (_depth_bucket, predict_row_buckets,
                                      row_bucket)
from lightgbm_tpu.utils.compile_ledger import LEDGER, ledger_jit


def _data(n=3100, f=9, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.4 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


# deliberately off-beat shapes (47 bins, 13 leaves) so no other test
# module warms these jit caches first — the exact-count assertions
# depend on this file doing the first compile of its own configuration
P_LIFE = {"objective": "binary", "num_leaves": 13, "max_bin": 47,
          "min_data_in_leaf": 5, "tpu_block_rows": 512, "verbosity": -1}


@pytest.fixture
def ledger():
    LEDGER.enable()
    LEDGER.reset()
    try:
        yield LEDGER
    finally:
        LEDGER.enable(False)


# the bucketed step's per-objective closures: one program each PER
# BOOSTER, whatever was compiled before
PER_BOOSTER = ("learner.pre", "learner.post")


def shared_programs(ledger) -> int:
    """Programs of the sites whose executables outlive a Booster."""
    return ledger.n_programs() - sum(ledger.n_programs(s)
                                     for s in PER_BOOSTER)


class TestLedgerUnit:
    def test_counts_programs_not_calls(self, ledger):
        calls = []

        @ledger_jit(site="unit.f", static_argnames=("k",))
        def f(x, k: int):
            calls.append(1)
            return x * k

        f(jnp.ones(8), k=2)
        f(jnp.ones(8), k=2)          # cache hit: not a new program
        f(jnp.ones(8), k=3)          # new static value: new program
        f(jnp.ones(16), k=3)         # new aval: new program
        assert ledger.n_programs("unit.f") == 3
        rep = {a["site"]: a["programs"] for a in ledger.report()}
        assert rep["unit.f"] == 3

    def test_disabled_ledger_records_nothing(self):
        LEDGER.enable(False)
        LEDGER.reset()

        @ledger_jit(site="unit.g")
        def g(x):
            return x + 1

        g(jnp.ones(4))  # compiles, but the disabled ledger records nothing
        assert LEDGER.n_programs() == 0

    def test_wrapper_delegates_jit_internals(self):
        f = ledger_jit(lambda x: x * 2, site="unit.h")
        f(jnp.ones(4))
        # transparent delegation: the serving tests poke _cache_size()
        assert f._cache_size() >= 1


def _loose_unit_program(x):
    return x * 2.5 + 0.125


COLD_THEN_WARM = """
import json, sys
sys.path.insert(0, {root!r})
import numpy as np
import jax
import lightgbm_tpu as lgb
from lightgbm_tpu.utils.compile_ledger import LEDGER

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
LEDGER.enable()
rng = np.random.default_rng(5)
X = rng.normal(size=(900, 5))
y = (X[:, 0] > 0).astype(np.float64)
p = {{"objective": "binary", "num_leaves": 5, "max_bin": 15,
     "min_data_in_leaf": 5, "verbosity": -1}}
bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
bst.update()
print(json.dumps(LEDGER.compiles()))
"""


class TestCompileAttribution:
    """ISSUE 24: while the ledger is on, every program JAX produces is
    charged to the `ledger_jit` site whose call was in flight."""

    def test_programs_land_at_their_site_or_at_none(self, ledger):
        from lightgbm_tpu import obs
        from lightgbm_tpu.utils.compile_ledger import NO_SITE

        @ledger_jit(site="unit.inner")
        def inner(x):
            return x * 3 + 1

        @ledger_jit(site="unit.outer")
        def outer(x):
            return inner(x) - 2   # traced inline: the outer site's program

        def count(site):
            return sum(obs.REGISTRY.value("lgbm_compile_programs_total",
                                          site=site, cache=c)
                       for c in ("hit", "miss"))

        before = {s: count(s) for s in ("unit.outer", "unit.inner", NO_SITE)}
        outer(jnp.ones(13))
        outer(jnp.ones(13))                       # cache hit: no program
        jax.jit(_loose_unit_program)(jnp.ones(13))  # off the ledger
        rows = ledger.compiles()
        assert all(set(r) == {"site", "fun_name", "compile_s", "cache"}
                   and r["cache"] in ("hit", "miss") and r["compile_s"] > 0
                   for r in rows)
        assert [(r["site"], r["fun_name"]) for r in rows
                if "unit" in r["site"] or "loose" in r["fun_name"]] == [
            ("unit.outer", "jit(outer)"),
            (NO_SITE, "jit(_loose_unit_program)")]
        assert count("unit.outer") == before["unit.outer"] + 1
        assert count("unit.inner") == before["unit.inner"]
        assert count(NO_SITE) >= before[NO_SITE] + 1
        # programs() keeps counting by jit cache growth, as before
        assert ledger.n_programs("unit.outer") == 1

    def test_a_compile_is_a_span_under_whatever_was_open(self, ledger):
        from lightgbm_tpu import obs

        obs.configure(mode="trace")
        obs.reset_events()
        try:
            @ledger_jit(site="unit.spanned")
            def f(x):
                return x * 7 - 3

            x = jnp.ones(17)
            with obs.span("unit/open"):
                f(x)
            evs = obs.events()
        finally:
            obs.configure(mode="off")
            obs.reset_events()
        opened = next(e for e in evs if e["name"] == "unit/open")
        (comp,) = [e for e in evs if e["name"] == "compile"
                   and e["tags"]["site"] == "unit.spanned"]
        assert comp["parent_id"] == opened["id"]
        assert comp["tags"]["fun_name"] == "jit(f)"
        (row,) = [r for r in ledger.compiles()
                  if r["site"] == "unit.spanned"]
        assert comp["dur"] / 1e6 == pytest.approx(row["compile_s"])
        assert comp["tags"]["cache"] == row["cache"]
        # start = end - duration: inside the span that asked for it
        assert opened["ts"] <= comp["ts"]
        assert comp["ts"] + comp["dur"] <= opened["ts"] + opened["dur"]

    def test_off_means_no_listener(self):
        from jax._src import monitoring as mon

        def listening():
            return (LEDGER._on_duration
                    in mon.get_event_duration_listeners(),
                    LEDGER._on_event in mon.get_event_listeners(),
                    LEDGER._on_start in mon.get_scalar_listeners())

        LEDGER.enable(False)
        assert listening() == (False, False, False)
        LEDGER.enable()
        LEDGER.enable()            # twice is once
        try:
            assert listening() == (True, True, True)
            assert mon.get_event_duration_listeners().count(
                LEDGER._on_duration) == 1
            assert mon.get_scalar_listeners().count(LEDGER._on_start) == 1
        finally:
            LEDGER.enable(False)
            LEDGER.enable(False)
        assert listening() == (False, False, False)
        LEDGER.reset()
        jax.jit(_loose_unit_program)(jnp.ones(19))
        assert LEDGER.compiles() == [] and LEDGER.births() == []

    def test_cold_then_warm_cache_turns_miss_to_hit(self, tmp_path):
        """A fresh process per run: the first fills an empty persistent
        cache, the second, same data, loads from it."""
        import json
        import os
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        runs = []
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", COLD_THEN_WARM.format(root=root)],
                capture_output=True, text=True, timeout=300, cwd=tmp_path,
                env={**os.environ, "JAX_PLATFORMS": "cpu",
                     "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
            assert proc.returncode == 0, proc.stderr[-2000:]
            rows = json.loads(proc.stdout.strip().splitlines()[-1])
            runs.append({r["site"]: r["cache"] for r in rows
                         if r["site"] != "(none)"})
        cold, warm = runs
        sites = {"learner.pre", "grower.grow", "learner.post"}
        assert set(cold) == set(warm) == sites
        assert set(cold.values()) == {"miss"}
        assert set(warm.values()) == {"hit"}


@jax.jit
def _inner_unit_program(x):
    return jnp.where(x > 0, x, 0.0) * 2


def _nested_unit_program(x):
    return _inner_unit_program(x) + jnp.sum(x)    # four inner jits deep


def _stage_counter(stage: str, what: str, site: str) -> float:
    from lightgbm_tpu import obs

    return obs.REGISTRY.value(f"lgbm_{stage}_{what}_total", site=site)


class TestProgramBirths:
    """ISSUE 39: a program's birth in three stages (trace, lower,
    compile), each charged to the site in flight, nested ones counted
    once."""

    STAGES = ("trace", "lower", "compile")

    def test_a_first_call_is_one_of_each_stage_and_a_second_is_none(
            self, ledger):
        f = ledger_jit(_nested_unit_program, site="unit.born")
        x = jnp.ones(21)
        before = {(st, what): _stage_counter(st, what, "unit.born")
                  for st in ("trace", "lower")
                  for what in ("programs", "seconds")}
        f(x)
        rows = [b for b in ledger.births() if b["site"] == "unit.born"]
        assert all(set(b) - {"cache"} == {"site", "fun_name", "stage",
                                          "seconds", "self_s", "depth"}
                   and 0 <= b["self_s"] <= b["seconds"] for b in rows)
        assert all(("cache" in b) == (b["stage"] == "compile")
                   for b in rows)
        outer = [b for b in rows if b["depth"] == 0]
        assert [(b["stage"], b["fun_name"]) for b in outer] == [
            ("trace", "_nested_unit_program"),
            ("lower", "jit(_nested_unit_program)"),
            ("compile", "jit(_nested_unit_program)")]
        # the inner jits were traced too, inside the one trace
        assert {"_inner_unit_program", "_where"} <= {
            b["fun_name"] for b in rows if b["depth"] > 0}
        assert {b["stage"] for b in rows if b["depth"] > 0} == {"trace"}
        for st in ("trace", "lower"):
            assert _stage_counter(st, "programs", "unit.born") \
                == before[st, "programs"] + 1
            assert _stage_counter(st, "seconds", "unit.born") \
                - before[st, "seconds"] == pytest.approx(
                    sum(b["self_s"] for b in rows if b["stage"] == st))
        assert ledger.compiles() == [
            {"site": b["site"], "fun_name": b["fun_name"],
             "compile_s": b["seconds"], "cache": b["cache"]}
            for b in ledger.births() if b["stage"] == "compile"]
        # nothing in a window: a warmed site fires no stage event
        y, n = x + 1, len(ledger.births())
        f(x)
        f(y)
        assert len(ledger.births()) == n

    @pytest.mark.parametrize("method, stages", [
        ("trace", ["trace"]), ("lower", ["trace", "lower"])])
    def test_trace_and_lower_on_the_wrapper_name_its_site(
            self, ledger, method, stages):
        f = ledger_jit(lambda x: jnp.cos(x) * 3, site="unit.aot")
        getattr(f, method)(jax.ShapeDtypeStruct((23,), jnp.float32))
        rows = [b for b in ledger.births() if b["depth"] == 0]
        assert [(b["site"], b["stage"]) for b in rows] == [
            ("unit.aot", st) for st in stages]
        assert ledger.n_programs() == 0      # no program was recorded
        # and with the ledger off they are the jit's own
        LEDGER.enable(False)
        assert getattr(f, method)(jnp.ones(5)) is not None
        assert len(ledger.births()) == len(
            [b for b in ledger.births() if b["site"] == "unit.aot"])

    def test_closed_over_bytes_is_a_trace_at_the_site(self, ledger):
        from lightgbm_tpu.utils.compile_ledger import closed_over_bytes

        table = jnp.ones((29, 3))
        f = ledger_jit(lambda x: x + table.sum(), site="unit.gauge")
        assert closed_over_bytes(f, (jnp.ones(3),), {}, [29]) == 29 * 3 * 4
        (row,) = [b for b in ledger.births()
                  if b["site"] == "unit.gauge" and b["depth"] == 0]
        assert row["stage"] == "trace"

    def test_nested_stages_are_not_counted_twice(self, ledger):
        """(a) and (c): self seconds add up to no more than the wall of
        the calls that caused them, in the rows and in the counters."""
        sites = ("unit.nest", "unit.nest2")
        before = {(st, s): _stage_counter(st, "seconds", s)
                  for st in ("trace", "lower") for s in sites}
        f = ledger_jit(_nested_unit_program, site=sites[0])
        g = ledger_jit(lambda x: _nested_unit_program(x) * 2,
                       site=sites[1])
        t0 = time.perf_counter()
        f.trace(jax.ShapeDtypeStruct((31,), jnp.float32))
        f(jnp.ones(31))
        g(jnp.ones(31))
        wall = time.perf_counter() - t0
        rows = [b for b in ledger.births() if b["site"] in sites]
        nested = sum(b["seconds"] for b in rows)
        own = sum(b["self_s"] for b in rows)
        assert own <= wall < nested * 10
        assert own < nested                   # inner traces were enclosed
        assert own == pytest.approx(
            sum(b["seconds"] for b in rows if b["depth"] == 0))
        counted = sum(_stage_counter(st, "seconds", s) - before[st, s]
                      for st in ("trace", "lower") for s in sites)
        assert counted == pytest.approx(
            sum(b["self_s"] for b in rows if b["stage"] != "compile"))
        assert counted <= wall

    def test_an_eager_op_outside_every_site_is_none_in_all_stages(
            self, ledger):
        from lightgbm_tpu.utils.compile_ledger import NO_SITE

        jnp.ones(37) * 41.5                   # shapes no other test uses
        rows = [b for b in ledger.births() if b["depth"] == 0]
        assert rows and {b["site"] for b in rows} == {NO_SITE}
        assert [b["stage"] for b in rows
                if "multiply" in b["fun_name"]] == list(self.STAGES)

    def test_stages_are_spans_under_whatever_was_open(self, ledger):
        from lightgbm_tpu import obs

        obs.configure(mode="trace")
        obs.reset_events()
        try:
            f = ledger_jit(_nested_unit_program, site="unit.tree")
            with obs.span("unit/build"):
                f.trace(jax.ShapeDtypeStruct((43,), jnp.float32))
            with obs.span("unit/dispatch"):
                f(jnp.ones(43))
            evs = {e["id"]: e for e in obs.events()}
        finally:
            obs.configure(mode="off")
            obs.reset_events()
        mine = [e for e in evs.values()
                if e["tags"].get("site") == "unit.tree"]
        assert [(e["name"], evs[e["parent_id"]]["name"],
                 e["tags"]["fun_name"]) for e in mine] == [
            ("program/trace", "unit/build", "_nested_unit_program"),
            ("program/trace", "unit/dispatch", "_nested_unit_program"),
            ("program/lower", "unit/dispatch",
             "jit(_nested_unit_program)"),
            ("compile", "unit/dispatch", "jit(_nested_unit_program)")]
        # the traces inside the first trace are its `inner=`, not spans;
        # the second found the first in JAX's trace cache
        assert mine[0]["tags"]["inner"] >= 4
        assert "inner" not in mine[1]["tags"]
        assert mine[1]["dur"] < mine[0]["dur"]
        rows = [b for b in ledger.births()
                if b["site"] == "unit.tree" and b["depth"] == 0]
        assert [b["stage"] for b in rows] == ["trace", "trace", "lower",
                                              "compile"]
        for e, b in zip(mine, rows):
            # a span is on the tracer's clock around JAX's own
            assert e["dur"] / 1e6 >= b["seconds"] * 0.999
            parent = evs[e["parent_id"]]
            assert parent["ts"] <= e["ts"] and e["ts"] + e["dur"] \
                <= parent["ts"] + parent["dur"]

    def test_a_stage_inside_another_stage_is_its_child(self, ledger):
        """An eager op run while a function is traced is a program of its
        own, born inside that trace: its spans are the trace's children
        and its seconds leave the trace's self time."""
        from lightgbm_tpu import obs

        def folds_a_constant(x):
            with jax.ensure_compile_time_eval():
                k = jnp.arange(47.0).sum() * 0.25
            return x * k

        obs.configure(mode="trace")
        obs.reset_events()
        try:
            ledger_jit(folds_a_constant, site="unit.fold").trace(
                jax.ShapeDtypeStruct((47,), jnp.float32))
            evs = obs.events()
        finally:
            obs.configure(mode="off")
            obs.reset_events()
        (outer,) = [e for e in evs if e["name"] == "program/trace"
                    and e["parent_id"] is None]
        inside = [e for e in evs if e is not outer]
        assert {e["name"] for e in inside} == {"program/lower", "compile"}
        assert all(e["parent_id"] == outer["id"] for e in inside)
        assert sum(e["dur"] for e in inside) <= outer["dur"]
        rows = ledger.births()
        (row,) = [b for b in rows if b["depth"] == 0]
        assert row["fun_name"] == "folds_a_constant"
        assert row["self_s"] == pytest.approx(row["seconds"] - sum(
            b["seconds"] for b in rows if b["depth"] == 1))
        assert {b["stage"] for b in rows if b["depth"] >= 1} == set(
            self.STAGES)


class TestBucketPolicy:
    def test_wide_ladder_is_strictly_smaller(self):
        chunk = 65536
        wide = predict_row_buckets(chunk, chunk, policy="wide")
        fine = predict_row_buckets(chunk, chunk, policy="fine")
        assert wide == [4096, 16384, 65536]
        assert fine == [1024, 2048, 4096, 8192, 16384, 32768, 65536]
        assert len(wide) < len(fine)
        # row_bucket lands every n on its policy's ladder
        for n in (1, 100, 4096, 4097, 20000, 65536, 70000):
            assert row_bucket(n, chunk, policy="wide") in wide
            assert row_bucket(n, chunk, policy="fine") in fine
            assert row_bucket(n, chunk, policy="wide") >= min(n, chunk)

    def test_depth_bucket_floors(self):
        assert [_depth_bucket(d, "wide") for d in (1, 3, 8, 9, 17)] == \
            [8, 8, 8, 16, 32]
        assert [_depth_bucket(d, "fine") for d in (1, 3, 8, 9, 17)] == \
            [1, 4, 8, 16, 32]

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="tpu_bucket_policy"):
            row_bucket(10, 1024, policy="chunky")
        X, y = _data(600, 4)
        config = Config({"objective": "binary",
                         "tpu_bucket_policy": "chunky"})
        td = TrainingData.from_matrix(X, y, config)
        with pytest.raises(ValueError, match="tpu_bucket_policy"):
            TPUTreeLearner(config, td)

    def test_wide_ramp_step_halves_preround_count(self):
        X, y = _data(1200, 6, seed=3)
        cfg = dict(P_LIFE, tpu_split_batch=8)
        config_w = Config(dict(cfg, tpu_bucket_policy="wide"))
        lw = TPUTreeLearner(config_w,
                            TrainingData.from_matrix(X, y, config_w))
        config_f = Config(dict(cfg, tpu_bucket_policy="fine"))
        lf = TPUTreeLearner(config_f,
                            TrainingData.from_matrix(X, y, config_f))
        assert lw.params.ramp_step == 4 and lf.params.ramp_step == 2


class TestCanonicalParams:
    def test_folded_fields_share_one_grower(self):
        base = dict(num_leaves=7, num_bins=16, block_rows=256,
                    precision="hilo", l1=0.0, l2=1.0, max_delta_step=0.0,
                    min_data_in_leaf=1.0, min_sum_hessian=1e-3,
                    min_gain_to_split=0.0, max_depth=0)
        a = GrowerParams(**base, quant_round="stochastic",
                         cegb_tradeoff=1.0)
        b = GrowerParams(**base, quant_round="nearest", cegb_tradeoff=3.0)
        assert canonical_params(a) == canonical_params(b)
        # memoized: the SAME jitted callable comes back
        ga = make_grower(canonical_params(a), 4)
        gb = make_grower(canonical_params(b), 4)
        assert ga is gb

    def test_mode_flags_vector(self):
        mf = mode_flags_np(quant_round="nearest", quant_refit=True,
                           cegb_tradeoff=2.0, cegb_penalty_split=0.5)
        np.testing.assert_array_equal(mf, [0.0, 1.0, 2.0, 0.5])


class TestLifecycleProgramCounts:
    def test_exact_counts_and_train_twice_compiles_nothing(self, ledger):
        """The canonical binary train + predict + serve lifecycle on the
        default (serial, bucketed) configuration: EXACT ledgered program
        counts, and an identical re-train reuses every executable."""
        from lightgbm_tpu.serving import ServingSession

        X, y = _data()
        ds = lgb.Dataset(X, label=y, params=P_LIFE)
        bst = lgb.train(P_LIFE, ds, num_boost_round=3,
                        keep_training_booster=True)
        # ONE grow program for the whole training run
        assert ledger.n_programs("grower.grow") == 1
        assert [ledger.n_programs(s) for s in PER_BOOSTER] == [1, 1]
        after_train = shared_programs(ledger)

        # identical second training: the memoized grower (and every
        # other shared site) reuses its compiled executables; the
        # Booster's own pre/post pair is traced and compiled again
        ds2 = lgb.Dataset(X, label=y, params=P_LIFE)
        lgb.train(P_LIFE, ds2, num_boost_round=3,
                  keep_training_booster=True)
        assert shared_programs(ledger) == after_train, (
            "a second identical train() compiled new programs:\n"
            + ledger.format_report())
        assert [ledger.n_programs(s) for s in PER_BOOSTER] == [2, 2]

        # serve: warmup compiles exactly the wide policy's bucket ladder
        # (one 4096-row bucket) for the class-scores kernel
        sess = ServingSession(params={"serving_max_batch_rows": 4096,
                                      "verbosity": -1})
        sess.load("m", booster=bst)
        got = sess.predict("m", X[:37], raw_score=True)
        # tpu_predict_device pinned per call: an unqualified device="tpu"
        # on a CPU host would auto-veto to the native walker and the
        # comparison would be device-kernel vs f64 walker ulps
        np.testing.assert_array_equal(
            got, bst.predict(X[:37], raw_score=True, device="tpu",
                             tpu_predict_device="true"))
        serve_programs = ledger.n_programs()

        # ISSUE 11 gate: the whole overload/robustness layer is host-
        # side control flow — admission sheds, a priority predict, a
        # deadline-capped predict, a device failover onto the native
        # walker, and the drain lifecycle must compile ZERO new
        # programs on top of the warmed serve lifecycle
        from lightgbm_tpu.serving import ServingOverloaded
        from lightgbm_tpu.utils import faultline

        sess.predict("m", X[:23], priority="high", deadline_ms=30000)
        import time as _time

        sess.admission._level = 1.0  # force an admission shed
        sess.admission.min_level = 1  # bypass the one-batch floor
        # pin the lazy AIMD update past the test so it cannot re-open
        # the level before the shed lands
        sess.admission._next_update = _time.monotonic() + 60.0
        try:
            with pytest.raises(ServingOverloaded):
                sess.predict("m", X[:23], priority="low")
        finally:
            sess.admission._level = float(sess.admission.queue_rows)
            sess.admission.min_level = 4096
        faultline.reset()
        faultline.arm("serve_dispatch", action="raise", times=1)
        try:
            sess.predict("m", X[:23])  # served via walker failover
        finally:
            faultline.reset()
        assert sess.drain()["drained"] is True
        sess.close()
        assert ledger.n_programs() == serve_programs, (
            "admission/drain/failover compiled new programs:\n"
            + ledger.format_report())

        sites = {a["site"]: a["programs"] for a in ledger.report()}
        assert sites == {"grower.grow": 1, "predict.class_scores": 1,
                         "learner.pre": 2, "learner.post": 2}, \
            ledger.format_report()
        # the regression gate the tier-1 smoke enforces: the whole
        # lifecycle stays a countable handful of programs
        assert shared_programs(ledger) <= 4


class TestDonationBitwise:
    def _model_text(self, X, y, **cfg):
        params = dict(P_LIFE, tpu_shape_buckets=0)
        params.update(cfg)
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.train(params, ds, num_boost_round=3,
                        keep_training_booster=True)
        return bst.model_to_string().split("\nparameters:")[0]

    def test_donation_is_bit_invisible_serial(self):
        X, y = _data(2048, 8, seed=5)
        on = self._model_text(X, y, tpu_donate_buffers=True)
        off = self._model_text(X, y, tpu_donate_buffers=False)
        assert on == off

    def test_int8_shard_bitwise_with_donation(self):
        """The PR-4/PR-5 guarantee with donation enabled: int8 model
        files bit-identical serial vs 2/4-shard scatter (the full
        1/2/4/8 sweep stays in test_sharded_agg's slow tier, which now
        also runs with donation by default)."""
        X, y = _data(2048, 8, seed=9)
        # refit off like the slow shard sweeps: the refit leaf psum is
        # the one f32 reduction whose shard-order ulps may reach values
        q = dict(tpu_hist_precision="int8", tpu_donate_buffers=True,
                 tpu_quant_refit_leaves=False)
        serial = self._model_text(X, y, **q)
        for shards in (2,):
            sharded = self._model_text(X, y, tree_learner="data",
                                       num_machines=shards, **q)
            assert serial == sharded, f"int8 mismatch at {shards} shards"
        # and donation itself changed nothing
        off = self._model_text(X, y, **{**q, "tpu_donate_buffers": False})
        assert serial == off

    def test_quant_round_mode_rides_one_program(self, ledger):
        """The traced rounding-mode flag: nearest vs stochastic share
        ONE grow program (previously distinct static closures) and still
        produce different (mode-correct) models."""
        X, y = _data(1600, 7, seed=13)
        # refit off: refit recomputes leaf values from TRUE f32 sums, so
        # with identical structures the two modes' models could coincide
        params = dict(P_LIFE, tpu_hist_precision="int16",
                      tpu_quant_refit_leaves=False)

        def run(round_mode):
            p = dict(params, tpu_quant_round=round_mode)
            ds = lgb.Dataset(X, label=y, params=p)
            bst = lgb.train(p, ds, num_boost_round=2,
                            keep_training_booster=True)
            return bst.model_to_string().split("\nparameters:")[0]

        a = run("stochastic")
        grower_programs = ledger.n_programs("grower.grow")
        b = run("nearest")
        assert ledger.n_programs("grower.grow") == grower_programs, \
            "flipping tpu_quant_round compiled a NEW grow program"
        assert a != b  # the traced flag actually changes the rounding


class TestCheckpointRetrace:
    def test_checkpointed_train_and_resume_add_zero_programs(
            self, ledger, tmp_path):
        """Bench hygiene (ISSUE 7): interval checkpointing is pure host
        IO + device_get — a checkpointed train (and a resumed one) must
        add ZERO programs to the CompileLedger beyond what the identical
        un-checkpointed train compiles."""
        X, y = _data(1400, 6, seed=17)
        ds = lgb.Dataset(X, label=y, params=P_LIFE)
        lgb.train(P_LIFE, ds, num_boost_round=3,
                  keep_training_booster=True)
        base = shared_programs(ledger)

        p = dict(P_LIFE, tpu_checkpoint_dir=str(tmp_path),
                 tpu_checkpoint_interval=1)
        ds2 = lgb.Dataset(X, label=y, params=p)
        lgb.train(p, ds2, num_boost_round=3, keep_training_booster=True)
        assert shared_programs(ledger) == base, (
            "checkpointing compiled new programs:\n"
            + ledger.format_report())

        ds3 = lgb.Dataset(X, label=y, params=p)
        bst = lgb.train(p, ds3, num_boost_round=5,
                        keep_training_booster=True, resume=True)
        assert bst.num_trees() == 5
        assert shared_programs(ledger) == base, (
            "checkpoint resume compiled new programs:\n"
            + ledger.format_report())
        # three Boosters, three pre/post pairs, and nothing else
        assert [ledger.n_programs(s) for s in PER_BOOSTER] == [3, 3]


class TestServingWarmupDedupe:
    def test_second_same_shaped_model_adds_zero_programs(self):
        from lightgbm_tpu.ops.predict import _class_scores_kernel
        from lightgbm_tpu.serving import ServingSession

        X, y = _data(1500, 6, seed=21)

        def train_one():
            p = dict(P_LIFE)
            ds = lgb.Dataset(X, label=y, params=p)
            return lgb.train(p, ds, num_boost_round=3,
                             keep_training_booster=True)

        b1, b2 = train_one(), train_one()
        sess = ServingSession(params={"serving_max_batch_rows": 2048,
                                      "verbosity": -1})
        sess.load("m1", booster=b1)
        before = _class_scores_kernel._cache_size()
        st1 = sess.stats()
        sess.load("m2", booster=b2)  # equal warm signature
        assert _class_scores_kernel._cache_size() == before, \
            "a same-shaped second model compiled new predict programs"
        # the dedupe also skipped the warmup device launches, but the
        # shape accounting still covers m2: its first real predict is a
        # cache HIT, not a miss
        assert sess.stats()["compiles_warmup"] > st1["compiles_warmup"]
        got = sess.predict("m2", X[:33], raw_score=True)
        np.testing.assert_array_equal(
            got, b2.predict(X[:33], raw_score=True, device="tpu",
                            tpu_predict_device="true"))
        assert sess.stats()["compile_cache_misses"] == 0
        assert _class_scores_kernel._cache_size() == before
        sess.close()


class TestRetraceSmoke:
    """The tier-1 wiring for `tools/perf_probe.py retrace`: the canonical
    lifecycle audit runs as a fast smoke, so a future PR that doubles
    n_programs fails HERE instead of silently inflating compile_s in the
    next bench round."""

    def test_retrace_lifecycle_bounds(self):
        import importlib.util
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "_perf_probe", os.path.join(root, "tools", "perf_probe.py"))
        probe = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(probe)
        try:
            phases, total = probe.run_retrace(n=2000, f=6, leaves=7,
                                              bins=31, iters=2)
        finally:
            LEDGER.enable(False)
        # an identical second train compiles NOTHING but its own
        # Booster's learner.pre / learner.post
        labels = list(phases)
        deltas = {}
        prev = 0
        for label in labels:
            deltas[label] = phases[label] - prev
            prev = phases[label]
        assert deltas["second identical train"] == 2, phases
        # a same-shaped second serving model adds at most the batcher's
        # own bucket (it must not re-compile the first model's shapes)
        assert deltas["serve (2 same-shaped models)"] <= 1, phases
        # the hard regression gate: the whole lifecycle is a handful of
        # programs — double the zoo and this fails loudly
        assert total <= 10, (phases, total)
