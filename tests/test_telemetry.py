"""Unified telemetry (ISSUE 10): registry thread-safety, span
nesting/export schema, Prometheus endpoint agreement with /stats, the
telemetry-off overhead bound, bitwise-invisibility of tracing, log
attribution, and the multihost trace merge."""

import json
import os
import re
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs.metrics import MetricsRegistry, histogram_quantile

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Every test leaves the process-global telemetry policy off and the
    span buffer empty — other test modules must keep seeing the default
    near-zero-cost path."""
    yield
    obs.configure(mode="off", trace_dir="")
    obs.flush()
    obs.reset_events()


def _problem(n=400, f=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


_P = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
      "min_data_in_leaf": 5, "verbosity": -1}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        r = MetricsRegistry()
        r.inc("a_total", 2, phase="x")
        r.inc("a_total", 3, phase="x")
        r.inc("a_total", 1, phase="y")
        assert r.value("a_total", phase="x") == 5
        assert r.value("a_total", phase="y") == 1
        assert r.value("a_total", phase="missing") == 0
        r.set_gauge("g", 7.5)
        r.set_gauge("g", 2.5)
        assert r.value("g") == 2.5
        r.observe("h_seconds", 0.3, buckets=(0.1, 0.5, 1.0))
        r.observe("h_seconds", 0.7, buckets=(0.1, 0.5, 1.0))
        n, s = r.histogram_stats("h_seconds")
        assert n == 2 and abs(s - 1.0) < 1e-12
        assert r.histogram_samples("h_seconds") == [0.3, 0.7]

    def test_kind_conflict_raises(self):
        r = MetricsRegistry()
        r.inc("m")
        with pytest.raises(ValueError, match="already registered"):
            r.observe("m", 1.0)

    def test_label_named_name_allowed(self):
        # the collective metrics label by collective name — the API must
        # accept a label literally called `name`
        r = MetricsRegistry()
        r.inc("c_total", 1, name="sync_sums")
        r.observe("w_seconds", 0.01, name="sync_sums")
        assert r.value("c_total", name="sync_sums") == 1

    def test_thread_safety_hammer(self):
        r = MetricsRegistry()
        threads, per = 16, 5000

        def work(k):
            for i in range(per):
                r.inc("hammer_total")
                r.inc("hammer_total", 1, worker=str(k % 4))
                r.observe("hammer_seconds", (i % 10) / 10.0,
                          buckets=(0.2, 0.5, 0.8))

        ts = [threading.Thread(target=work, args=(k,))
              for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert r.value("hammer_total") == threads * per
        assert sum(r.value("hammer_total", worker=str(w))
                   for w in range(4)) == threads * per
        n, _ = r.histogram_stats("hammer_seconds")
        assert n == threads * per

    def test_quantile_interpolation(self):
        r = MetricsRegistry()
        for v in (0.05, 0.15, 0.15, 0.25):  # buckets 0.1 / 0.2 / 0.3
            r.observe("q_seconds", v, buckets=(0.1, 0.2, 0.3))
        # rank(0.5) = 2 -> second bucket (1 below it, 2 inside):
        # 0.1 + 0.1 * (2 - 1) / 2 = 0.15
        assert abs(r.histogram_quantile("q_seconds", 0.5) - 0.15) < 1e-12
        # empty histogram -> 0.0
        assert r.histogram_quantile("missing", 0.99) == 0.0

    def test_prometheus_text_parses_and_is_cumulative(self):
        r = MetricsRegistry()
        r.inc("x_total", 3, help="a counter", phase="a b\"c")
        r.set_gauge("y", 1.5)
        for v in (0.05, 0.3, 2.0):
            r.observe("z_seconds", v, buckets=(0.1, 1.0))
        text = r.to_prometheus_text()
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+$|'
            r'^# (HELP|TYPE) .*$')
        for line in text.strip().splitlines():
            assert sample.match(line), f"unparseable line: {line!r}"
        # histogram buckets cumulative and +Inf == count
        buckets = {}
        for line in text.splitlines():
            m = re.match(r'z_seconds_bucket\{le="([^"]+)"\} (\d+)', line)
            if m:
                buckets[m.group(1)] = int(m.group(2))
        assert buckets["+Inf"] == 3
        vals = [buckets[k] for k in sorted(buckets, key=lambda s: (
            float("inf") if s == "+Inf" else float(s)))]
        assert vals == sorted(vals)
        assert 'phase="a b\\"c"' in text  # label escaping survives


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class TestSpans:
    def test_off_mode_is_shared_null_cm(self):
        assert obs.mode() == "off"
        cm1 = obs.span("anything", tag=1)
        cm2 = obs.span("else")
        assert cm1 is cm2  # the shared null context manager
        with cm1:
            pass
        assert obs.events() == []

    def test_nesting_depth_and_parent_tags(self):
        obs.configure(mode="trace")
        obs.reset_events()
        with obs.span("outer"):
            with obs.span("inner"):
                time.sleep(0.001)
        evs = {e["name"]: e for e in obs.events()}
        assert evs["inner"]["tags"]["parent"] == "outer"
        assert evs["inner"]["tags"]["depth"] == 1
        assert evs["outer"]["tags"]["depth"] == 0
        # child window nested inside the parent's
        o, i = evs["outer"], evs["inner"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6

    def test_chrome_trace_schema_roundtrip(self, tmp_path):
        obs.configure(mode="trace", trace_dir=str(tmp_path))
        obs.reset_events()
        with obs.span("a", iteration=3):
            with obs.span("b"):
                pass
        obs.event("watchdog_fired", name="sync")
        path = obs.write_chrome_trace()
        obs.flush()
        tr = json.loads(open(path).read())  # parses = loadable
        assert isinstance(tr["traceEvents"], list)
        phs = set()
        for ev in tr["traceEvents"]:
            assert isinstance(ev["name"], str)
            assert ev["ph"] in ("X", "M", "i")
            phs.add(ev["ph"])
            if ev["ph"] == "X":
                assert isinstance(ev["ts"], (int, float))
                assert isinstance(ev["dur"], (int, float))
                assert isinstance(ev["pid"], int)
                assert isinstance(ev["tid"], int)
        assert {"X", "M", "i"} <= phs
        # the JSONL stream carries the same records incrementally
        lines = [json.loads(ln) for ln in
                 open(tmp_path / "events-host0.jsonl")]
        kinds = {(ln["kind"], ln["name"]) for ln in lines}
        assert ("span", "a") in kinds and ("span", "b") in kinds
        assert ("event", "watchdog_fired") in kinds

    def test_timed_records_registry_samples(self):
        obs.configure(mode="metrics")
        with obs.timed("unit/seg"):
            time.sleep(0.002)
        samples = obs.REGISTRY.histogram_samples("lgbm_timed_seconds",
                                                 name="unit/seg")
        assert samples and samples[-1] >= 0.002


class TestSpanArithmetic:
    """ISSUE 24: ids, parent ids and the clock origin, so that a reader
    can compute self time and join the spans with its own clock."""

    def test_ids_are_unique_and_parent_id_nests_per_thread(self):
        obs.configure(mode="trace")
        obs.reset_events()

        def work(tag):
            with obs.span("outer", who=tag):
                with obs.span("inner", who=tag):
                    obs.event("tick", who=tag)

        with obs.span("main/root"):
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            work("main")
        evs = obs.events()
        ids = [e["id"] for e in evs]
        assert len(ids) == len(set(ids)) == 3 * 5 + 1
        by_id = {e["id"]: e for e in evs}
        root = next(e for e in evs if e["name"] == "main/root")
        assert root["parent_id"] is None
        for e in evs:
            who = e["tags"].get("who")
            if e["name"] == "outer":
                # a thread's root has no parent: the stack is per thread
                assert e["parent_id"] == (root["id"] if who == "main"
                                          else None)
            elif e["name"] in ("inner", "tick"):
                parent = by_id[e["parent_id"]]
                assert parent["name"] == {"inner": "outer",
                                          "tick": "inner"}[e["name"]]
                assert parent["tags"]["who"] == who
                assert parent["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]

    def test_origin_puts_a_span_on_perf_counter(self):
        obs.configure(mode="trace")
        obs.reset_events()
        before = time.perf_counter()
        with obs.span("clocked"):
            pass
        after = time.perf_counter()
        (ev,) = obs.events()
        start = obs.origin_ns() / 1e9 + ev["ts"] / 1e6
        assert before - 1e-3 <= start <= after + 1e-3
        assert start + ev["dur"] / 1e6 <= after + 1e-3

    def test_span_ended_is_a_child_that_ends_now(self):
        obs.span_ended("late", 0.5)          # off: nothing recorded
        assert obs.events() == []
        obs.configure(mode="trace")
        with obs.span("open"):
            obs.span_ended("late", 0.25, cache="miss")
            now = time.perf_counter()
        late, opened = obs.events()
        assert (late["name"], late["kind"], late["ph"]) == \
            ("late", "span", "X")
        assert late["parent_id"] == opened["id"]
        assert late["tags"] == {"cache": "miss", "parent": "open",
                                "depth": 1}
        assert late["dur"] == pytest.approx(0.25e6)
        end = obs.origin_ns() / 1e9 + (late["ts"] + late["dur"]) / 1e6
        assert abs(end - now) < 1e-3

    def test_exports_carry_id_and_parent_id(self, tmp_path):
        obs.configure(mode="trace", trace_dir=str(tmp_path))
        obs.reset_events()
        with obs.span("a"):
            with obs.span("b"):
                pass
        path = obs.write_chrome_trace()
        obs.flush()
        a, b = (next(e for e in obs.events() if e["name"] == n)
                for n in "ab")
        chrome = {e["name"]: e["args"] for e in
                  json.loads(open(path).read())["traceEvents"]
                  if e["ph"] == "X"}
        lines = {ln["name"]: ln for ln in map(
            json.loads, open(tmp_path / "events-host0.jsonl"))}
        for out in (chrome, lines):
            assert (out["a"]["id"], out["a"]["parent_id"]) == (a["id"], None)
            assert (out["b"]["id"], out["b"]["parent_id"]) == \
                (b["id"], a["id"])
        sys.path.insert(0, TOOLS)
        try:
            import trace_merge
        finally:
            sys.path.remove(TOOLS)
        merged, _, _ = trace_merge.merge(str(tmp_path))
        args = {e["name"]: e["args"] for e in merged["traceEvents"]
                if e["ph"] == "X"}
        assert args["b"]["parent_id"] == a["id"]


SETUP_TREE = {              # span -> the parent it must sit in
    "sketch": "dataset/construct", "binning": "dataset/construct",
    "ingest/stage": "binning", "ingest/dispatch": "binning",
    "learner/init": "booster/init", "layout": "learner/init",
    "objective/init": "booster/init", "train_step/build": "booster/init",
}


class TestSetupTree:
    """ISSUE 24: one span tree from Dataset.construct to the first
    iteration."""

    def _spans(self):
        return [e for e in obs.events() if e["kind"] == "span"]

    def test_dataset_and_booster_yield_the_tree(self):
        X, y = _problem(n=700)
        p = dict(_P, tpu_telemetry="trace", tpu_ingest_device="true",
                 tpu_ingest_chunk_rows=256)
        obs.reset_events()
        layout0 = obs.REGISTRY.value("lgbm_phase_seconds_total",
                                     phase="layout")
        ds = lgb.Dataset(X, label=y, params=p).construct()
        lgb.Booster(params=p, train_set=ds)
        spans = self._spans()
        by_id = {e["id"]: e for e in spans}
        names = {e["name"] for e in spans}
        assert set(SETUP_TREE) | {"dataset/construct",
                                  "booster/init"} <= names
        for e in spans:
            if e["name"] in ("dataset/construct", "booster/init"):
                assert e["parent_id"] is None and e["tags"]["depth"] == 0
            elif e["name"] in SETUP_TREE:
                parent = by_id[e["parent_id"]]
                assert parent["name"] == SETUP_TREE[e["name"]]
                assert parent["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= \
                    parent["ts"] + parent["dur"] + 1e-6
        # 700 rows in chunks of 256: three launches, staged then sent
        stages = [e for e in spans if e["name"] == "ingest/stage"]
        assert [e["tags"]["chunk"] for e in stages] == [0, 1, 2]
        assert [e["tags"]["rows"] for e in stages] == [256, 256, 188]
        assert len([e for e in spans
                    if e["name"] == "ingest/dispatch"]) == 3
        # `layout` is a PHASE: the counter AND the span, the same wall
        (layout,) = [e for e in spans if e["name"] == "layout"]
        counted = obs.REGISTRY.value("lgbm_phase_seconds_total",
                                     phase="layout") - layout0
        assert counted == pytest.approx(layout["dur"] / 1e6, abs=5e-3)

    def test_telemetry_given_only_to_the_booster(self):
        X, y = _problem()
        ds = lgb.Dataset(X, label=y, params=_P).construct()
        assert obs.events() == []
        lgb.Booster(params=dict(_P, tpu_telemetry="trace"), train_set=ds)
        names = [e["name"] for e in self._spans()]
        # armed before the learner is built, not after
        assert "learner/init" in names and "layout" in names
        assert "dataset/construct" not in names

    @pytest.mark.parametrize("source", ["sparse", "file"])
    def test_the_other_constructors_open_the_same_root(self, source,
                                                       tmp_path):
        X, y = _problem(n=300)
        p = dict(_P, tpu_telemetry="trace")
        obs.reset_events()
        if source == "sparse":
            import scipy.sparse as sp

            data = sp.csr_matrix(np.where(np.abs(X) > 1.0, X, 0.0))
        else:
            data = str(tmp_path / "train.csv")
            np.savetxt(data, np.column_stack([y, X]), delimiter=",")
        lgb.Dataset(data, label=None if source == "file" else y,
                    params=p).construct()
        roots = [e for e in self._spans()
                 if e["name"] == "dataset/construct"
                 and e["parent_id"] is None]
        assert [e["tags"]["source"] for e in roots] == [source]
        sketch = next(e for e in self._spans() if e["name"] == "sketch")
        assert sketch["tags"]["parent"] == "dataset/construct"


# ---------------------------------------------------------------------------
# end-to-end train trace
# ---------------------------------------------------------------------------
class TestTrainTrace:
    def test_trace_covers_train_wall_and_loads(self, tmp_path):
        X, y = _problem(n=800)
        p = dict(_P, tpu_telemetry="trace", tpu_trace_dir=str(tmp_path))
        obs.reset_events()
        ds = lgb.Dataset(X, label=y, params=p)
        vd = lgb.Dataset(X[:200], label=y[:200], reference=ds, params=p)
        lgb.train(p, ds, num_boost_round=10, valid_sets=[vd],
                  verbose_eval=False)
        trace = json.loads(open(tmp_path / "trace-host0.json").read())
        spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        rounds = [e for e in spans if e["name"] == "train/round"]
        assert len(rounds) == 10
        assert sorted(e["args"]["iteration"] for e in rounds) == list(
            range(10))
        # acceptance: per-iteration spans cover >= 95% of the train-loop
        # wall (first round start -> last round end)
        loop_wall = (max(e["ts"] + e["dur"] for e in rounds)
                     - min(e["ts"] for e in rounds))
        covered = sum(e["dur"] for e in rounds)
        assert covered >= 0.95 * loop_wall
        # the lifecycle vocabulary is present as child spans
        names = {e["name"] for e in spans}
        for want in ("train/iteration", "train_dispatch",
                     "tree_materialize", "metric_eval", "sketch",
                     "binning"):
            assert want in names, f"missing span {want!r} in {names}"

    def test_model_bit_identical_trace_on_vs_off(self, tmp_path):
        # telemetry must not touch PRNG streams or device math — bagged
        # int16 training is the sensitive configuration
        X, y = _problem(n=600)
        q = dict(_P, num_leaves=15, bagging_fraction=0.8, bagging_freq=1,
                 tpu_hist_precision="int16")

        def train_text():
            ds = lgb.Dataset(X, label=y, params=q)
            bst = lgb.train(q, ds, num_boost_round=4,
                            keep_training_booster=True)
            return bst.model_to_string().split("\nparameters:")[0]

        obs.configure(mode="off", trace_dir="")
        m_off = train_text()
        obs.configure(mode="trace", trace_dir=str(tmp_path))
        m_trace = train_text()
        assert m_off == m_trace


# ---------------------------------------------------------------------------
# serving /metrics <-> /stats agreement
# ---------------------------------------------------------------------------
class TestServingMetrics:
    @pytest.fixture()
    def served(self):
        from lightgbm_tpu.serving import ServingSession
        from lightgbm_tpu.serving.server import serve_http

        X, y = _problem(n=500)
        ds = lgb.Dataset(X, label=y, params=_P)
        bst = lgb.train(_P, ds, num_boost_round=3)
        sess = ServingSession(params={"serving_max_batch_rows": 256,
                                      "verbosity": -1})
        sess.load("m", booster=bst)
        server = serve_http(sess, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            yield sess, base, X
        finally:
            server.shutdown()
            sess.close()

    @staticmethod
    def _get(url):
        with urllib.request.urlopen(url) as resp:
            return resp.headers.get("Content-Type", ""), resp.read().decode()

    def test_metrics_endpoint_agrees_with_stats(self, served):
        sess, base, X = served
        for sz in (1, 9, 33, 120):
            sess.predict("m", X[:sz])
        ctype, text = self._get(base + "/metrics")
        assert ctype.startswith("text/plain")
        # every sample line parses
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+$|'
            r'^# (HELP|TYPE) .*$')
        for line in text.strip().splitlines():
            assert sample.match(line), f"unparseable line: {line!r}"
        # rebuild the latency estimate FROM THE SCRAPE and compare to
        # /stats — one estimator, two surfaces, zero disagreement
        buckets = {}
        for line in text.splitlines():
            m = re.match(
                r'lgbm_serving_latency_seconds_bucket\{le="([^"]+)"\} (\d+)',
                line)
            if m:
                buckets[m.group(1)] = int(m.group(2))
        assert buckets, "latency histogram missing from /metrics"
        bounds = sorted(float(k) for k in buckets if k != "+Inf")
        cum = [buckets[repr(b)] for b in bounds] + [buckets["+Inf"]]
        counts = [cum[0]] + [cum[i] - cum[i - 1]
                             for i in range(1, len(cum))]
        st = json.loads(self._get(base + "/stats")[1])
        assert st["latency_window"] >= 4
        for tag, q in (("latency_p50_ms", 0.50), ("latency_p95_ms", 0.95),
                       ("latency_p99_ms", 0.99)):
            scraped = round(histogram_quantile(bounds, counts, q) * 1e3, 3)
            assert scraped == st[tag], (tag, scraped, st[tag])
        # request totals agree between the two surfaces
        m = re.search(r"lgbm_serving_requests_total(\{\})? (\d+)", text)
        assert m and int(m.group(2)) == st["requests_total"]

    def test_drift_gauges_agree_with_drift_payload(self, served):
        """ISSUE 14 extension of the scrape-equality contract: the
        `lgbm_drift_*` gauges on /metrics and the GET /drift JSON read
        the SAME accumulators — values must agree (modulo the %g gauge
        formatting), and every profiled feature appears on both."""
        sess, base, X = served
        sess.predict("m", X[:200] + 1.0)   # shifted: non-trivial PSI
        payload = json.loads(self._get(base + "/drift")[1])
        assert "m@1" in payload["models"]
        snap = payload["models"]["m@1"]
        assert snap["rows_sampled"] > 0
        text = self._get(base + "/metrics")[1]
        gauges = {}
        for line in text.splitlines():
            m = re.match(r'lgbm_drift_psi\{feature="([^"]+)",'
                         r'model="m@1"\} (-?[0-9.eE+-]+)', line)
            if m:
                gauges[m.group(1)] = float(m.group(2))
        assert set(gauges) == set(snap["features"])
        for name, f in snap["features"].items():
            assert gauges[name] == pytest.approx(f["psi"], rel=1e-5,
                                                 abs=1e-9)
        m = re.search(r'lgbm_drift_score_js\{model="m@1"\} '
                      r'(-?[0-9.eE+-]+)', text)
        assert m and float(m.group(1)) == pytest.approx(
            snap["score_js_max"], rel=1e-5, abs=1e-9)
        m = re.search(r'lgbm_drift_sampled_rows\{model="m@1"\} (\d+)',
                      text)
        assert m and int(m.group(1)) >= snap["rows_sampled"]

    def test_queue_wait_and_dispatch_distributions_populate(self, served):
        sess, base, X = served
        for _ in range(3):
            sess.predict("m", X[:16])
        st = sess.stats()
        assert st["dispatch_mean_ms"] > 0.0
        assert st["queue_wait_mean_ms"] >= 0.0
        text = self._get(base + "/metrics")[1]
        assert "lgbm_serving_dispatch_seconds_bucket" in text
        assert "lgbm_serving_queue_wait_seconds_bucket" in text


# ---------------------------------------------------------------------------
# overhead: telemetry off vs the registry absent
# ---------------------------------------------------------------------------
class TestOffOverhead:
    N_ITERS = 100

    def _train_wall(self):
        X, y = _problem(n=1500, f=6, seed=3)
        ds = lgb.Dataset(X, label=y, params=_P)
        bst = lgb.Booster(params=dict(_P), train_set=ds)
        import jax

        bst.update()  # compile + warm
        jax.block_until_ready(bst._driver.train_scores.scores)
        t0 = time.perf_counter()
        for _ in range(self.N_ITERS):
            bst.update()
        jax.block_until_ready(bst._driver.train_scores.scores)
        return time.perf_counter() - t0

    def test_off_mode_regression_under_1pct(self, monkeypatch):
        import contextlib

        import lightgbm_tpu.models.gbdt as gbdt_mod
        import lightgbm_tpu.utils.timer as timer_mod

        assert obs.mode() == "off"

        # (a) deterministic microbench: the exact per-iteration gated
        # work (the spans/PHASE checks the hot loop added) must cost
        # < 1% of a measured training iteration.  Min-of-5 windows so a
        # transient container stall (GC, noisy neighbor) cannot inflate
        # the measured per-call cost
        reps = 5000
        from lightgbm_tpu.obs import flightrecorder, resources
        from lightgbm_tpu.utils import lockcheck

        assert not lockcheck.enabled()
        _lk = lockcheck.make_lock("test.offgate")
        per_call = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for i in range(reps):
                with obs.span("train/iteration", iteration=i):
                    with timer_mod.PHASE("train_dispatch"):
                        # ISSUE 12 sites: the gated phase watermark and
                        # the ALWAYS-ON flight-recorder round note must
                        # fit inside the same 1% gate
                        with resources.phase_peak("hist_build"):
                            pass
                flightrecorder.note("round", "train/round", iteration=i)
                # ISSUE 13 site: serving/obs locks are now created via
                # lockcheck.make_lock — a DISABLED instrumented lock
                # cycle rides the same 1% budget
                with _lk:
                    pass
            per_call = min(per_call,
                           (time.perf_counter() - t0) / reps)
        wall = self._train_wall()
        per_iter = wall / self.N_ITERS
        assert per_call < 0.01 * per_iter, (
            f"gated telemetry sites cost {per_call * 1e6:.2f}us/iter vs "
            f"{per_iter * 1e3:.2f}ms training iterations")

        # (b) end-to-end A/B vs "the registry absent" (instrumentation
        # stubbed to bare no-ops), interleaved min-of-N with a retry:
        # both arms run identical device work, so a consistent >1% gap
        # is a real regression, not container noise
        class _Null:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        _null = _Null()

        @contextlib.contextmanager
        def _null_phase(name):
            yield

        import statistics

        def inside_gate(off, absent):
            # min-vs-min washes UPWARD noise spikes (container stalls)
            # but one lucky downward outlier in the stubbed arm poisons
            # it irrecoverably, so the median is an alternate judge: a
            # REAL >1% gap shifts min AND median, pure noise rarely
            # shifts both
            return (min(off) <= min(absent) * 1.01
                    or statistics.median(off)
                    <= statistics.median(absent) * 1.01)

        off_walls, absent_walls = [], []
        # 6 attempts (was 4): the CPU container's wall noise spans tens
        # of percent between repeats, and an extra retry round only
        # runs on the bad-luck path
        for attempt in range(6):
            for _ in range(2):
                off_walls.append(self._train_wall())
                with pytest.MonkeyPatch.context() as mp:
                    mp.setattr(obs, "span", lambda *a, **k: _null)
                    mp.setattr(gbdt_mod.obs, "span", lambda *a, **k: _null)
                    mp.setattr(timer_mod, "PHASE", _null_phase)
                    absent_walls.append(self._train_wall())
            if inside_gate(off_walls, absent_walls):
                break
        assert inside_gate(off_walls, absent_walls), (
            f"telemetry-off train min {min(off_walls):.3f}s / median "
            f"{statistics.median(off_walls):.3f}s vs registry-absent "
            f"min {min(absent_walls):.3f}s / median "
            f"{statistics.median(absent_walls):.3f}s (> 1% regression)")


# ---------------------------------------------------------------------------
# log attribution
# ---------------------------------------------------------------------------
class TestLogTelemetry:
    def test_warning_counts_into_registry(self):
        from lightgbm_tpu.utils.log import Log

        before = obs.REGISTRY.value("lgbm_log_warnings_total")
        lines = []
        Log.reset_callback(lines.append)
        try:
            Log.warning("observable warning")
        finally:
            Log.reset_callback(None)
        assert obs.REGISTRY.value("lgbm_log_warnings_total") == before + 1
        assert any("observable warning" in ln for ln in lines)

    def test_host_prefix_on_multiprocess(self):
        from lightgbm_tpu.utils import log as log_mod

        lines = []
        log_mod.Log.reset_callback(lines.append)
        prev = log_mod._host_tag_cache
        try:
            log_mod._host_tag_cache = "[host 3] "
            log_mod.Log.warning("who said this")
        finally:
            log_mod._host_tag_cache = prev
            log_mod.Log.reset_callback(None)
        assert lines and lines[-1].startswith("[host 3] [LightGBM]")

    def test_single_process_has_no_prefix(self):
        from lightgbm_tpu.utils import log as log_mod

        # on the single-process test harness the resolver must yield ""
        assert log_mod._host_tag() == ""


# ---------------------------------------------------------------------------
# multihost merge tool
# ---------------------------------------------------------------------------
class TestTraceMerge:
    def test_merges_hosts_and_skips_torn_tails(self, tmp_path):
        sys.path.insert(0, TOOLS)
        try:
            import trace_merge
        finally:
            sys.path.remove(TOOLS)
        for host in (0, 1):
            with open(tmp_path / f"events-host{host}.jsonl", "w") as f:
                for i in range(3):
                    f.write(json.dumps({
                        "kind": "span", "name": f"iter{i}",
                        "ts_us": 100.0 * i, "dur_us": 50.0,
                        "host": host, "tid": 1,
                        "tags": {"iteration": i}}) + "\n")
                if host == 1:  # a dying host's torn final line
                    f.write('{"kind": "span", "name": "tor')
        trace, counts, skipped = trace_merge.merge(str(tmp_path))
        assert counts == {0: 3, 1: 3}
        assert skipped == 1
        pids = {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"}
        assert pids == {0, 1}
        names = {e["args"]["name"] for e in trace["traceEvents"]
                 if e["ph"] == "M"}
        assert names == {"lightgbm_tpu host 0", "lightgbm_tpu host 1"}
        out = trace_merge.main([str(tmp_path)])
        assert json.loads(open(out).read())["traceEvents"]

    def test_missing_dir_raises(self, tmp_path):
        sys.path.insert(0, TOOLS)
        try:
            import trace_merge
        finally:
            sys.path.remove(TOOLS)
        with pytest.raises(FileNotFoundError):
            trace_merge.merge(str(tmp_path))


# ---------------------------------------------------------------------------
# collective / checkpoint / guard counters
# ---------------------------------------------------------------------------
class TestLifecycleCounters:
    def test_collective_timeout_counts_and_events(self, tmp_path):
        from lightgbm_tpu.parallel.collective import (CollectiveTimeout,
                                                      guarded_collective)
        from lightgbm_tpu.utils import faultline

        obs.configure(mode="trace", trace_dir=str(tmp_path))
        obs.reset_events()
        before = obs.REGISTRY.value("lgbm_collective_timeouts_total",
                                    name="unit_sync")
        faultline.reset()
        faultline.arm("collective_sync", action="hang")
        try:
            with pytest.raises(CollectiveTimeout):
                guarded_collective(lambda: 1, name="unit_sync", local=True)
        finally:
            faultline.reset()
        assert obs.REGISTRY.value("lgbm_collective_timeouts_total",
                                  name="unit_sync") == before + 1
        assert any(e["name"] == "collective_timeout"
                   for e in obs.events() if e["kind"] == "event")
        # the successful path records wait time under metrics mode
        assert guarded_collective(lambda: 41, name="unit_sync",
                                  local=True) == 41
        n, _ = obs.REGISTRY.histogram_stats("lgbm_collective_wait_seconds",
                                            name="unit_sync")
        assert n >= 1

    def test_checkpoint_write_and_restore_count(self, tmp_path):
        from lightgbm_tpu.utils.checkpoint import (CheckpointManager,
                                                   restore_checkpoint,
                                                   save_checkpoint)

        X, y = _problem()
        ds = lgb.Dataset(X, label=y, params=_P)
        bst = lgb.Booster(params=dict(_P), train_set=ds)
        bst.update()
        w0 = obs.REGISTRY.value("lgbm_checkpoint_writes_total")
        r0 = obs.REGISTRY.value("lgbm_checkpoint_restores_total")
        manager = CheckpointManager(str(tmp_path), keep=2)
        save_checkpoint(bst, manager)
        assert obs.REGISTRY.value("lgbm_checkpoint_writes_total") == w0 + 1
        bst2 = lgb.Booster(params=dict(_P), train_set=ds)
        restore_checkpoint(bst2, manager)
        assert obs.REGISTRY.value("lgbm_checkpoint_restores_total") == r0 + 1

    def test_guard_poison_counts(self):
        from lightgbm_tpu.utils import faultline

        X, y = _problem()
        p = dict(_P, tpu_guard_numerics="warn")
        ds = lgb.Dataset(X, label=y, params=p)
        bst = lgb.Booster(params=p, train_set=ds)
        before = obs.REGISTRY.value("lgbm_guard_poisoned_total",
                                    mode="warn")
        faultline.reset()
        faultline.arm("grow_step", action="poison", at=2)
        try:
            for _ in range(3):
                bst.update()
        finally:
            faultline.reset()
        # warn mode CONTINUES with the poisoned scores, so every later
        # iteration re-detects them: at least one firing, maybe more
        assert obs.REGISTRY.value("lgbm_guard_poisoned_total",
                                  mode="warn") >= before + 1

    def test_fault_firing_counts(self):
        from lightgbm_tpu.utils import faultline

        before = obs.REGISTRY.value("lgbm_fault_injections_total",
                                    point="h2d_copy", action="raise")
        faultline.reset()
        faultline.arm("h2d_copy", action="raise")
        with pytest.raises(faultline.FaultInjected):
            faultline.fire("h2d_copy")
        faultline.reset()
        assert obs.REGISTRY.value("lgbm_fault_injections_total",
                                  point="h2d_copy",
                                  action="raise") == before + 1

    def test_phase_seconds_absorbed_into_registry(self):
        obs.configure(mode="metrics")
        from lightgbm_tpu.utils import timer

        s0 = obs.REGISTRY.value("lgbm_phase_seconds_total", phase="sketch")
        X, y = _problem()
        ds = lgb.Dataset(X, label=y, params=_P)
        ds.construct()
        s1 = obs.REGISTRY.value("lgbm_phase_seconds_total", phase="sketch")
        assert s1 > s0
        assert timer.summary().get("sketch", 0.0) == s1
