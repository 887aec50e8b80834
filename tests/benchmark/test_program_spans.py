"""The readers of the program's own set-up spans (`lib/program_spans.py`
and the five per-layer metrics on it): each on a hand-built event list,
None where the program's names are gone, and one rehearsal in which the
program's compile spans must add up to what the benchmark's own
`jax.monitoring` listener counted."""

import json
import types

import pytest

from benchmarks.lib import harness, program_spans
from tests.benchmark.test_harness import CELLS, ROOT, run_cell

ORIGIN_S = 100.0     # the tracer's origin on time.perf_counter
T0, WINDOW_START = 100.0, 172.0


def span(id, parent_id, name, start_s, seconds, **tags):
    """A span as `lightgbm_tpu.obs.events()` gives it: microseconds
    since the tracer's origin."""
    return {"kind": "span", "name": name, "ph": "X", "id": id,
            "parent_id": parent_id, "ts": start_s * 1e6,
            "dur": seconds * 1e6, "host": 0, "tid": 1, "tags": tags}


EVENTS = [
    span(1, None, "dataset/construct", 10, 20, source="matrix"),
    span(2, 1, "sketch", 10.5, 4),
    span(3, 1, "binning", 15, 14),
    span(4, 3, "ingest/stage", 15, 1, chunk=0, rows=256),
    span(6, 3, "ingest/dispatch", 16, 0.5, chunk=0),
    span(7, 6, "compile", 16.2, 0.25, site="binning.chunk",
         fun_name="jit(_bin_chunk_kernel)", cache="hit"),
    span(5, 3, "ingest/stage", 17, 2, chunk=1, rows=100),
    span(8, None, "sketch", 31, 1),          # not under dataset/construct
    span(9, None, "booster/init", 40, 15),
    span(10, 9, "learner/init", 41, 12),
    span(11, 10, "layout", 42, 10),
    span(12, 11, "compile", 43, 1.5, site="(none)",
         fun_name="jit(transpose)", cache="miss"),
    span(13, 11, "compile", 45, 0.5, site="(none)",
         fun_name="jit(transpose)", cache="hit"),
    span(14, None, "train/iteration", 60, 10, iteration=0),
    span(15, 14, "compile", 61, 6, site="learner.pre",
         fun_name="jit(_pre)", cache="miss"),
    # the window: nothing of it is set-up
    span(16, None, "train/iteration", 73, 2, iteration=1),
    span(17, 16, "compile", 73.5, 1, site="(none)",
         fun_name="jit(late)", cache="miss"),
    {"kind": "event", "name": "tick", "ph": "i", "id": 18,
     "parent_id": None, "ts": 1e6, "dur": 0.0, "host": 0, "tid": 1,
     "tags": {}},
]
BENCH_ROWS = [("bench/setup/make_data", 104.0, 109.0),
              ("bench/setup/ingest", 110.0, 131.0),
              ("bench/setup/learner", 139.0, 156.0),
              ("bench/setup/warmup", 159.0, 171.5),
              ("bench/update", 173.0, 175.0)]
WANT = {"sketch_s": 4.0, "ingest_stage_host_s": 3.0,
        "learner_layout_s": 10.0,
        "unledgered_programs": 2, "setup_unattributed_s": 22.0}


@pytest.fixture
def run(monkeypatch):
    """A Run whose program recorded EVENTS; `run.said` collects the
    notes."""
    from lightgbm_tpu import obs

    monkeypatch.setattr(obs, "events", lambda: list(EVENTS))
    monkeypatch.setattr(obs, "origin_ns", lambda: int(ORIGIN_S * 1e9))
    said = []
    cell = types.SimpleNamespace(
        t0=T0, bench_dir=harness.BENCH_DIR,
        spans=types.SimpleNamespace(rows=list(BENCH_ROWS)),
        say=lambda what, **fields: said.append((what, fields)),
        load=lambda kind, name: harness.load_module(harness.BENCH_DIR,
                                                    kind, name))
    r = harness.Run(cell, {"window_start": WINDOW_START}, None, None)
    r.said = said
    return r


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_on_a_hand_built_event_list(run, metric):
    assert run.metric(metric) == pytest.approx(WANT[metric])


def test_the_compile_notes_name_sites_programs_and_spans(run):
    """Both lines are `unledgered_programs`' since PR 35 (`compile_miss_s`
    said the first; a cached run has no miss to time, so it went): the
    misses' 7.5 s are the table's, site by site."""
    run.metric("unledgered_programs")
    (_, table), (_, loose) = run.said
    misses = sum(r[3] for r in table["rows"])
    assert misses == 2 and table["rows"][0][3:] == [1, 6.0]
    assert table["columns"] == ["site", "programs", "hits", "misses",
                                "seconds"]
    assert table["rows"] == [["learner.pre", 1, 0, 1, 6.0],
                             ["(none)", 2, 1, 1, 2.0],
                             ["binning.chunk", 1, 1, 0, 0.25]]
    assert (table["programs"], table["seconds"]) == (4, 8.25)
    assert loose["by_fun_name"] == [("jit(transpose)", 2)]
    assert loose["by_span"] == [("layout", 2)]
    assert loose["seconds"] == 2.0


def test_unattributed_plus_the_union_is_the_setup_wall(run):
    left = run.metric("setup_unattributed_s")
    (_, note), = run.said
    assert note["setup_wall_s"] == WINDOW_START - T0
    assert note["attributed_s"] + left == pytest.approx(note["setup_wall_s"])
    assert note["attributed_s"] == pytest.approx(20 + 15 + 10 + 5)
    assert note["unattributed_inside_bench_spans"] == pytest.approx(
        {"bench/setup/ingest": 1.0, "bench/setup/learner": 2.0,
         "bench/setup/warmup": 2.5})
    assert note["unattributed_outside_them"] == pytest.approx(16.5)
    rows = {r[0]: r[1:] for r in note["rows"]}
    # self time by parent_id: binning 14 less its stages and dispatch,
    # the dispatch less the compile inside it
    assert rows["binning"] == pytest.approx([1, 14.0, 10.5])
    assert rows["ingest/dispatch"] == pytest.approx([1, 0.5, 0.25])
    assert rows["compile"] == pytest.approx([4, 8.25, 8.25])
    assert "tick" not in rows and rows["train/iteration"][0] == 1


def test_spans_are_on_perf_counter_and_cut_at_the_window(run):
    spans = program_spans.setup_spans(run)
    assert [s.id for s in spans] == [1, 2, 3, 4, 6, 7, 5, 8, 9, 10, 11,
                                     12, 13, 14, 15]
    first = spans[0]
    assert (first.start, first.end, first.seconds) == (110.0, 130.0, 20.0)
    assert [s.id for s in program_spans.named(spans, "sketch")] == [2, 8]
    assert [s.id for s in program_spans.named(
        spans, "compile", under="booster/init")] == [12, 13]
    cover = program_spans.union([(3, 4), (1, 2), (1.5, 3.5), (9, 9), (6, 7)])
    assert cover == [(1, 4), (6, 7)]
    assert program_spans.overlap(cover, 2, 6.5) == 2.5


GONE = {
    "no origin (the parent of the PR that added it)":
        lambda mp, obs, run: mp.delattr(obs, "origin_ns"),
    "spans without ids":
        lambda mp, obs, run: mp.setattr(obs, "events", lambda: [
            {k: v for k, v in e.items() if k not in ("id", "parent_id")}
            for e in EVENTS]),
    "no span at all (telemetry off)":
        lambda mp, obs, run: mp.setattr(obs, "events", lambda: []),
    "a job that has no window":
        lambda mp, obs, run: run.facts.clear(),
}


@pytest.mark.parametrize("metric", sorted(WANT))
@pytest.mark.parametrize("how", sorted(GONE))
def test_a_reader_says_none_where_the_names_are_gone(run, monkeypatch,
                                                     metric, how):
    from lightgbm_tpu import obs

    GONE[how](monkeypatch, obs, run)
    assert run.metric(metric) is None
    assert run.said == []


def test_every_compile_a_hit_or_on_a_site_is_left_out(run, monkeypatch):
    """A line carries positive values only (test_harness)."""
    from lightgbm_tpu import obs

    tame = [dict(e, tags=dict(e["tags"], cache="hit", site="unit.site"))
            if e["name"] == "compile" else e for e in EVENTS]
    monkeypatch.setattr(obs, "events", lambda: tame)
    assert run.metric("unledgered_programs") is None
    (_, table), _ = run.said        # the tables are still said
    assert [r[:4] for r in table["rows"]] == [["unit.site", 4, 4, 0]]


def test_rehearsal_compile_spans_equal_the_benchmarks_own_count():
    rc, lines, err = run_cell(ROOT, "--workload", CELLS[0], "--seed", "2424",
                              "--seconds", "1", "--trace", "1",
                              "--rehearse-cpu")
    assert rc == 0, err
    notes = {n["note"]: n for n in map(json.loads, lines[:-1])}
    metrics = json.loads(lines[-1])["metrics"]
    assert {"sketch_s", "learner_layout_s", "unledgered_programs",
            "setup_unattributed_s"} <= set(metrics)
    facts, table = notes["facts"], notes["compiles in set-up by site"]
    assert table["programs"] == facts["programs_in_setup"] \
        == metrics["programs_compiled"]["value"]
    assert table["seconds"] == pytest.approx(
        facts["compile_or_load_s_in_setup"], abs=1e-9)
    assert sum(r[2] for r in table["rows"]) == facts["cache_hits_in_setup"]
    assert {"learner.pre", "grower.grow"} <= {r[0] for r in table["rows"]}
    tree = notes["set-up by the program's spans"]
    assert tree["attributed_s"] + tree["unattributed_s"] == pytest.approx(
        tree["setup_wall_s"])
    assert tree["setup_wall_s"] == pytest.approx(facts["setup_s"], abs=0.05)
    assert metrics["setup_unattributed_s"]["value"] == tree["unattributed_s"]
