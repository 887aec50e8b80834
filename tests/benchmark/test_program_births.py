"""The readers of a program's birth (`lib/program_births.py` and the three
per-layer metrics on it): each on a hand-built event list, None where the
program records no trace span, the births table, and two runs of the real
program: a nested jit whose spans must keep every self time at or above
zero, and a rehearsal whose set-up table must say where the grow program's
stages fell."""

import json
import types

import pytest

from benchmarks.lib import harness, program_births, program_spans
from tests.benchmark.test_harness import CELLS, ROOT, run_cell
from tests.benchmark.test_program_spans import (BENCH_ROWS, ORIGIN_S, T0,
                                                WINDOW_START, span)

GROW = dict(site="learner.grow", fun_name="grow")
PRE = dict(site="learner.pre", fun_name="_pre")
EVENTS = [
    span(1, None, "booster/init", 40, 15),
    span(2, 1, "layout", 40.5, 0.5),
    # an eager op, born whole and outside every site, twice
    span(20, 2, "program/trace", 40.5, 0.125, site="(none)",
         fun_name="transpose"),
    span(21, 2, "program/lower", 40.625, 0.125, site="(none)",
         fun_name="jit(transpose)"),
    span(22, 2, "compile", 40.75, 0.0625, site="(none)",
         fun_name="jit(transpose)", cache="hit"),
    span(23, 2, "program/trace", 40.8125, 0.0625, site="(none)",
         fun_name="transpose"),
    span(24, 2, "compile", 40.875, 0.0625, site="(none)",
         fun_name="jit(transpose)", cache="hit"),
    span(3, 1, "train_step/build", 41, 9),
    # the gauge's trace: 900 inner traces folded into it, an eager op run
    # to its compile inside it, and one trace nested in it as a span
    span(4, 3, "program/trace", 41, 6, inner=900, **GROW),
    span(5, 4, "program/lower", 42, 0.5, site="learner.grow",
         fun_name="jit(arange)"),
    span(6, 4, "compile", 42.5, 0.5, site="learner.grow",
         fun_name="jit(arange)", cache="hit"),
    span(7, 4, "program/trace", 43, 1, site="learner.grow",
         fun_name="kernel"),
    span(8, 3, "program/trace", 47.5, 1, **PRE),
    span(9, None, "train/iteration", 60, 10, iteration=0),
    span(10, 9, "train_dispatch", 60, 9),
    # the first call traces `grow` again, as long; `_pre` from the cache
    span(11, 10, "program/trace", 60, 5, inner=900, **GROW),
    span(12, 10, "program/lower", 65, 2, site="learner.grow",
         fun_name="jit(grow)"),
    span(13, 12, "program/trace", 65.5, 0.5, site="learner.grow",
         fun_name="_where"),           # a lowering rule's
    span(14, 10, "compile", 67, 1, site="learner.grow",
         fun_name="jit(grow)", cache="hit"),
    span(15, 10, "program/trace", 68, 0.125, **PRE),
    span(16, 10, "program/lower", 68.125, 0.25, site="learner.pre",
         fun_name="jit(_pre)"),
    span(17, 10, "compile", 68.375, 0.125, site="learner.pre",
         fun_name="jit(_pre)", cache="miss"),
    # the window: nothing of it is set-up
    span(18, None, "train/iteration", 73, 2, iteration=1),
    span(19, 18, "program/trace", 73.5, 1, site="(none)", fun_name="late"),
]
WANT = {
    # 6 less the three stages inside it, and the one nested in it; the
    # lowering rule's trace is trace time, not lowering
    "program_trace_s": (6 - 2) + 1 + 1 + 5 + 0.5 + 0.125 + 0.125 + 0.0625,
    "program_lower_s": 0.125 + 0.5 + (2 - 0.5) + 0.25,
    # `grow` and `_pre` twice for one program each; `transpose` twice for
    # two; the spans inside a stage are no program's first trace
    "traces_without_program": 2,
}


def a_run(monkeypatch, events):
    """A Run whose program recorded `events`; `run.said` collects the
    notes."""
    from lightgbm_tpu import obs

    monkeypatch.setattr(obs, "events", lambda: list(events))
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


@pytest.fixture
def run(monkeypatch):
    return a_run(monkeypatch, EVENTS)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_on_a_hand_built_event_list(run, metric):
    assert run.metric(metric) == pytest.approx(WANT[metric])


PAIRS = {
    "a pair of traces and one program": ([(50, 4), (56, 4)], 1, 1),
    "the second from JAX's trace cache": ([(50, 4), (56, 0.000125)], 1, 1),
    "one trace, one program": ([(50, 4)], 1, None),
    "a trace and no program (a gauge, an AOT lower)": ([(50, 4)], 0, 1),
    "three traces, two programs": ([(50, 1), (52, 1), (54, 1)], 2, 1),
    "more programs than traces is no credit": ([(50, 1)], 3, None),
}


@pytest.mark.parametrize("case", sorted(PAIRS))
def test_traces_without_program_counts_by_site_and_function(monkeypatch,
                                                            case):
    traces, programs, want = PAIRS[case]
    events = [span(1, None, "booster/init", 40, 30)]
    events += [span(10 + i, 1, "program/trace", at, secs, **GROW)
               for i, (at, secs) in enumerate(traces)]
    events += [span(20 + i, 1, "compile", 60 + i, 0.5, site="learner.grow",
                    fun_name="jit(grow)", cache="hit")
               for i in range(programs)]
    # the same function at another site is another program
    events += [span(30, 1, "program/trace", 65, 0.5, site="other.site",
                    fun_name="grow"),
               span(31, 1, "compile", 66, 0.5, site="other.site",
                    fun_name="jit(grow)", cache="hit")]
    run = a_run(monkeypatch, events)
    assert run.metric("traces_without_program") == want
    (_, note), = run.said
    assert note["columns"] == ["site", "function", "traces", "programs",
                               "seconds_by_trace"]
    assert note["rows"] == ([] if want is None else [
        ["learner.grow", "grow", len(traces), programs,
         pytest.approx([secs for _, secs in traces])]])


def test_an_inner_trace_is_self_seconds_not_the_sum(monkeypatch):
    events = [span(1, None, "booster/init", 40, 10),
              span(2, 1, "program/trace", 41, 4, **GROW),
              span(3, 2, "program/trace", 42, 1.5, site="learner.grow",
                   fun_name="body"),
              span(4, 3, "program/trace", 42.5, 0.5, site="learner.grow",
                   fun_name="_where")]
    run = a_run(monkeypatch, events)
    assert run.metric("program_trace_s") == pytest.approx(4.0)   # not 6
    assert run.metric("program_lower_s") is None
    # one outermost trace, no program
    assert run.metric("traces_without_program") == 1
    table = run.said[0][1]
    assert table["rows"] == [["learner.grow", "trace", 1, 4.0,
                              pytest.approx(4.0), "booster/init"]]


def test_the_births_table_names_site_stage_and_enclosing_span(run):
    run.metric("program_trace_s")
    (what, table), = run.said
    assert what == "program births in set-up by site"
    assert table["columns"] == ["site", "stage", "programs", "seconds",
                                "self_seconds", "under"]
    assert (table["spans"], table["inner_traces"]) == (17, 1800)
    rows = table["rows"]
    assert rows == sorted(rows, key=lambda r: -r[3])     # longest first
    assert [r[:2] + r[5:] for r in rows[:3]] == [
        ["learner.grow", "trace", "train_step/build"],
        ["learner.grow", "trace", "train_dispatch"],
        ["learner.grow", "lower", "train_dispatch"]]
    by_key = {(r[0], r[1], r[5]): r[2:5] for r in rows}
    # outermost events and their seconds; every event's self seconds
    assert by_key["learner.grow", "trace", "train_step/build"] \
        == pytest.approx([1, 6.0, 4.0 + 1.0])
    assert by_key["learner.grow", "trace", "train_dispatch"] \
        == pytest.approx([1, 5.0, 5.0 + 0.5])
    assert by_key["learner.grow", "lower", "train_dispatch"] \
        == pytest.approx([1, 2.0, 1.5])
    # what ran inside a stage is under the span around that stage, and
    # no program of its own
    assert by_key["learner.grow", "lower", "train_step/build"] \
        == pytest.approx([0, 0.0, 0.5])
    assert by_key["(none)", "trace", "layout"] == pytest.approx(
        [2, 0.1875, 0.1875])
    assert by_key["learner.pre", "compile", "train_dispatch"] \
        == pytest.approx([1, 0.125, 0.125])
    # self seconds are a wall: the stages' union, each second once
    assert sum(r[4] for r in rows) == pytest.approx(
        sum(r[3] for r in rows))


def test_the_stage_spans_leave_every_self_time_at_or_above_zero(run):
    spans = program_spans.setup_spans(run)
    own = program_spans.self_seconds(spans)
    assert min(own.values()) >= 0
    by_name = {r[0]: r[1:] for r in program_spans.by_name(spans)}
    assert by_name["train_step/build"] == pytest.approx([1, 9.0, 2.0])
    assert by_name["train_dispatch"] == pytest.approx([1, 9.0, 0.5])


def test_a_function_is_named_without_its_wrapper():
    assert [program_births.program(n) for n in (
        "grow", "jit(grow)", "pmap(step)", "jit(<lambda>)", "", None)] == [
        "grow", "grow", "step", "<lambda>", "", ""]


GONE = {
    "no trace span (the parent of the PR that added them)":
        lambda mp, obs, run: mp.setattr(obs, "events", lambda: [
            e for e in EVENTS if e["name"] != "program/trace"]),
    "no origin": lambda mp, obs, run: mp.delattr(obs, "origin_ns"),
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


def test_a_real_nested_jit_keeps_every_self_time_at_or_above_zero(
        monkeypatch):
    """Invariant (b): the program's own spans of a site whose function
    calls inner jits, whose lowering traces, and which runs an eager op
    while it is traced, read through `program_spans`."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu import obs
    from lightgbm_tpu.utils.compile_ledger import LEDGER, ledger_jit

    @jax.jit
    def inner(x):
        return jnp.where(x > 0, x, 0.0) * 2

    def nested(x):
        with jax.ensure_compile_time_eval():
            k = jnp.arange(53.0).sum()
        return inner(x) + jnp.sum(x) * k + jnp.linalg.norm(x)

    was_on = LEDGER.enabled
    LEDGER.enable()
    obs.configure(mode="trace")
    obs.reset_events()
    try:
        f = ledger_jit(nested, site="unit.births")
        with obs.span("unit/build"):
            f.trace(jax.ShapeDtypeStruct((53,), jnp.float32))
        with obs.span("unit/dispatch"):
            f(jnp.ones(53)).block_until_ready()
        events = obs.events()
    finally:
        obs.configure(mode="off")
        obs.reset_events()
        LEDGER.enable(was_on)
    run = a_run(monkeypatch, events)
    monkeypatch.setattr(obs, "origin_ns", lambda: 0)
    run.facts["window_start"] = float("inf")
    spans = program_spans.setup_spans(run)
    own = program_spans.self_seconds(spans)
    assert min(own.values()) >= 0
    births = program_births.of_setup(run)
    assert {program_births.STAGE_OF[s.name] for s in births.stages} == {
        "trace", "lower", "compile"}
    mine = [s for s in births.stages if s.tags["site"] == "unit.births"]
    assert {births.under(s) for s in mine} == {"unit/build",
                                               "unit/dispatch"}
    # a stage inside a stage is there, and none of them is outermost
    nested_in = [s for s in mine if not births.outermost(s)]
    assert nested_in and all(
        births.by_id[s.parent_id].name in program_births.STAGE_OF
        for s in nested_in)
    outer = [(s.name, s.tags["fun_name"]) for s in mine
             if births.outermost(s)]
    assert outer == [("program/trace", "nested"),
                     ("program/trace", "nested"),
                     ("program/lower", "jit(nested)"),
                     ("compile", "jit(nested)")]
    # the stages' self seconds are no more than the spans they ran under
    walls = sum(s.seconds for s in spans if s.name.startswith("unit/"))
    assert sum(own[s.id] for s in births.stages) <= walls
    assert run.metric("traces_without_program") == 1


def test_rehearsal_says_where_the_grow_programs_stages_fell():
    rc, lines, err = run_cell(ROOT, "--workload", CELLS[0], "--seed", "3939",
                              "--seconds", "1", "--trace", "1",
                              "--rehearse-cpu")
    assert rc == 0, err
    notes = {n["note"]: n for n in map(json.loads, lines[:-1])}
    metrics = json.loads(lines[-1])["metrics"]
    assert {"program_trace_s", "program_lower_s",
            "traces_without_program"} <= set(metrics)
    table = notes["program births in set-up by site"]
    rows = {(r[0], r[1], r[5]): r[2:5] for r in table["rows"]}
    # the gauge traces the grow program under train_step/build; its first
    # call lowers it and loads or compiles it under train_dispatch
    assert rows["grower.grow", "trace", "train_step/build"][0] >= 1
    assert rows["grower.grow", "lower", "train_dispatch"][0] == 1
    assert rows["grower.grow", "compile", "train_dispatch"][0] == 1
    assert table["inner_traces"] > table["spans"]
    # the stage spans' self seconds are the two metrics and the compiles
    tree = notes["set-up by the program's spans"]
    by_name = {r[0]: r[1:] for r in tree["rows"]}
    assert all(r[3] >= 0 for r in tree["rows"])
    assert metrics["program_trace_s"]["value"] == pytest.approx(
        by_name["program/trace"][2])
    assert metrics["program_lower_s"]["value"] == pytest.approx(
        by_name["program/lower"][2])
    assert sum(r[4] for r in table["rows"]) == pytest.approx(
        by_name["program/trace"][2] + by_name["program/lower"][2]
        + by_name["compile"][2])
    # the compile spans are what they were: the benchmark's own count
    assert by_name["compile"][0] == notes["facts"]["programs_in_setup"]
    spare = notes["functions traced more often than programs of them "
                  "were produced"]
    grow = next(r for r in spare["rows"] if r[0] == "grower.grow")
    assert grow[2] > grow[3] == 1 and len(grow[4]) == grow[2]
    assert metrics["traces_without_program"]["value"] == sum(
        r[2] - r[3] for r in spare["rows"])
