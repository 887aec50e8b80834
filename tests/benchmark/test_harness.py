"""The harness: BENCHMARK.json and the files it names agree; new cells,
configurations, traffic mixes, job kinds and per-layer metrics are found as
new files with no edit; every cell rehearses end to end on the CPU and
prints the contract's result line; without an accelerator nothing runs."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import harness

ROOT = os.path.dirname(harness.BENCH_DIR)
SPEC = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]
KEPT = harness.load_json(os.path.join(harness.BENCH_DIR, "kept_for_later.json"))
KEPT_CELLS = [w["name"] for w in KEPT["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "facts", "compared"}


def run_cell(root, *args, env=None):
    """One run of `<root>/benchmarks/run.py`; (exit code, stdout lines)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, **(env or {})})
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


# ---- BENCHMARK.json against the files ------------------------------------------------
def test_spec_has_exactly_the_contracts_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert all(len(x["why"]) <= 200
               for x in SPEC["configs"] + SPEC["workloads"])
    assert 1 <= SPEC["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def test_every_entry_names_files_that_are_there():
    under = tuple(p + "/" for p in SPEC["paths"])
    for c in SPEC["configs"]:
        assert c["file"].startswith(under)
        conf = harness.load_json(os.path.join(ROOT, c["file"]))
        assert conf["name"] == c["name"]
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
        gen = conf["data"]["generator"]
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "datagen",
                                           gen + ".py"))
    for w in SPEC["workloads"]:
        traffic = harness.load_json(os.path.join(
            harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "jobs",
                                           traffic["job"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_are_declared_as_the_contract_wants():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    readers = {f[:-3] for f in os.listdir(
        os.path.join(harness.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert readers == {m["name"] for m in SPEC["per_layer"] + KEPT["per_layer"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and LAYER.match(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        # reported only where the metric it moves is
        moved = e2e[m["moves"]].get("workloads", CELLS)
        assert set(m.get("workloads", CELLS)) <= set(moved)
    for cell in CELLS:
        assert sum(harness.applies(m, cell) for m in SPEC["end_to_end"]) >= 2
        assert any(harness.applies(m, cell) for m in SPEC["per_layer"])


def test_overlay_lays_groups_over_groups():
    base = {"params": {"a": 1, "b": 2}, "data": {"rows": 9}, "x": 1}
    over = {"params": {"b": 3}, "data": {"rows": 1, "new": 2}}
    assert harness.overlay(base, over) == {
        "params": {"a": 1, "b": 3}, "data": {"rows": 1, "new": 2}, "x": 1}
    assert base["params"]["b"] == 2


@pytest.mark.parametrize("checks, want", [
    ({"a": True, "b": True}, True),
    ({"a": True, "b": False}, False),
    ({"a": True, "b": None}, False),   # not observable is not passed
    ({}, True),
])
def test_correct_needs_every_check_to_hold(checks, want):
    assert harness.correct(checks) is want


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_sizes_replace_the_real_ones(cell):
    _, entry, real, _ = harness.resolve_cell(ROOT, harness.BENCH_DIR, cell,
                                             rehearse=False)
    _, _, toy, traffic = harness.resolve_cell(ROOT, harness.BENCH_DIR, cell,
                                              rehearse=True)
    assert real["data"]["rows"] >= 1_000_000 > toy["data"]["rows"]
    assert real["params"]["num_leaves"] == 255 > toy["params"]["num_leaves"]
    assert entry["chips"] in (1, 4) and traffic["job"]


def test_an_unknown_cell_is_a_usage_error():
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve_cell(ROOT, harness.BENCH_DIR, "nope", False)
    rc, lines, err = run_cell(ROOT, "--workload", "nope", "--seed", "1",
                              "--seconds", "1", "--trace", "0")
    assert rc == harness.EXIT_USAGE and not lines and "no workload" in err


# ---- found as new files, with no edit ------------------------------------------------------
NEW_JOB = '''
"""A job kind of a later PR: traces one tiny program, trains nothing."""
import contextlib
import jax, jax.numpy as jnp
from benchmarks.lib.harness import Outcome


def run(cell):
    rows = cell.load("datagen", cell.config["data"]["generator"]).make(
        cell.config["data"], cell.seed, 4, stream=0)
    with (cell.spans.traced_window(cell.out_dir) if cell.trace
          else contextlib.nullcontext()):
        jax.jit(lambda x: x * cell.traffic["factor"])(
            jnp.asarray(rows["X"])).block_until_ready()
    return Outcome(attempted=1, failed=0, checks={"ran": True},
                   end_to_end={"echo_per_s": 7.0, "setup_s": 1.0},
                   facts={"answer": 42}, notes={})   # a job may compare nothing
'''
NEW_DATAGEN = '''
import numpy as np


def make(spec, seed, rows, stream):
    return {"X": np.full((rows, spec["features"]), float(seed))}
'''
NEW_READER = '''
def read(run):
    return run.facts["answer"]
'''


def files_under(bench):
    return {os.path.relpath(os.path.join(d, f), bench):
            open(os.path.join(d, f), "rb").read()
            for d, _, files in os.walk(bench) for f in files
            if "__pycache__" not in d}


@pytest.fixture(scope="module")
def later_pr(tmp_path_factory):
    """A copy of the benchmark to which a later PR has added a cell, a
    configuration, a traffic mix, a job kind, a generator and a per-layer
    metric: new files and new entries of BENCHMARK.json, nothing edited."""
    root = str(tmp_path_factory.mktemp("later_pr"))
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "lightgbm_tpu"),
               os.path.join(root, "lightgbm_tpu"))
    before = files_under(bench)
    files = {
        "jobs/echo.py": NEW_JOB, "datagen/constant.py": NEW_DATAGEN,
        "layer_metrics/answer.py": NEW_READER,
        "configs/tiny.json": json.dumps({
            "name": "tiny", "data": {"generator": "constant", "features": 3}}),
        "traffic/echo.json": json.dumps({"job": "echo", "factor": 2.0}),
    }
    for rel, text in files.items():
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "benchmarks/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.echo", "config": "tiny",
                              "traffic": "echo", "chips": 1, "why": "test"})
    spec["end_to_end"].append({
        "name": "echo_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["tiny.echo"]})
    spec["per_layer"].append({
        "name": "answer", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "echo", "moves": "echo_per_s",
        "workloads": ["tiny.echo"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root, bench, before, set(files)


def test_a_later_prs_files_are_found_by_name(later_pr):
    root, bench, _, _ = later_pr
    _, entry, config, traffic = harness.resolve_cell(root, bench, "tiny.echo",
                                                     rehearse=False)
    assert (entry["chips"], config["name"], traffic["job"]) == (1, "tiny", "echo")
    assert harness.load_module(bench, "layer_metrics", "answer").read(
        harness.Run(None, {"answer": 3}, None, None)) == 3
    with pytest.raises(FileNotFoundError, match="no jobs called"):
        harness.load_module(bench, "jobs", "absent")


@pytest.mark.parametrize("trace", [0, 1])
def test_a_later_prs_cell_runs_with_no_edit(later_pr, trace):
    root, bench, before, added = later_pr
    rc, lines, err = run_cell(root, "--workload", "tiny.echo", "--seed", "5",
                              "--seconds", "1", "--trace", str(trace),
                              "--rehearse-cpu")
    assert rc == 0, err
    result = json.loads(lines[-1])
    assert result["correct"] and result["attempted"] == 1
    assert result["compared"] == {} and "compared " not in err
    if trace:
        # only the readers that apply to the cell were asked
        assert result["metrics"]["answer"] == {"value": 42.0, "unit": "1"}
        assert set(result["metrics"]) <= {"answer", "ingest_rows_per_s",
                                          "compile_s", "programs_compiled"}
        assert result["device"]["window_s"] > 0
    else:
        assert result["metrics"] == {
            "echo_per_s": {"value": 7.0, "unit": "1/s"},
            "setup_s": {"value": 1.0, "unit": "s"}}
    assert {k: v for k, v in files_under(bench).items()
            if k not in added} == before


# ---- every cell, end to end, at toy size -----------------------------------------------------
def with_kept(spec: dict) -> dict:
    """`spec` after a later PR has moved kept_for_later.json's entries in."""
    spec = json.loads(json.dumps(spec))
    for key in ("workloads", "end_to_end", "per_layer"):
        spec[key] += KEPT[key]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in KEPT["also_in"]:
            m["workloads"] = m["workloads"] + KEPT["also_in"][m["name"]]
    return spec


@pytest.fixture(scope="module")
def kept_root(tmp_path_factory):
    """A copy of the benchmark whose BENCHMARK.json lists the kept cells
    too; no other file differs."""
    root = str(tmp_path_factory.mktemp("kept"))
    shutil.copytree(harness.BENCH_DIR, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "lightgbm_tpu"),
               os.path.join(root, "lightgbm_tpu"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(with_kept(SPEC), f)
    return root


def test_kept_cells_are_entries_a_later_pr_can_move_in():
    spec = with_kept(SPEC)
    assert set(KEPT["blocked_by"]) == set(KEPT_CELLS)
    assert not set(KEPT_CELLS) & set(CELLS)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in KEPT["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in SPEC["configs"]}
        traffic = harness.load_json(os.path.join(
            harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "jobs",
                                           traffic["job"] + ".py"))
        assert sum(harness.applies(m, w["name"])
                   for m in spec["end_to_end"]) >= 2
    for m in KEPT["per_layer"]:
        assert m["moves"] in e2e and LAYER.match(m["layer"])
    assert set(KEPT["also_in"]) <= {m["name"] for m in
                                    SPEC["end_to_end"] + SPEC["per_layer"]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS + KEPT_CELLS)
def test_rehearsal_prints_the_contracts_result_line(cell, trace, kept_root):
    root, spec = ((kept_root, with_kept(SPEC)) if cell in KEPT_CELLS
                  else (ROOT, SPEC))
    chips = next(w["chips"] for w in spec["workloads"] if w["name"] == cell)
    rc, lines, err = run_cell(root, "--workload", cell, "--seed", "3",
                              "--seconds", "1", "--trace", str(trace),
                              "--rehearse-cpu")
    assert rc == 0, err
    notes, result = lines[:-1], json.loads(lines[-1])
    assert all("note" in json.loads(n) for n in notes if n.startswith("{"))
    assert set(result) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert result["correct"] is True and result["failed"] == 0
    # each number compared beside its limit: last in the result's line, and
    # the last lines of standard error
    compared = result["compared"]
    assert list(result)[-1] == "compared" and len(compared) >= 1
    assert all(harness.within(c) for c in compared.values())
    assert err.strip().splitlines()[-len(compared):] == [
        f"compared {k}: {c['value']!r} {c['holds']} limit {c['limit']!r}"
        for k, c in compared.items()]
    said = {n["note"]: n for n in map(json.loads, notes) if "note" in n}
    if "train" in cell.split(".")[-1]:
        # the window, for a reader of the line: traced, one group of the
        # traffic's `trace_iters` is the one reading
        facts = result["facts"]
        assert set(facts) == {"iterations", "window_s", "iteration_ms",
                              "slowest_group"}
        slowest = facts["slowest_group"]
        assert 0 <= slowest["index"] < facts["iteration_ms"]["n"]
        per_group = facts["iterations"] // facts["iteration_ms"]["n"]
        assert 0 < slowest["update_ms"] + slowest["sync_ms"] <= (
            per_group * facts["iteration_ms"]["max"])
        assert facts["iterations"] == result["attempted"]
        assert facts["window_s"] > 0 and facts["iteration_ms"]["n"] == (
            1 if trace else facts["iterations"] // 2)   # rehearsed in pairs
        assert facts["iteration_ms"]["q1"] <= facts["iteration_ms"]["q3"]
        assert {k: said["groups"][k] for k in facts} == facts
        # the rows each tree histograms: at least the table once, at most
        # what a tree of its depth can, and a quarter of it a chip on four
        rows = said["histogrammed rows"]
        assert rows["first_window_tree"] >= 1
        assert len(rows["hist_rows_by_tree"]) == result["attempted"] + \
            rows["first_window_tree"]
        assert all(1.0 <= got <= most for got, most in zip(
            rows["over_table_rows_by_tree"], rows["most_a_tree_can_by_tree"]))
        if chips > 1:
            assert said["histogrammed rows per chip"]["by_tree"] == [
                r / chips for r in rows["hist_rows_by_tree"]]
    assert result["attempted"] >= 1
    dev = result["device"]
    assert (dev["platform"], dev["count"]) == ("cpu", chips)  # a rehearsal says so
    assert {"kind", "memory_peak_bytes"} <= set(dev)
    declared = spec["per_layer"] if trace else spec["end_to_end"]
    want = {m["name"]: m["unit"] for m in declared
            if harness.applies(m, cell)}
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["unit"] == want[name]
        assert isinstance(metric["value"], float) and metric["value"] > 0
    if trace:
        # on the CPU no device plane exists: the readers of device events
        # find nothing and are left out, the others report
        assert {"ingest_rows_per_s", "compile_s", "programs_compiled"} \
            <= set(result["metrics"])
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        for key in ("device_ops", "idle_gaps"):
            rows = result["breakdown"][key]
            assert 1 <= len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    else:
        assert set(result["metrics"]) == set(want) and "setup_s" in want


BREAK_THE_THIRD_UPDATE = """
import sys, time
t0 = time.perf_counter()
sys.path.insert(0, {root!r})
import lightgbm_tpu as lgb
from benchmarks.lib import harness
calls, update = [], lgb.Booster.update
def third_raises(self, *a, **k):
    calls.append(1)
    if len(calls) == 3:
        raise RuntimeError("the device is gone")
    return update(self, *a, **k)
lgb.Booster.update = third_raises
sys.exit(harness.main(sys.argv[1:], t0))
"""


def test_an_update_that_raises_is_a_failed_iteration_and_ends_the_window():
    proc = subprocess.run(
        [sys.executable, "-c", BREAK_THE_THIRD_UPDATE.format(root=ROOT),
         "--workload", CELLS[0], "--seed", "3", "--seconds", "60",
         "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    # one warm-up iteration, then the window's second update raised
    assert (result["attempted"], result["failed"]) == (2, 1)
    assert result["correct"] is False
    assert any("the device is gone" in n for n in lines[:-1])


# ---- where nothing may run -----------------------------------------------------------------------
def test_without_an_accelerator_nothing_runs():
    # the tests' own environment holds JAX to the CPU, as a machine whose
    # libtpu failed to start would be
    rc, lines, err = run_cell(ROOT, "--workload", CELLS[0], "--seed", "1",
                              "--seconds", "1", "--trace", "0",
                              env={"JAX_PLATFORMS": "cpu"})
    assert rc == harness.EXIT_NO_ACCELERATOR and not lines
    assert "not 'tpu'" in err and "nothing was run" in err


def test_without_the_program_nothing_runs(tmp_path):
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    rc, lines, err = run_cell(str(tmp_path), "--workload", CELLS[0],
                              "--seed", "1", "--seconds", "1", "--trace", "0",
                              "--rehearse-cpu")
    assert rc == harness.EXIT_NO_PROGRAM and not lines
    assert "not in this checkout" in err
