"""The harness: one cell per process, everything found by name.

    BENCHMARK.json  workloads[name] -> config, traffic, chips
    configs/<config>.json           the configuration as it is run
    traffic/<traffic>.json          the traffic mix; names its job
    datagen/<generator>.py          make(spec, seed, rows, stream)
    jobs/<job>.py                   run(cell) -> Outcome
    layer_metrics/<metric>.py       read(run) -> number or None

A later PR adds a cell, a configuration, a traffic mix, a job kind or a
per-layer metric as new files plus new entries of BENCHMARK.json; nothing
here lists them.  This module imports JAX only inside `main`, after the
environment of the run is fixed.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR_NAME = "bench_out"  # in the checkout, listed in .gitignore
EXIT_USAGE, EXIT_NO_ACCELERATOR, EXIT_NO_PROGRAM = 2, 3, 4


# ---- discovery ---------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """`<bench_dir>/<kind>/<name>.py`, loaded by its path."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} called {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def overlay(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, group by group."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (overlay(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def resolve_cell(root: str, bench_dir: str, name: str, rehearse: bool):
    """(BENCHMARK.json, its workload entry, the configuration, the traffic
    mix) for one cell; a rehearsal lays each file's `rehearse` group over
    the rest of it."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       + ", ".join(w["name"] for w in spec["workloads"]))
    conf_entry = next(c for c in spec["configs"]
                      if c["name"] == entry["config"])
    config = load_json(os.path.join(root, conf_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     entry["traffic"] + ".json"))
    if rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    return spec, entry, config, traffic


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


# ---- what a job gets and gives -------------------------------------------------
@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    bench_dir: str
    out_dir: str
    t0: float            # process start on time.perf_counter
    devices: list        # the chips the cell asked for
    spans: object        # lib.spans.Spans
    compiles: object     # lib.compilewatch.CompileWatch

    def load(self, kind: str, name: str):
        return load_module(self.bench_dir, kind, name)

    def say(self, what: str, **fields) -> None:
        """One of the run's earlier lines."""
        print(json.dumps({"note": what, **fields}), flush=True)

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


@dataclass
class Outcome:
    attempted: int
    failed: int
    checks: dict                  # name -> True / False / None (see `correct`)
    end_to_end: dict              # metric name -> value
    facts: dict                   # what the per-layer readers need
    notes: dict                   # what else the run found, for its earlier lines
    # each number `correct` compared, beside its limit:
    # name -> {"value": .., "limit": .., "holds": "<=" or ">="}
    compared: dict = field(default_factory=dict)
    # what a reader of the result's line needs to see the window, under its
    # key `facts`: plain numbers (job `train`: iterations, seconds, the
    # quartiles of an iteration's milliseconds)
    result_facts: dict = field(default_factory=dict)


@dataclass
class Run:
    """What a per-layer reader is handed."""
    cell: Cell
    facts: dict
    trace: object                 # lib.xplane.Trace
    window: tuple                 # (start, end) on the trace's clock

    def metric(self, name: str):
        """What another per-layer reader says of this run."""
        return self.cell.load("layer_metrics", name).read(self)


def compare(value, holds: str, limit) -> dict:
    """One entry of `Outcome.compared`: `value` has to be `<=` or `>=`
    its `limit`."""
    if holds not in ("<=", ">="):
        raise ValueError(f"a number holds its limit by <= or >=, not {holds!r}")
    return {"value": value, "holds": holds, "limit": limit}


def within(c: dict) -> bool:
    """Whether a compared number is on its limit's right side."""
    return (c["value"] <= c["limit"] if c["holds"] == "<="
            else c["value"] >= c["limit"])


def correct(checks: dict) -> bool:
    """A run is correct when every check held.  A check whose fact could not
    be read (None, "not observable" on the run's earlier lines) did not
    hold: a run that fell back to another path must not pass because the
    name that would have said so is gone."""
    return all(v is True for v in checks.values())


# ---- the run ---------------------------------------------------------------------
def parse_args(argv):
    p = argparse.ArgumentParser(prog="benchmarks/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="the same code at toy size on the CPU; the result "
                        "says platform cpu and is no measurement")
    return p.parse_args(argv)


def set_environment(root: str, chips: int, rehearse: bool) -> None:
    """Fixed before JAX is imported.  The compile cache stays where the
    machine puts it, else at the program's own fixed place in the checkout
    (the path is part of the cache's key)."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={chips}"])


def main(argv, t0: float) -> int:
    args = parse_args(argv)
    bench_dir, root = BENCH_DIR, os.path.dirname(BENCH_DIR)
    try:
        spec, entry, config, traffic = resolve_cell(
            root, bench_dir, args.workload, args.rehearse_cpu)
    except (KeyError, FileNotFoundError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_USAGE
    chips = int(entry["chips"])
    set_environment(root, chips, args.rehearse_cpu)
    sys.path.insert(0, root)

    import jax

    try:
        import lightgbm_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e}); "
              "nothing was run", file=sys.stderr)
        return EXIT_NO_PROGRAM
    from . import compilewatch, device, spans, xplane

    try:
        devices = device.require("cpu" if args.rehearse_cpu else "tpu", chips)
    except device.NoAccelerator as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    # every program of the run goes to the persistent cache, so that only
    # the first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    out_dir = os.path.join(root, OUT_DIR_NAME, args.workload,
                           f"seed{args.seed}-trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    cell = Cell(name=args.workload, config=config, traffic=traffic,
                seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                bench_dir=bench_dir, out_dir=out_dir, t0=t0, devices=devices,
                spans=spans.Spans(),
                compiles=compilewatch.CompileWatch().install())
    try:
        outcome = cell.load("jobs", traffic["job"]).run(cell)
    except Exception:
        traceback.print_exc()
        return 1

    dev = device.describe(devices)
    result = {"correct": correct(outcome.checks),
              "attempted": outcome.attempted, "failed": outcome.failed}
    cell.say("checks", **{k: ("not observable" if v is None else v)
                          for k, v in outcome.checks.items()})
    cell.say("facts", peak_bytes_by_device=device.peaks_by_device(devices),
             **outcome.notes)
    metrics = {}
    if cell.trace:
        trace = xplane.load(xplane.find_xplane(cell.out_dir))
        t0, t1 = xplane.window_of(trace, spans.WINDOW_SPAN)
        run = Run(cell, outcome.facts, trace, (t0, t1))
        for m in spec["per_layer"]:
            if not applies(m, cell.name):
                continue
            value = run.metric(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        dev.update(busy_s=xplane.busy_seconds(trace, t0, t1),
                   window_s=t1 - t0)
        result["breakdown"] = {
            "device_ops": xplane.top_device_ops(trace, t0, t1),
            "idle_gaps": xplane.idle_gaps_by_host_span(trace, t0, t1)}
    else:
        for m in spec["end_to_end"]:
            if applies(m, cell.name):
                metrics[m["name"]] = {
                    "value": float(outcome.end_to_end[m["name"]]),
                    "unit": m["unit"]}
    result.update(metrics=metrics, device=dev, facts=outcome.result_facts,
                  compared=outcome.compared)
    print(json.dumps(result), flush=True)
    # the numbers compared are also the last lines of standard error
    for name, c in outcome.compared.items():
        print(f"compared {name}: {c['value']!r} {c['holds']} limit "
              f"{c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0
