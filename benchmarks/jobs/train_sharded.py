"""Job `train_sharded`: job `train` on a row-sharded table, with what only
a sharded run can be asked.

It calls `jobs/train.py`'s `run` unchanged: same set-up, same window, same
recount.  Around it:

(a) **Before the table is drawn**, and before JAX is touched, it refuses a
    program whose source nowhere names `lgbm_step_row_constant_bytes`, the
    gauge of the bytes of row-shaped arrays that the programs of the
    training step close over, with `ShardedStepHoldsTheTable`: a program
    that cannot state the gauge holds the binned table and the labels as
    constants of its sharded step, which at this cell's size is a program
    of gigabytes that the persistent cache refuses and every run compiles
    again (the one such run was killed at 435 s with a sixth of this
    table, PERF.md).  Reading the package's files costs a tenth of a
    second and builds nothing; what the gauge *reads* is checked in (b),
    on the booster that trained.
(b) After `run` it adds the checks only a sharded job has, from the
    program's gauges ("not observable" where one is gone):
    `step_holds_no_row_constant`, `data_shards_equal_chips`,
    `shard_rows_equal_within_one_block` and `hist_agg_as_resolved`, and
    the shards' rows and the exchange's reckoned bytes among the notes;
    `data_shards` among the facts, by which the readers of the histogram
    work divide a tree's rows, and the rows a chip histograms on a line.
"""

import importlib.util
import pathlib

from benchmarks.lib import program_gauges

ROW_CONSTANTS = "lgbm_step_row_constant_bytes"


class ShardedStepHoldsTheTable(RuntimeError):
    pass


def by_label(snap, name: str) -> dict:
    """label text -> value of every child of one gauge family."""
    if snap is None:
        return {}
    return {k[len(name) + 1:-1]: float(v) for k, v in snap.items()
            if k.startswith(name + "{") and isinstance(v, (int, float))}


def row_constants(snap):
    """site -> bytes, or None where the program states none."""
    return by_label(snap, ROW_CONSTANTS) or None


def source_names(gauge: str, package: str = "lightgbm_tpu") -> bool:
    """Whether any file of the program's package names `gauge`."""
    found = importlib.util.find_spec(package)
    if found is None or not found.origin:
        return False
    return any(gauge in p.read_text(errors="ignore")
               for p in pathlib.Path(found.origin).parent.rglob("*.py"))


def refuse_a_program_that_cannot_say(package: str = "lightgbm_tpu") -> None:
    if not source_names(ROW_CONSTANTS, package):
        raise ShardedStepHoldsTheTable(
            "train_sharded refuses this program before the table is drawn: "
            f"it does not state {ROW_CONSTANTS}, so its sharded training "
            "step cannot be shown to hold no row-shaped constant. A step "
            "that closes over the binned table and the labels is, at this "
            "cell's size, a program of gigabytes compiled anew on every run.")


def sharded_checks(cell, snap):
    """(checks, notes) from the program's gauges after the run."""
    g = program_gauges.gauge
    chips = len(cell.devices)
    shards = g(snap, "lgbm_data_shards")
    swept = by_label(snap, "lgbm_shard_rows")
    mine = ([swept.get(f'shard="{k}"') for k in range(int(shards))]
            if shards else [None])
    # one block of the kernel's grid where the kernel states one, else none
    blocks = g(snap, "lgbm_hist_grid", axis="row_blocks")
    block = mine[0] / blocks if blocks and mine[0] else 0.0
    sites = row_constants(snap)
    mode = g(snap, "lgbm_hist_agg", mode=cell.traffic["sharded"]["hist_agg"])
    checks = {
        "step_holds_no_row_constant":
            None if sites is None else not any(sites.values()),
        "data_shards_equal_chips": None if shards is None else shards == chips,
        "shard_rows_equal_within_one_block":
            None if None in mine else max(mine) - min(mine) <= block,
        "hist_agg_as_resolved": None if mode is None else mode == 1.0,
    }
    notes = {"step_row_constant_bytes": sites, "data_shards": shards,
             "shard_rows": mine,
             "shard_table_rows": by_label(snap, "lgbm_shard_table_rows"),
             "exchange_bytes_per_tree":
                 by_label(snap, "lgbm_exchange_bytes_per_tree")}
    return checks, notes


def run(cell):
    refuse_a_program_that_cannot_say()
    outcome = cell.load("jobs", "train").run(cell)
    checks, notes = sharded_checks(cell, program_gauges.snapshot())
    outcome.checks.update(checks)
    outcome.notes.update(notes)
    shards = outcome.facts["data_shards"] = notes["data_shards"]
    if shards:
        # every chip histograms its own rows of each tree
        cell.say("histogrammed rows per chip", data_shards=shards,
                 by_tree=[r / shards for r in
                          outcome.facts["hist_rows_by_tree"]])
    return outcome
