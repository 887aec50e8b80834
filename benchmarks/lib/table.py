"""What every job that trains shares: at its start the parameters as they
are run, the table and the hold-out from the seed, and the table binned by
`Dataset.construct`; after its window the facts its per-layer readers get
of the set-up and of the trees."""

from dataclasses import dataclass

from . import device, program_gauges, reference, sut


@dataclass
class Table:
    params: dict       # the configuration's, the traffic's laid over them
    data: dict         # the same for the configuration's `data` group
    train: dict        # the generator's output for the training rows
    hold: dict         # the same for the hold-out rows
    dataset: object    # lgb.Dataset, constructed
    ingest_s: float    # Dataset.construct and the wait for the device


def build(cell) -> Table:
    import lightgbm_tpu as lgb

    spans = cell.spans
    params = {**cell.config["params"], **cell.traffic.get("params", {})}
    data = {**cell.config["data"], **cell.traffic.get("data", {})}
    if cell.trace:
        # the program's own host spans, mirrored into the profiler's trace
        params["tpu_telemetry"] = "trace"
    sut.ledger()
    gen = cell.load("datagen", data["generator"])
    with spans.span("bench/setup/make_data"):
        train = gen.make(data, cell.seed, int(data["rows"]), stream=0)
        hold = gen.make(data, cell.seed, int(data["holdout_rows"]), stream=1)
    with spans.span("bench/setup/ingest"):
        ds = lgb.Dataset(train["X"], label=train["y"], params=params)
        ds.construct()
        device.sync()
    return Table(params, data, train, hold, ds,
                 spans.walls("bench/setup/ingest")[-1])


def setup_facts(cell, table: Table, setup_compiles, window_compiles: int):
    """(notes, facts) every such job reports about its set-up."""
    rows = int(table.data["rows"])
    notes = {"programs_in_setup": setup_compiles.programs,
             "compile_or_load_s_in_setup": setup_compiles.seconds,
             "cache_hits_in_setup": setup_compiles.cache_hits,
             "programs_in_window": window_compiles,
             "ingest_s": table.ingest_s}
    facts = {"ingest_rows_per_s": rows / table.ingest_s,
             "setup_compiles": setup_compiles}
    return notes, facts


def histogram_facts(cell, trees: list, first_window_tree: int,
                    rows: int) -> dict:
    """The facts of the histogram work (`reference.window_histogram_facts`,
    from the trees as the model text states them), and a line that says
    each tree's histogrammed rows over the table's rows beside the most a
    tree of its depth can histogram, 1 + depth / 2: a reading over that
    is a fault of the count, not of the program.  The line also has what
    the program's kernel says it swept, contracted and found live a tree
    (`program_gauges.hist_rows_per_tree`; None where it says nothing): an
    untraced run reads no per-layer metric, and the contracted share is
    what a seed's rate varies with."""
    facts = reference.window_histogram_facts(trees, first_window_tree)
    kernel_rows = program_gauges.hist_rows_per_tree(program_gauges.snapshot())
    cell.say("histogrammed rows", table_rows=rows, **facts,
             kernel_rows_per_tree=kernel_rows,
             kernel_contracted_share=program_gauges.contracted_share(
                 kernel_rows),
             over_table_rows_by_tree=[r / rows for r in
                                      facts["hist_rows_by_tree"]],
             most_a_tree_can_by_tree=[1 + reference.depth(t) / 2
                                      for t in trees])
    return facts
