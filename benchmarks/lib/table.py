"""The start of every job that trains: the parameters as they are run, the
table and the hold-out from the seed, and the table binned by
`Dataset.construct`."""

from dataclasses import dataclass

from . import device, sut


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
