"""Rows per second of `Dataset.construct` (bin finding, binning, the
upload), on the host clock around the call and a wait for the device."""


def read(run):
    return run.facts.get("ingest_rows_per_s")
