"""Host seconds of set-up spent staging chunks for the device binner: the
program's `ingest/stage` spans (`DeviceBinner._prep_chunk`: the float64
copy and the two key planes of every chunk), summed.  The device bins
chunk i while the host stages chunk i+1, so this is the part of ingest no
faster device shortens.  None where the table was binned on the host or
the program records no such span."""

from benchmarks.lib import program_spans


def read(run):
    return program_spans.setup_seconds(run, "ingest/stage")
