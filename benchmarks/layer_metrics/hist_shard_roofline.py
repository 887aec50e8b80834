"""The histogram kernel's share of its roofline on a row-sharded table, in
percent: `hist_kernel_roofline`'s arithmetic (each call's slots, planes
and statistic type from its own instruction text, `lib/opcount.
hist_contraction` against the published peaks) with the rows of ONE shard,
the table's rows over the shards the job states (`data_shards`), since
every chip's call contracts its own shard and the times of all chips'
calls are summed.  `hist_kernel_roofline` counts the whole table for every
chip's call and would read the shards' count too much.  None where the run
states no shard count, or the kernel is not the one that reader knows."""

from benchmarks.lib import opcount, peaks


def read(run):
    shards = run.facts.get("data_shards")
    if not shards:
        return None
    whole = run.cell.load("layer_metrics", "hist_kernel_roofline")
    hist = run.cell.load("layer_metrics", "hist_build_ms_per_iter")
    facts = run.facts
    rows = facts["rows"] / shards
    ops = byts = seconds = 0.0
    for ev in hist.events(run):
        for name, dur in zip(ev.names, ev.dur):
            shape = whole.call_shape(name)
            if shape is None:
                return None
            slots, planes, stat_bytes = shape
            o, b = opcount.hist_contraction(
                rows, facts["features"], facts["bins"], slots, planes,
                stat_bytes=stat_bytes)
            ops, byts, seconds = ops + o, byts + b, seconds + float(dur)
    if not seconds:
        return None
    peak = peaks.peaks_for(run.cell.devices[0].device_kind)
    share, bound = opcount.roofline(ops, byts, seconds, peak["bf16_flops"],
                                    peak["hbm_bytes_per_s"])
    run.cell.say("hist_shard_roofline", bound=bound, kernel_s=seconds,
                 operations=ops, bytes=byts, rows_per_shard=rows)
    return share
