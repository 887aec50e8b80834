"""Rows the histogram kernel's calls contracted over rows they swept, in
percent, over the trees grown (`lgbm_hist_rows_per_tree{kind="swept"|
"contracted"|"live"}`: means over the trees the host has read, summed over
a job's row shards, set where the host reads a tree, so after the window).
A kernel that contracts every row it sweeps reads 100; one that packs a
block's live rows and contracts only the sub-blocks that hold one reads
the share of the table a call still pays for, which cannot fall under the
live share.  It is also the covariate of `train_iters_per_s` between seeds:
a table whose trees keep more rows live iterates slower.

An earlier line says the three row counts and live over contracted (the
packing's efficiency: the rest are tail lanes of half-full sub-blocks).
None where the program sets no such gauge (the parent of the PR that added
it), where no tree has been read, and where the kernel counts nothing."""

from benchmarks.lib import program_gauges


def from_snapshot(snap):
    return program_gauges.contracted_share(
        program_gauges.hist_rows_per_tree(snap))


def read(run):
    rows = program_gauges.hist_rows_per_tree(program_gauges.snapshot())
    value = program_gauges.contracted_share(rows)
    if value is not None:
        run.cell.say("hist_rows_contracted_share", **rows,
                     live_over_contracted=rows["live"] / rows["contracted"])
    return value
