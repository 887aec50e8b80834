"""Gauges of the program's registry, read by their printed key
(`name{label="value",...}`, as `obs.REGISTRY.snapshot()` has them).  Like
`lib/sut.py`, a read answers None where the program no longer (or, at the
parent of the PR that added a gauge, not yet) has the name: the per-layer
reader then reports nothing and the result line leaves its metric out."""


def snapshot():
    """The registry's snapshot, or None where the program has none."""
    try:
        from lightgbm_tpu import obs

        return dict(obs.REGISTRY.snapshot())
    except (ImportError, AttributeError):
        return None


def gauge(snap, name: str, **labels):
    """One gauge of a snapshot; None where it is absent or no number."""
    if snap is None:
        return None
    key = name
    if labels:
        key += "{" + ",".join(f'{k}="{v}"' for k, v in labels.items()) + "}"
    value = snap.get(key)
    return float(value) if isinstance(value, (int, float)) else None


HIST_ROWS_GAUGE = "lgbm_hist_rows_per_tree"


def hist_rows_per_tree(snap):
    """{"swept", "contracted", "live"} -> rows a tree that the histogram
    kernel's calls swept, contracted and found live (means over the trees
    the host has read, summed over a job's row shards), or None where any
    of the three is gone."""
    rows = {kind: gauge(snap, HIST_ROWS_GAUGE, kind=kind)
            for kind in ("swept", "contracted", "live")}
    return None if None in rows.values() else rows


def contracted_share(rows):
    """100 x contracted / swept of `hist_rows_per_tree`'s answer; None
    where there is none or the kernel counted nothing."""
    if rows is None or not rows["swept"] or not rows["contracted"]:
        return None
    return 100.0 * rows["contracted"] / rows["swept"]
