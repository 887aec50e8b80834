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
