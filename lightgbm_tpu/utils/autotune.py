"""Persisted perf autotuning: measured winners replace hard-coded "auto".

PRs 3-5 tuned the histogram kernel by hand and froze the winners into
`_resolve_hist_impl`'s heuristics; every new backend generation re-opens
the question and the answer so far lived in a human re-running
tools/perf_probe.py.  This module makes the sweep's verdict durable:

* a PROFILE FILE (JSON, beside the PR-4 persistent XLA compile cache by
  default) maps (backend platform, device count, shape bucket) to the
  measured winning configuration — hist impl x block today, with the
  aggregation and bucket-policy winners recorded alongside for the
  learner's other "auto" sites;
* `tpu_autotune=load` resolves every "auto" from the profile when a
  matching entry exists; a profile recorded on a DIFFERENT platform or
  device count raises AutotuneStaleProfile — measured numbers from the
  wrong topology are worse than heuristics because they look authoritative;
* `tpu_autotune=tune` measures the missing bucket first (the same
  bench_hist_operands microbench perf_probe's hist sweep runs, on
  synthetic operands keyed by the bucket — dataset-independent, so one
  profile serves every same-shaped dataset), persists it, then loads.

Shape buckets quantize (rows, features) to powers of two and carry the
bin count exactly — the same coarsening the PR-4 compile-cache shape
buckets apply, so profile entries and cached XLA programs invalidate on
the same boundaries.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
import time
from typing import Dict, Optional

import numpy as np

LOG = logging.getLogger("lightgbm_tpu.autotune")

PROFILE_VERSION = 1
# rows of synthetic operands per tune measurement: enough blocks for a
# stable rows/s at every candidate block size, small enough that a tune
# pass costs seconds, not a training run
_TUNE_ROWS_CAP = 131072
_TUNE_REPS = 3


class AutotuneStaleProfile(RuntimeError):
    """The profile was recorded on a different backend/topology.

    Raised (never silently ignored) in load/tune modes: applying a v5e
    profile to a v4 pod — or a 1-chip profile to an 8-chip mesh — would
    pin measured-looking but wrong winners.  Delete or re-tune the file."""


def profile_path(config) -> str:
    """Resolved profile location: the explicit override, else beside the
    persistent XLA compile cache, else a dotfile in the working dir."""
    explicit = str(getattr(config, "tpu_autotune_profile", "") or "")
    if explicit:
        return explicit
    cache_dir = str(getattr(config, "tpu_compile_cache_dir", "") or "")
    if cache_dir:
        return os.path.join(cache_dir, "autotune_profile.json")
    return os.path.join(os.getcwd(), ".lgbtpu_autotune.json")


def backend_fingerprint() -> Dict[str, object]:
    import jax

    return {"platform": str(jax.devices()[0].platform),
            "device_count": int(jax.device_count())}


def shape_bucket(n_rows: int, num_features: int, num_bins: int) -> str:
    """Power-of-two (rows, features) + exact bin count bucket key."""
    def up2(x):
        return 1 << max(int(x) - 1, 1).bit_length()

    return f"r{up2(n_rows)}_f{up2(num_features)}_b{int(num_bins)}"


def load_profile(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            prof = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError) as exc:
        LOG.warning("autotune profile %r unreadable (%s) — ignoring", path,
                    exc)
        return None
    if not isinstance(prof, dict) or "entries" not in prof:
        LOG.warning("autotune profile %r malformed — ignoring", path)
        return None
    return prof


def save_profile(path: str, profile: dict) -> None:
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    # atomic replace: a concurrent reader never sees a half-written file
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(profile, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def check_fingerprint(profile: dict, path: str) -> None:
    """Raise AutotuneStaleProfile unless the profile matches this process'
    backend platform, device count, and schema version."""
    fp = backend_fingerprint()
    if int(profile.get("version", -1)) != PROFILE_VERSION:
        raise AutotuneStaleProfile(
            f"autotune profile {path!r} has schema version "
            f"{profile.get('version')!r}, this build expects "
            f"{PROFILE_VERSION}; re-run `perf_probe tune` (or delete it)")
    for key in ("platform", "device_count"):
        got, now = profile.get(key), fp[key]
        if got != now:
            raise AutotuneStaleProfile(
                f"autotune profile {path!r} was recorded on {key}={got!r} "
                f"but this process runs {key}={now!r} — measured winners "
                "from another topology are refused; re-run `perf_probe "
                "tune` here (or point tpu_autotune_profile elsewhere)")


def tune_entry(n_rows: int, num_features: int, num_bins: int,
               precision: str, split_batch: int = 25) -> dict:
    """Measure the hist-kernel winners for one shape bucket.

    Synthetic operands (bucket-keyed rng) through the grower's own
    batched contraction — the same microbench tools/perf_probe.py's hist
    sweep times — across impl x block.  A candidate the compiler refuses
    raises: the winner is never decided by a caught exception.  Returns
    the profile entry (winning impl/block + the full measured table)."""
    import jax
    import jax.numpy as jnp

    from ..ops.histogram import (PERFEATURE_AUTO_PRECISIONS,
                                 bench_hist_operands,
                                 build_histogram_batched_t)

    n = min(int(n_rows), _TUNE_ROWS_CAP)
    rng = np.random.default_rng(num_features * 1_000_003 + num_bins)
    bins_np = rng.integers(
        0, num_bins, size=(n, num_features)).astype(
            np.uint8 if num_bins <= 256 else np.int32)
    K = split_batch

    # pallas candidates run the interpreter off-TPU: slow but small n
    # keeps a CPU tune pass tractable.  pallas2 is a candidate exactly
    # where the auto rule offers it (learner._resolve_hist_impl): f32/f64
    # and int16 pallas2 are explicit-only
    candidates = [("xla", 8192), ("xla", 16384)]
    if precision in PERFEATURE_AUTO_PRECISIONS:
        candidates += [("pallas2", 4096), ("pallas2", 8192)]

    def _fit_block(block: int) -> int:
        # datasets smaller than a candidate block still deserve a
        # measured winner: clamp to the largest pow2 block the rows can
        # fill (floor 1024) instead of skipping — every candidate
        # skipping out used to raise 'no viable candidate' on any
        # dataset under the smallest block
        while block > 1024 and block > n:
            block //= 2
        return block

    seen = set()
    table = {}
    for impl, block in candidates:
        block = _fit_block(block)
        if n < block or (impl, block) in seen:
            continue
        seen.add((impl, block))
        bins_tb, stats, n_use = bench_hist_operands(
            bins_np, precision, block)
        nb = n_use // block
        leaf_b = jnp.asarray(
            rng.integers(0, K, size=n_use).astype(np.int32)
            .reshape(nb, block))
        slots = jnp.arange(K, dtype=jnp.int32)
        # graftlint: disable-next-line=J201 throwaway measurement probes on synthetic operands — deliberately off-ledger so tuning never perturbs n_programs gates
        fn = jax.jit(lambda b, s, l, i=impl: build_histogram_batched_t(
            b, s, l, slots, num_bins, precision, impl=i))
        # graftlint: disable-next-line=J201 probe warm-up (see above)
        jax.block_until_ready(fn(bins_tb, stats, leaf_b))  # compile
        t0 = time.perf_counter()
        for _ in range(_TUNE_REPS):
            # graftlint: disable-next-line=J201 probe timing loop (see above)
            jax.block_until_ready(fn(bins_tb, stats, leaf_b))
        rps = n_use * _TUNE_REPS / max(time.perf_counter() - t0, 1e-9)
        table[f"{impl}:{block}"] = rps
    if not table:
        raise RuntimeError(
            f"autotune measured no viable candidate for "
            f"{n_rows}x{num_features} rows/features at {num_bins} bins")
    best = max(table, key=table.get)
    impl, block = best.split(":")
    return {
        "hist_impl": impl,
        "block_rows": int(block),
        "rows_per_sec": table[best],
        # the non-hist "auto" winners: recorded from the same measured
        # principles the heuristics encode (scatter beats psum whenever a
        # real data axis exists — PR-11's comm sweep; bucket policy
        # trades compile count for pad waste and stays fine by default)
        "hist_agg": ("scatter" if backend_fingerprint()["device_count"] > 1
                     else "psum"),
        "bucket_policy": "fine",
        "precision": precision,
        "table": table,
    }


def resolve_autotune(config, n_rows: int, num_features: int, num_bins: int,
                     precision: str) -> Optional[dict]:
    """The learner's one entry point: the profile entry for this shape
    bucket, or None (mode off / nothing measured).  load mode refuses
    stale profiles (AutotuneStaleProfile); tune mode measures and
    persists missing entries first."""
    mode = str(getattr(config, "tpu_autotune", "off"))
    if mode == "off":
        return None
    if mode not in ("load", "tune"):
        raise ValueError(f"tpu_autotune={mode!r}; expected off, load, "
                         "or tune")
    path = profile_path(config)
    prof = load_profile(path)
    if prof is not None:
        check_fingerprint(prof, path)
    bucket = shape_bucket(n_rows, num_features, num_bins)
    entry = (prof or {}).get("entries", {}).get(bucket)
    if entry is not None and str(entry.get("precision")) != precision:
        entry = None  # measured at another stats precision: re-tune
    if entry is None:
        if mode == "load":
            LOG.info("autotune: no profile entry for bucket %s at %r — "
                     "auto falls back to the built-in heuristics", bucket,
                     path)
            return None
        try:
            entry = tune_entry(n_rows, num_features, num_bins, precision)
        except RuntimeError as exc:
            # nothing measurable (e.g. a dataset below the smallest
            # candidate block): tuning must never kill a training run —
            # fall back to the heuristics, loudly, and persist nothing
            LOG.warning("autotune: %s — auto falls back to the built-in "
                        "heuristics", exc)
            return None
        prof = prof or {"version": PROFILE_VERSION,
                        **backend_fingerprint(), "entries": {}}
        prof["entries"][bucket] = entry
        save_profile(path, prof)
        LOG.info("autotune: measured bucket %s -> %s:%d (%.0f rows/s), "
                 "persisted to %r", bucket, entry["hist_impl"],
                 entry["block_rows"], entry["rows_per_sec"], path)
    return entry


__all__ = ["AutotuneStaleProfile", "PROFILE_VERSION", "backend_fingerprint",
           "check_fingerprint", "load_profile", "profile_path",
           "resolve_autotune", "save_profile", "shape_bucket",
           "tune_entry"]
