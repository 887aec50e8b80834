"""Perf-regression sentinel: compare two bench records metric-by-metric.

    python tools/bench_diff.py OLD.json NEW.json    # explicit old vs new
    python tools/bench_diff.py --gate OLD.json NEW.json   # same, CI intent

Semantics:

* every known metric carries a DIRECTION (higher-better throughput vs
  lower-better walls/overheads) and a relative TOLERANCE — a metric
  outside tolerance in the bad direction is a regression;
* comparisons are REFUSED (exit 2, loud message) when the two records
  ran on different backends, when either side is marked degraded, or
  when either side is a crash record — a TPU-vs-CPU ratio is fiction
  and the tool says so instead of printing it;
* ``--allow-degraded`` permits same-backend degraded-vs-degraded
  comparisons (informational);
* exit codes: 0 = comparable + no regression, 1 = regression,
  2 = refused, 3 = usage/IO error.  ``--gate`` is an alias that makes
  the intent explicit where a nonzero exit must fail a run.
"""

import argparse
import json
import os
import sys

EXIT_OK = 0
EXIT_REGRESSION = 1
EXIT_REFUSED = 2
EXIT_ERROR = 3

# direction: +1 = higher is better, -1 = lower is better.
# tolerance: relative slack before a bad-direction move counts as a
# regression (generous where cross-round container variance is known).
METRICS = {
    "value": (+1, 0.15),                      # headline iters/s
    "predict_rows_per_sec": (+1, 0.15),
    "serve_rows_per_sec": (+1, 0.20),
    "serve_goodput_rows_per_sec": (+1, 0.20),
    "ingest_rows_per_sec": (+1, 0.20),
    "hist_int8_rows_per_sec": (+1, 0.20),
    "hist_hilo_rows_per_sec": (+1, 0.20),
    "train_auc": (+1, 0.01),
    "serve_p99_ms": (-1, 0.30),
    "serve_shed_pct": (-1, 0.50),
    "eval_ms_per_iter": (-1, 0.30),
    "checkpoint_overhead_pct": (-1, 0.50),
    "resume_s": (-1, 0.30),
    "resume_elastic_s": (-1, 0.30),
    "collective_timeout_recovery_s": (-1, 0.30),
    # OOM recovery (ISSUE 15): rollback + ladder step + retried
    # iteration — wide slack, it embeds one training iteration's wall
    "oom_recovery_s": (-1, 0.50),
    # budget minus observed train peak: MORE headroom is better; null
    # on CPU rounds (no capacity report -> no budget resolves).  The
    # slack is WIDE on purpose: headroom is a small difference of two
    # large numbers, so ordinary peak jitter swings it by large
    # fractions — only losing more than the whole baseline headroom
    # (crossing toward over-budget) scores as a regression
    "hbm_budget_headroom_bytes": (+1, 1.00),
    "compile_s": (-1, 0.20),
    "n_programs": (-1, 0.0),                  # program zoo: exact gate
    "n_programs_train": (-1, 0.0),
    "train_peak_hbm_bytes": (-1, 0.10),       # HBM budget (ISSUE 12)
    "serve_model_hbm_bytes": (-1, 0.10),
    # drift-monitor cost (ISSUE 14): absolute percentages at CPU-noise
    # scale, so the slack is wide — the hard bound lives in the
    # telemetry off-overhead test, this just tracks the trend
    "drift_overhead_pct": (-1, 1.00),
    # out-of-core streaming (ISSUE 16): throughput at 4x the resident
    # cap, and the fraction of H2D copy wall hidden behind histogram
    # work.  Both noisy on CPU rounds (copy/compute ratio is nothing
    # like the PCIe/ICI one), hence wide slack; the hard guarantees
    # (bitwise models, bounded programs) live in tests/test_stream.py
    "stream_rows_per_sec": (+1, 0.35),
    "stream_overlap_pct": (+1, 0.50),
    # per-iteration grow wall (ISSUE 18)
    "grow_iter_ms": (-1, 0.30),
    # fleet serving (ISSUE 19): replicated-dispatch goodput across the
    # device set, cold-replica time-to-first-batch (AOT deserialization
    # path — wide slack, it embeds process/session startup wall), and
    # the per-model serving-table footprint (quantization exists to
    # shrink it; a tightened 10% band would fight f32 rounds, so the
    # band only flags a real format regrowth)
    "serve_fleet_goodput_rows_per_sec": (+1, 0.25),
    "serve_cold_start_ms": (-1, 0.50),
    "serve_table_hbm_bytes": (-1, 0.10),
}


class RecordError(ValueError):
    """Unreadable/malformed bench record — maps to EXIT_ERROR, never to
    the regression code (CI must distinguish 'bench got slower' from
    'your path is wrong')."""


def load_record(path):
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError) as exc:
        raise RecordError(f"bench_diff: cannot read {path!r}: {exc}")
    parsed = rec.get("parsed", rec)
    if not isinstance(parsed, dict):
        if "parsed" in rec:
            # a crash wrapper ({'rc': 1, 'parsed': null}): keep it as a
            # record so refusal() fires LOUDLY on it
            return {"error": f"crashed round (rc={rec.get('rc')}, "
                             "parsed=null)"}
        raise RecordError(f"bench_diff: {path!r} holds no record dict")
    return parsed


def _backend(rec):
    return str(rec.get("backend", rec.get("platform", "unknown")))


def refusal(old, new, allow_degraded=False):
    """Reason this comparison must not be scored, or None."""
    for tag, rec in (("old", old), ("new", new)):
        if rec.get("error"):
            return (f"{tag} record is a CRASH record "
                    f"({rec['error']!r}) — nothing to compare")
    b_old, b_new = _backend(old), _backend(new)
    if b_old != b_new:
        return (f"cross-backend comparison refused: old ran on "
                f"{b_old!r}, new on {b_new!r} — a "
                "TPU-vs-degraded-CPU ratio is fiction, not a regression "
                "signal")
    degraded = bool(old.get("degraded")) or bool(new.get("degraded"))
    if degraded and not allow_degraded:
        which = " and ".join(tag for tag, r in (("old", old), ("new", new))
                             if r.get("degraded"))
        return (f"degraded comparison refused: {which} ran on the "
                "degraded fallback path (reduced problem, throwaway "
                "container) — pass --allow-degraded for an "
                "informational same-backend diff")
    return None


def diff(old, new, tolerance_scale=1.0):
    """[(metric, old, new, ratio, verdict)] for every shared metric."""
    rows = []
    for metric, (direction, tol) in METRICS.items():
        a, b = old.get(metric), new.get(metric)
        if a is None or b is None or not isinstance(a, (int, float)) \
                or not isinstance(b, (int, float)):
            continue
        if a == 0:
            # zero baseline: the relative tolerance has no scale, so
            # never score it as a regression — a 0.0 -> 0.01 shed_pct
            # move is noise, not a gate failure; surface it as
            # new-nonzero for the human reader instead
            rows.append((metric, a, b, float("inf") if b else 1.0,
                         "ok" if b == 0 else "new-nonzero"))
            continue
        ratio = b / a
        tol = tol * tolerance_scale
        # tolerance band scaled by |a|, compared as a signed DELTA: a
        # multiplicative band inverts for negative baselines (headroom
        # can legitimately go negative — an over-budget round improving
        # from -1.0e9 to -0.9e9 must not score as a regression)
        band = tol * abs(a)
        delta = b - a
        if direction > 0:            # higher better: a big drop is bad
            bad = delta < -band
            improved = delta > band
        else:                        # lower better: a big rise is bad
            bad = delta > band
            improved = delta < -band
        verdict = "REGRESSION" if bad else ("improved" if improved else "ok")
        rows.append((metric, a, b, ratio, verdict))
    return rows


def format_table(rows, old_name, new_name):
    lines = [f"{'metric':<32s} {'old':>14s} {'new':>14s} {'ratio':>7s}  "
             f"verdict   ({old_name} -> {new_name})"]
    for metric, a, b, ratio, verdict in rows:
        lines.append(f"{metric:<32s} {a:>14.4g} {b:>14.4g} "
                     f"{ratio:>7.3f}  {verdict}")
    return "\n".join(lines)


def run(old_path, new_path, allow_degraded=False, tolerance_scale=1.0):
    """-> (exit_code, text).  The CLI and the dryrun tail both call
    this; the dryrun treats EXIT_REFUSED as a loud skip, never a
    pass."""
    try:
        old_name, old = os.path.basename(old_path), load_record(old_path)
        new_name, new = os.path.basename(new_path), load_record(new_path)
    except RecordError as exc:
        return EXIT_ERROR, str(exc)
    reason = refusal(old, new, allow_degraded=allow_degraded)
    if reason is not None:
        return EXIT_REFUSED, (f"bench_diff REFUSED ({old_name} -> "
                              f"{new_name}): {reason}")
    rows = diff(old, new, tolerance_scale=tolerance_scale)
    if not rows:
        return EXIT_ERROR, ("bench_diff: the records share no known "
                            "numeric metrics")
    text = format_table(rows, old_name, new_name)
    regressions = [r for r in rows if r[4] == "REGRESSION"]
    if regressions:
        names = ", ".join(r[0] for r in regressions)
        return EXIT_REGRESSION, (
            text + f"\nbench_diff: {len(regressions)} REGRESSION(s): "
            f"{names}")
    return EXIT_OK, text + "\nbench_diff: no regressions"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs=2, metavar=("OLD.json", "NEW.json"))
    ap.add_argument("--gate", action="store_true",
                    help="CI intent marker: identical behavior, spelled "
                         "out where a nonzero exit must fail the run")
    ap.add_argument("--allow-degraded", action="store_true",
                    help="permit same-backend degraded-vs-degraded "
                         "comparisons (informational)")
    ap.add_argument("--tolerance-scale", type=float, default=1.0,
                    help="scale every per-metric tolerance (2.0 = twice "
                         "as lenient)")
    args = ap.parse_args(argv)
    code, text = run(*args.paths, allow_degraded=args.allow_degraded,
                     tolerance_scale=args.tolerance_scale)
    print(text, file=sys.stderr if code in (EXIT_REFUSED, EXIT_ERROR)
          else sys.stdout)
    return code


if __name__ == "__main__":
    sys.exit(main())
