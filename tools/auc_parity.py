"""Full-scale AUC parity: ours vs the compiled reference on IDENTICAL data.

The north-star metric has two halves — speed (bench.py) and QUALITY: the
reference's published Higgs AUC is 0.845154 CPU / 0.845209-0.845239 GPU
(reference docs/Experiments.rst:127, docs/GPU-Performance.rst:139).  The
real Higgs cannot be fetched here (no egress), so this tool trains BOTH
frameworks on the same materialized dataset file (real data via
--data/LIGHTGBM_TPU_BENCH_DATA when available, else the bench's seeded
Higgs-shaped synthetic) and reports a GPU-Performance.rst-style table.

Usage:
    python tools/auc_parity.py [--rows 1000000] [--trees 500]
        [--leaves 255] [--data FILE] [--skip-ref] [--out docs/AUC_PARITY.md]

The reference runs through `.refbuild/lightgbm` with is_training_metric;
ours runs through the Python API on the identical matrix.  Both report the
final TRAIN AUC (the published Higgs experiments use train AUC, see
Experiments.rst "AUC on the training set").
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ORACLE = os.path.join(ROOT, ".refbuild", "lightgbm")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--trees", type=int, default=500)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--max-bin", type=int, default=63)
    ap.add_argument("--data", default=os.environ.get(
        "LIGHTGBM_TPU_BENCH_DATA", ""))
    ap.add_argument("--skip-ref", action="store_true",
                    help="skip the reference run (ours-only JSON; no "
                         "parity table is written)")
    ap.add_argument("--out", default=os.path.join(ROOT, "docs",
                                                  "AUC_PARITY.md"))
    ap.add_argument("--workdir", default="/tmp/auc_parity")
    args = ap.parse_args()

    os.makedirs(args.workdir, exist_ok=True)
    from bench import make_data  # bench's data rules (real-file override)

    if args.data:
        if not os.path.exists(args.data):
            raise FileNotFoundError(f"--data {args.data!r} does not exist")
        os.environ["LIGHTGBM_TPU_BENCH_DATA"] = args.data
    X, y = make_data(args.rows, 28)
    src = args.data if args.data else f"synthetic(seed=42, n={args.rows})"

    # cache key includes the SOURCE so switching --data never reuses a
    # stale file; both frameworks then train from the same tsv (full
    # %.17g round-trip precision) so "identical data" is literal
    import hashlib

    tag = hashlib.sha1(src.encode()).hexdigest()[:10]
    data_file = os.path.join(args.workdir, f"train_{args.rows}_{tag}.tsv")
    if not os.path.exists(data_file):
        np.savetxt(data_file, np.column_stack([y, X]), delimiter="\t",
                   fmt="%.17g")
    del X, y
    raw = np.loadtxt(data_file, ndmin=2)
    y, X = raw[:, 0], np.ascontiguousarray(raw[:, 1:])
    del raw

    results = {}

    # ---- reference CLI -------------------------------------------------
    if not args.skip_ref:
        t0 = time.time()
        out = subprocess.run(
            [ORACLE, "task=train", f"data={data_file}", "objective=binary",
             f"num_trees={args.trees}", f"num_leaves={args.leaves}",
             "learning_rate=0.1", "min_data_in_leaf=20",
             f"max_bin={args.max_bin}", "metric=auc",
             "is_training_metric=true", "verbosity=2",
             f"output_model={args.workdir}/ref_model.txt"],
            capture_output=True, text=True, cwd=args.workdir,
            timeout=4 * 3600)
        ref_s = time.time() - t0
        assert out.returncode == 0, out.stderr[-800:]
        aucs = [float(ln.rsplit(":", 1)[1]) for ln in out.stdout.splitlines()
                if "auc" in ln and ":" in ln]
        results["ref"] = {"auc": aucs[-1], "seconds": round(ref_s, 1)}

    # ---- ours ----------------------------------------------------------
    import lightgbm_tpu as lgb

    t0 = time.time()
    ds = lgb.Dataset(X, label=y, params={"max_bin": args.max_bin})
    res = {}
    lgb.train({"objective": "binary", "num_leaves": args.leaves,
               "learning_rate": 0.1, "min_data_in_leaf": 20,
               "max_bin": args.max_bin, "metric": "auc",
               "verbosity": -1},
              ds, num_boost_round=args.trees, valid_sets=[ds],
              valid_names=["training"], verbose_eval=False,
              evals_result=res)
    our_s = time.time() - t0
    import jax

    results["ours"] = {"auc": float(res["training"]["auc"][-1]),
                       "seconds": round(our_s, 1),
                       "platform": jax.devices()[0].platform}

    line = {"tool": "auc_parity", "rows": args.rows, "trees": args.trees,
            "leaves": args.leaves, "data": src, **{
                f"{k}_{kk}": vv for k, v in results.items()
                for kk, vv in v.items()}}
    print(json.dumps(line))

    if "ref" in results:
        with open(args.out, "w") as f:
            f.write(
                "# AUC parity on identical data\n\n"
                "Style of reference docs/GPU-Performance.rst:139 "
                "(0.845209 vs 0.845239 on real Higgs).\n\n"
                f"Data: `{src}`  rows={args.rows}  trees={args.trees}  "
                f"leaves={args.leaves}  max_bin={args.max_bin}\n\n"
                "| framework | final train AUC | wall s |\n"
                "|---|---|---|\n"
                f"| reference CPU (.refbuild) | "
                f"{results['ref']['auc']:.6f} | "
                f"{results['ref']['seconds']} |\n"
                f"| lightgbm_tpu ({results['ours']['platform']}) | "
                f"{results['ours']['auc']:.6f} | "
                f"{results['ours']['seconds']} |\n")
            d = abs(results["ref"]["auc"] - results["ours"]["auc"])
            f.write(f"\nDelta: {d:.6f} "
                    f"(reference GPU-parity band is ~0.0001-0.001)\n")
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
