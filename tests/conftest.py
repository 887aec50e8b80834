"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is unavailable in CI; sharding correctness is
validated on `--xla_force_host_platform_device_count=8` CPU devices instead
(the driver separately dry-run-compiles the multi-chip path via
`__graft_entry__.dryrun_multichip`).  Must run before the first jax import.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests never touch the TPU: hold jax to the cpu backend with 8 virtual
# devices (for the sharding tests) before the first jax import.  Assigned,
# not defaulted: a test run must not depend on the caller's environment.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if "xla_force_host_platform_device_count" not in f]
    + ["--xla_force_host_platform_device_count=8"])

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _blackbox_dumps_stay_out_of_the_repo(tmp_path_factory):
    """Crash-path tests (OOM exhaustion, collective chaos) dump a
    blackbox to the configured dir > $LIGHTGBM_TPU_BLACKBOX_DIR > cwd;
    cwd is the repo root under pytest, which is exactly how the stale
    `blackbox-host0.json` kept regrowing at the root (ISSUEs 16/18).
    Default the env fallback to a session temp dir so no test can
    strand a dump in the checkout; tests that assert on dump placement
    still override via monkeypatch.setenv / fr.configure(dump_dir=...)."""
    os.environ.setdefault(
        "LIGHTGBM_TPU_BLACKBOX_DIR",
        str(tmp_path_factory.mktemp("blackbox")))
    yield


REFERENCE_DIR = "/root/reference"
ORACLE_BIN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          ".refbuild", "lightgbm")
ORACLE_LIB = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          ".refbuild", "lib_lightgbm.so")


def has_oracle() -> bool:
    return os.path.exists(ORACLE_BIN) and os.path.exists(ORACLE_LIB)


def _write_seeded_examples(root: str) -> None:
    """The four example directories the loaders below read, made from a
    fixed seed in the reference files' formats and row counts
    (label first, tab-separated; LibSVM with `.query` side-cars for the
    ranking example): 28 / 28 / 28 / 300 columns, 7000 + 500 rows
    (3005 + 768 in 201 + 50 queries for ranking).  Learnable, so the
    quality floors of the tests that train on them mean something."""
    rng = np.random.default_rng(20260929)

    def table(name, n, label_of):
        X = rng.normal(size=(n, 28))
        X[:, 21:] = np.abs(X[:, 21:])  # the Higgs table's positive masses
        y = label_of(X, rng.normal(size=n))
        np.savetxt(os.path.join(root, "examples", name), np.column_stack(
            [y, X]), delimiter="\t", fmt="%.6g")

    for d in ("binary_classification", "regression",
              "multiclass_classification", "lambdarank"):
        os.makedirs(os.path.join(root, "examples", d))

    def binary(X, e):
        return (X[:, 0] + X[:, 1] * X[:, 2] - X[:, 21] + 1.0 + e > 0) * 1.0

    for split, n in (("train", 7000), ("test", 500)):
        name = f"binary_classification/binary.{split}"
        table(name, n, binary)
        # the reference example ships per-row weights beside both files
        np.savetxt(os.path.join(root, "examples", name + ".weight"),
                   rng.uniform(0.5, 1.5, size=n), fmt="%.6g")
        table(f"regression/regression.{split}", n,
              lambda X, e: X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * e)
        table(f"multiclass_classification/multiclass.{split}", n,
              lambda X, e: np.argmax(X[:, :5] + 0.5 * e[:, None], axis=1)
              * 1.0)
    w = rng.normal(size=300) * (rng.random(300) < 0.1)
    for split, rows, queries in (("train", 3005, 201), ("test", 768, 50)):
        X = rng.normal(size=(rows, 300)) * (rng.random((rows, 300)) < 0.15)
        rel = np.clip(np.round(X @ w + 1.5 + 0.5 * rng.normal(size=rows)),
                      0, 4).astype(int)
        path = os.path.join(root, "examples", "lambdarank", f"rank.{split}")
        with open(path, "w") as f:
            for r, row in zip(rel, X):
                nz = np.flatnonzero(row)
                f.write(" ".join([str(r)] + [f"{k}:{row[k]:.5g}"
                                             for k in nz]) + "\n")
        sizes = np.full(queries, rows // queries)
        sizes[:rows - sizes.sum()] += 1
        np.savetxt(path + ".query", sizes, fmt="%d")


@pytest.fixture(scope="session")
def reference_dir(tmp_path_factory):
    """`REFERENCE_DIR` where this installation has the reference checkout,
    else a session temp directory holding seeded stand-ins for its
    example files (`_write_seeded_examples`)."""
    if os.path.isdir(os.path.join(REFERENCE_DIR, "examples")):
        return REFERENCE_DIR
    root = str(tmp_path_factory.mktemp("reference"))
    _write_seeded_examples(root)
    return root


@pytest.fixture(scope="session")
def binary_example(reference_dir):
    """Load the binary_classification example data."""
    path = os.path.join(reference_dir, "examples", "binary_classification")
    train = np.loadtxt(os.path.join(path, "binary.train"))
    test = np.loadtxt(os.path.join(path, "binary.test"))
    return {
        "X_train": train[:, 1:], "y_train": train[:, 0],
        "X_test": test[:, 1:], "y_test": test[:, 0],
        "train_file": os.path.join(path, "binary.train"),
        "test_file": os.path.join(path, "binary.test"),
    }


@pytest.fixture(scope="session")
def rank_example(reference_dir):
    # rank.train/.test are LibSVM-format: parse via the framework loader
    from lightgbm_tpu.io.parser import load_text_file
    path = os.path.join(reference_dir, "examples", "lambdarank")
    Xtr, ytr, _, _, _, _ = load_text_file(os.path.join(path, "rank.train"))
    Xte, yte, _, _, _, _ = load_text_file(
        os.path.join(path, "rank.test"), num_features_hint=Xtr.shape[1])
    qtrain = np.loadtxt(os.path.join(path, "rank.train.query")).astype(np.int64)
    qtest = np.loadtxt(os.path.join(path, "rank.test.query")).astype(np.int64)
    return {
        "X_train": Xtr, "y_train": ytr, "q_train": qtrain,
        "X_test": Xte[:, :Xtr.shape[1]], "y_test": yte, "q_test": qtest,
        "train_file": os.path.join(path, "rank.train"),
    }


@pytest.fixture(scope="session")
def regression_example(reference_dir):
    path = os.path.join(reference_dir, "examples", "regression")
    train = np.loadtxt(os.path.join(path, "regression.train"))
    test = np.loadtxt(os.path.join(path, "regression.test"))
    return {
        "X_train": train[:, 1:], "y_train": train[:, 0],
        "X_test": test[:, 1:], "y_test": test[:, 0],
        "train_file": os.path.join(path, "regression.train"),
    }


@pytest.fixture(scope="session")
def multiclass_example(reference_dir):
    path = os.path.join(reference_dir, "examples", "multiclass_classification")
    train = np.loadtxt(os.path.join(path, "multiclass.train"))
    test = np.loadtxt(os.path.join(path, "multiclass.test"))
    return {
        "X_train": train[:, 1:], "y_train": train[:, 0],
        "X_test": test[:, 1:], "y_test": test[:, 0],
        "train_file": os.path.join(path, "multiclass.train"),
    }
