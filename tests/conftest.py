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


@pytest.fixture(scope="session")
def binary_example():
    """Load the reference binary_classification example data."""
    path = os.path.join(REFERENCE_DIR, "examples", "binary_classification")
    train = np.loadtxt(os.path.join(path, "binary.train"))
    test = np.loadtxt(os.path.join(path, "binary.test"))
    return {
        "X_train": train[:, 1:], "y_train": train[:, 0],
        "X_test": test[:, 1:], "y_test": test[:, 0],
        "train_file": os.path.join(path, "binary.train"),
        "test_file": os.path.join(path, "binary.test"),
    }


@pytest.fixture(scope="session")
def rank_example():
    # rank.train/.test are LibSVM-format: parse via the framework loader
    from lightgbm_tpu.io.parser import load_text_file
    path = os.path.join(REFERENCE_DIR, "examples", "lambdarank")
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
def regression_example():
    path = os.path.join(REFERENCE_DIR, "examples", "regression")
    train = np.loadtxt(os.path.join(path, "regression.train"))
    test = np.loadtxt(os.path.join(path, "regression.test"))
    return {
        "X_train": train[:, 1:], "y_train": train[:, 0],
        "X_test": test[:, 1:], "y_test": test[:, 0],
        "train_file": os.path.join(path, "regression.train"),
    }


@pytest.fixture(scope="session")
def multiclass_example():
    path = os.path.join(REFERENCE_DIR, "examples", "multiclass_classification")
    train = np.loadtxt(os.path.join(path, "multiclass.train"))
    test = np.loadtxt(os.path.join(path, "multiclass.test"))
    return {
        "X_train": train[:, 1:], "y_train": train[:, 0],
        "X_test": test[:, 1:], "y_test": test[:, 0],
        "train_file": os.path.join(path, "multiclass.train"),
    }
