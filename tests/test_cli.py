"""CLI application tests (reference tests/cpp_test: run the CLI on the
shipped example configs)."""

import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute e2e trainings

from .conftest import REFERENCE_DIR

BINARY_DIR = os.path.join(REFERENCE_DIR, "examples", "binary_classification")


def run_cli_module(args, cwd):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-m", "lightgbm_tpu"] + args,
                         cwd=cwd, env=env, capture_output=True, text=True,
                         timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"CLI failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


class TestCLI:
    def test_train_predict_cycle(self, tmp_path):
        model = str(tmp_path / "model.txt")
        stdout = run_cli_module([
            "task=train", f"data={BINARY_DIR}/binary.train",
            "objective=binary", "num_trees=10", "num_leaves=15",
            "metric=binary_logloss,auc", "is_training_metric=true",
            f"output_model={model}", "verbosity=1"], str(tmp_path))
        assert os.path.exists(model)
        assert "finished training" in stdout

        result = str(tmp_path / "preds.txt")
        run_cli_module([
            "task=predict", f"data={BINARY_DIR}/binary.test",
            f"input_model={model}", f"output_result={result}"],
            str(tmp_path))
        preds = np.loadtxt(result)
        labels = np.loadtxt(f"{BINARY_DIR}/binary.test")[:, 0]
        assert preds.shape == labels.shape
        assert 0.0 <= preds.min() and preds.max() <= 1.0
        auc_acc = ((preds > 0.5) == labels).mean()
        assert auc_acc > 0.7

    def test_train_conf_file(self, tmp_path):
        conf = tmp_path / "train.conf"
        model = tmp_path / "model.txt"
        conf.write_text(
            f"task = train\n"
            f"objective = binary\n"
            f"data = {BINARY_DIR}/binary.train\n"
            f"num_trees = 5\n"
            f"num_leaves = 7\n"
            f"output_model = {model}\n")
        stdout = run_cli_module([f"config={conf}"], str(tmp_path))
        assert os.path.exists(str(model))

    def test_cli_overrides_conf(self, tmp_path):
        conf = tmp_path / "train.conf"
        model = tmp_path / "model.txt"
        conf.write_text(
            f"task = train\n"
            f"objective = binary\n"
            f"data = {BINARY_DIR}/binary.train\n"
            f"num_trees = 50\n"
            f"output_model = {model}\n")
        run_cli_module([f"config={conf}", "num_trees=3", "num_leaves=7"],
                       str(tmp_path))
        text = open(str(model)).read()
        assert text.count("Tree=") == 3
