"""Shape bucketing: compile-cache policy for the padded (rows, features)
axes (SURVEY §7 "dispatch overhead is the #1 wall-clock risk").

With tpu_shape_buckets=k, at most k distinct padded shapes exist per
power-of-2 octave, so a NEW dataset of similar size maps to the SAME XLA
program and deserializes from the persistent compilation cache in seconds
instead of paying the cold compile.  tpu_shape_buckets=0 restores exact
block-multiple padding (the hardware-validated bench path).
"""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.models.learner import TPUTreeLearner


def _learner(n, f=10, **cfg):
    rng = np.random.default_rng(1)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "max_bin": 32, "num_leaves": 15,
              "tpu_block_rows": 256}
    params.update(cfg)
    config = Config(params)
    return TPUTreeLearner(config, TrainingData.from_matrix(X, y, config))


class TestBucketShapes:
    def test_similar_sizes_share_one_shape(self):
        a = _learner(5000, tpu_shape_buckets=4)
        b = _learner(5150, tpu_shape_buckets=4)
        assert (a.n_pad, a.f_pad, a.g_pad) == (b.n_pad, b.f_pad, b.g_pad)
        # exact mode keeps distinct block-multiple shapes
        a0 = _learner(5000, tpu_shape_buckets=0)
        b0 = _learner(5150, tpu_shape_buckets=0)
        assert a0.n_pad != b0.n_pad
        assert a0.n_pad == 5120 and b0.n_pad == 5376

    def test_waste_is_bounded(self):
        # worst-case pad waste is 2/buckets above the block quantum
        for n in (4097, 9000, 33333, 100001):
            lr = _learner(n, tpu_shape_buckets=16)
            assert lr.n_pad >= n
            assert lr.n_pad <= int(n * (1 + 2.0 / 16)) + 256, \
                (n, lr.n_pad)

    def test_sub_block_rows_bucket_too(self):
        # the common TPU regime: n below the resolved block (8-16k).
        # Rows quantize from the 128-lane tile upward instead of every n
        # being its own program
        a = _learner(5000, tpu_shape_buckets=32, tpu_block_rows=8192)
        b = _learner(5050, tpu_shape_buckets=32, tpu_block_rows=8192)
        assert a.n_pad == b.n_pad == 5120
        # exact mode keeps n as-is in the sub-block regime
        a0 = _learner(5000, tpu_shape_buckets=0, tpu_block_rows=8192)
        assert a0.n_pad == 5000

    def test_feature_axis_buckets(self):
        a = _learner(3000, f=70, tpu_shape_buckets=4)
        b = _learner(3000, f=75, tpu_shape_buckets=4)
        assert a.f_pad == b.f_pad and a.g_pad == b.g_pad

    def test_data_parallel_shards_stay_equal(self):
        lr = _learner(5000, tree_learner="data", num_machines=8,
                      tpu_shape_buckets=4)
        assert lr.n_pad % 8 == 0

    def test_bucketed_training_matches_exact(self):
        """Bucketing only adds masked padding rows/trivial features —
        the grown model must be identical."""
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(3)
        X = rng.normal(size=(5000, 10))
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
        out = []
        for buckets in (0, 4):
            p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "tpu_block_rows": 256, "tpu_shape_buckets": buckets}
            ds = lgb.Dataset(X, label=y, params=p)
            s = lgb.train(p, ds, num_boost_round=5).model_to_string()
            out.append(s.split("\nparameters:")[0])  # trees + headers only
        assert out[0] == out[1]


_CACHE_WORKER = """
import os, sys, time
root = {root!r}
sys.path.insert(0, root)
import numpy as np
import lightgbm_tpu as lgb

n = int(sys.argv[1])
rng = np.random.default_rng(0)
X = rng.normal(size=(n, 10))
y = (X[:, 0] > 0).astype(np.float64)
p = {{"objective": "binary", "num_leaves": 31, "verbosity": -1,
     "tpu_block_rows": 256, "tpu_shape_buckets": 4}}
ds = lgb.Dataset(X, label=y, params=p)
from lightgbm_tpu.booster import Booster
bst = Booster(params=p, train_set=ds)
t0 = time.time()
bst.update()
np.asarray(bst._driver.train_scores.scores)  # sync
print(f"FIRST_ITER_S={{time.time() - t0:.2f}}", flush=True)
"""


@pytest.mark.slow
class TestPersistentCacheReuse:
    def test_second_similar_dataset_hits_cache(self, tmp_path):
        """A fresh process training a DIFFERENT dataset of similar size
        must reuse the cached grower program: no new cache entries, and
        the first iteration (compile included) runs in a fraction of the
        cold time."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cache = tmp_path / "fake_jax_cache"
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
        env.pop("XLA_FLAGS", None)

        def run(n):
            t = time.time()
            r = subprocess.run([sys.executable, "-c",
                                _CACHE_WORKER.format(root=root), str(n)],
                               env=env, capture_output=True, text=True,
                               timeout=900)
            assert r.returncode == 0, r.stdout + r.stderr
            first = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("FIRST_ITER_S=")][0]
            return float(first.split("=")[1]), time.time() - t

        cold_first, _ = run(5000)
        entries_after_a = sorted(os.listdir(cache))
        assert entries_after_a, "cold run persisted no cache entries"
        warm_first, _ = run(5150)   # different n, same bucket
        entries_after_b = sorted(os.listdir(cache))
        assert entries_after_b == entries_after_a, \
            "similar-size dataset compiled NEW programs"
        assert warm_first < max(0.6 * cold_first, 2.0), \
            f"warm {warm_first:.1f}s vs cold {cold_first:.1f}s"
