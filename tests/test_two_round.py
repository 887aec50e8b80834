"""two_round streaming load vs the one-pass loader (reference
dataset_loader.cpp:188-216): identical bins when the sample covers the
file; valid training either way when it doesn't."""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import TrainingData


@pytest.fixture(scope="module")
def train_file(binary_example):
    return binary_example["train_file"]


class TestTwoRound:
    def test_identical_when_sample_covers(self, train_file):
        one = TrainingData.from_file(train_file, Config({}))
        two = TrainingData._from_file_two_round(
            train_file, Config({"two_round": True}), None)
        np.testing.assert_array_equal(one.bins, two.bins)
        np.testing.assert_array_equal(one.metadata.label, two.metadata.label)
        assert [m.to_dict() for m in one.mappers] == \
            [m.to_dict() for m in two.mappers]

    def test_multichunk_identical(self, train_file):
        """Chunked streaming must not depend on the chunk size."""
        a = TrainingData._from_file_two_round(
            train_file, Config({"two_round": True}), None, chunk_rows=613)
        b = TrainingData._from_file_two_round(
            train_file, Config({"two_round": True}), None)
        np.testing.assert_array_equal(a.bins, b.bins)

    def test_reservoir_subsample_trains(self, train_file):
        """Sampled bin finding (sample < n) still yields a usable dataset
        and close bin boundaries."""
        full = TrainingData.from_file(train_file, Config({}))
        sub = TrainingData._from_file_two_round(
            train_file, Config({"two_round": True,
                          "bin_construct_sample_cnt": 800}), None,
            chunk_rows=977)
        assert sub.bins.shape == full.bins.shape
        # bins from an 800-row sample differ slightly but the row->bin map
        # must stay monotone per feature; spot-check rank correlation
        col = full.bins[:, 0].astype(np.int64)
        col2 = sub.bins[:, 0].astype(np.int64)
        assert np.corrcoef(col, col2)[0, 1] > 0.98

    def test_dataset_api_two_round(self, train_file):
        import lightgbm_tpu as lgb
        ds = lgb.Dataset(train_file, params={"two_round": True})
        bst = lgb.train({"objective": "binary", "num_leaves": 15},
                        ds, num_boost_round=5, verbose_eval=False)
        assert bst.num_trees() == 5
