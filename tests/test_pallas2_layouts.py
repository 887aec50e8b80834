"""The pallas2 histogram kernel (interpret mode off-TPU) against the xla
scan at the quantized precisions, model bytes equal, under every layout
that hands the kernel a different slice of the table: serial, data shards,
feature shards (the learner pads the columns to 32 x shards for exactly
this case), voting, the 2-D data x feature mesh (at two devices its
feature axis has ONE shard), and the streamed layout.  int32 accumulation
is associative, so equality is exact WITHIN each layout; across layouts
only int8 is sharding-invariant (test_quantized.py
TestDataParallelModelBitwise).

A file of its own: each case traces and compiles two grow programs
(~10 s), and the test runner hands a file to one worker whole."""

import numpy as np
import pytest

import lightgbm_tpu as lgb

LAYOUTS = {"serial": {}, "streamed": {"tpu_stream_mode": "streamed"},
           **{f"{kind}-{m}": {"tree_learner": kind, "num_machines": m}
              for kind in ("data", "feature", "voting", "data_feature")
              for m in (2, 4)}}


@pytest.mark.parametrize("prec", ["int8", "int16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_model_bitwise(layout, prec):
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2048, 10))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.normal(size=2048) > 0).astype(np.float64)
    texts = []
    for impl in ("xla", "pallas2"):
        p = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
             "min_data_in_leaf": 5, "verbosity": -1, "tpu_block_rows": 512,
             "tpu_quant_refit_leaves": False, "tpu_hist_precision": prec,
             "tpu_hist_impl": impl, **LAYOUTS[layout]}
        bst = lgb.train(p, lgb.Dataset(X, label=y, params={"max_bin": 63}),
                        num_boost_round=4)
        texts.append(bst.model_to_string().split("\nparameters:")[0])
    assert texts[0] == texts[1]
