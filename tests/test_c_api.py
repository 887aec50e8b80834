"""C API (ABI) tests: load lib_lightgbm_tpu.so via ctypes and exercise the
LGBM_* surface end to end, the analog of reference tests/c_api_test/
test_.py:12-46 (which loads lib_lightgbm.so directly and drives dataset
creation + booster train/predict at the ABI level)."""

import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB_PATH = os.path.join(ROOT, "build", "lib_lightgbm_tpu.so")

C_API_DTYPE_FLOAT32 = 0
C_API_DTYPE_FLOAT64 = 1
C_API_DTYPE_INT32 = 2
C_API_PREDICT_NORMAL = 0
C_API_PREDICT_RAW_SCORE = 1


@pytest.fixture(scope="module")
def lib():
    if not os.path.exists(LIB_PATH):
        os.makedirs(os.path.dirname(LIB_PATH), exist_ok=True)
        build = subprocess.run(
            [os.path.join(ROOT, "src", "capi", "build.sh"),
             os.path.dirname(LIB_PATH)],
            capture_output=True, text=True)
        if build.returncode != 0:
            pytest.skip(f"C API build failed: {build.stderr[-500:]}")
    os.environ["LIGHTGBM_TPU_PYROOT"] = ROOT
    L = ctypes.CDLL(LIB_PATH)
    L.LGBM_GetLastError.restype = ctypes.c_char_p
    return L


def _check(lib, ret):
    if ret != 0:
        raise RuntimeError(lib.LGBM_GetLastError().decode())


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    n, f = 1200, 6
    X = rng.normal(size=(n, f)).astype(np.float64)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.4).astype(np.float32)
    return X, y


class TestCAPIDataset:
    def test_create_from_mat_and_fields(self, lib, data):
        X, y = data
        h = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromMat(
            X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(X.shape[0]), ctypes.c_int32(X.shape[1]),
            ctypes.c_int(1), b"max_bin=63", None, ctypes.byref(h)))
        assert h.value

        nd = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(h, ctypes.byref(nd)))
        assert nd.value == X.shape[0]
        nf = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumFeature(h, ctypes.byref(nf)))
        assert nf.value == X.shape[1]

        _check(lib, lib.LGBM_DatasetSetField(
            h, b"label", y.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int(len(y)), C_API_DTYPE_FLOAT32))

        out_len = ctypes.c_int()
        out_ptr = ctypes.c_void_p()
        out_type = ctypes.c_int()
        _check(lib, lib.LGBM_DatasetGetField(
            h, b"label", ctypes.byref(out_len), ctypes.byref(out_ptr),
            ctypes.byref(out_type)))
        assert out_len.value == len(y)
        assert out_type.value == C_API_DTYPE_FLOAT32
        got = np.ctypeslib.as_array(
            ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_float)),
            shape=(out_len.value,))
        np.testing.assert_allclose(got, y)
        _check(lib, lib.LGBM_DatasetFree(h))

    def test_create_from_file(self, lib, binary_example):
        path = binary_example["train_file"]
        h = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromFile(
            path.encode(), b"max_bin=255", None, ctypes.byref(h)))
        nd = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(h, ctypes.byref(nd)))
        assert nd.value == 7000
        _check(lib, lib.LGBM_DatasetFree(h))

    def test_error_reporting(self, lib):
        h = ctypes.c_void_p()
        ret = lib.LGBM_DatasetCreateFromFile(
            b"/nonexistent/file.csv", b"", None, ctypes.byref(h))
        assert ret == -1
        assert len(lib.LGBM_GetLastError()) > 0


class TestCAPIBooster:
    def test_train_eval_predict_cycle(self, lib, data, tmp_path):
        X, y = data
        dh = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromMat(
            X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(X.shape[0]), ctypes.c_int32(X.shape[1]),
            ctypes.c_int(1), b"max_bin=63", None, ctypes.byref(dh)))
        _check(lib, lib.LGBM_DatasetSetField(
            dh, b"label", y.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int(len(y)), C_API_DTYPE_FLOAT32))

        bh = ctypes.c_void_p()
        _check(lib, lib.LGBM_BoosterCreate(
            dh, b"objective=binary metric=binary_logloss num_leaves=15 "
                b"min_data_in_leaf=10 learning_rate=0.2",
            ctypes.byref(bh)))

        ncls = ctypes.c_int()
        _check(lib, lib.LGBM_BoosterGetNumClasses(bh, ctypes.byref(ncls)))
        assert ncls.value == 1

        fin = ctypes.c_int()
        for _ in range(20):
            _check(lib, lib.LGBM_BoosterUpdateOneIter(bh, ctypes.byref(fin)))
        it = ctypes.c_int()
        _check(lib, lib.LGBM_BoosterGetCurrentIteration(bh, ctypes.byref(it)))
        assert it.value == 20

        cnt = ctypes.c_int()
        _check(lib, lib.LGBM_BoosterGetEvalCounts(bh, ctypes.byref(cnt)))
        assert cnt.value == 1
        res = (ctypes.c_double * cnt.value)()
        out_len = ctypes.c_int()
        _check(lib, lib.LGBM_BoosterGetEval(bh, 0, ctypes.byref(out_len), res))
        assert out_len.value == 1
        assert 0.0 < res[0] < 0.6  # training logloss after 20 iters

        n = X.shape[0]
        pred = (ctypes.c_double * n)()
        plen = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh, X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(n), ctypes.c_int32(X.shape[1]), ctypes.c_int(1),
            C_API_PREDICT_NORMAL, ctypes.c_int(0), b"",
            ctypes.byref(plen), pred))
        assert plen.value == n
        p = np.ctypeslib.as_array(pred)
        assert ((p > 0.5) == (y > 0.5)).mean() > 0.85

        model_file = str(tmp_path / "capi_model.txt").encode()
        _check(lib, lib.LGBM_BoosterSaveModel(bh, 0, model_file))
        assert os.path.exists(model_file.decode())

        # round-trip through the model file
        bh2 = ctypes.c_void_p()
        iters = ctypes.c_int()
        _check(lib, lib.LGBM_BoosterCreateFromModelfile(
            model_file, ctypes.byref(iters), ctypes.byref(bh2)))
        assert iters.value == 20
        pred2 = (ctypes.c_double * n)()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh2, X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(n), ctypes.c_int32(X.shape[1]), ctypes.c_int(1),
            C_API_PREDICT_NORMAL, ctypes.c_int(0), b"",
            ctypes.byref(plen), pred2))
        np.testing.assert_allclose(np.ctypeslib.as_array(pred2), p,
                                   rtol=1e-6)

        _check(lib, lib.LGBM_BoosterFree(bh))
        _check(lib, lib.LGBM_BoosterFree(bh2))
        _check(lib, lib.LGBM_DatasetFree(dh))

    def test_custom_objective_update(self, lib, data):
        X, y = data
        dh = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromMat(
            X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(X.shape[0]), ctypes.c_int32(X.shape[1]),
            ctypes.c_int(1), b"max_bin=63", None, ctypes.byref(dh)))
        _check(lib, lib.LGBM_DatasetSetField(
            dh, b"label", y.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int(len(y)), C_API_DTYPE_FLOAT32))
        bh = ctypes.c_void_p()
        _check(lib, lib.LGBM_BoosterCreate(
            dh, b"objective=none num_leaves=15 min_data_in_leaf=10",
            ctypes.byref(bh)))
        n = X.shape[0]
        score = np.zeros(n, np.float64)
        fin = ctypes.c_int()
        for _ in range(5):
            p = 1.0 / (1.0 + np.exp(-score))
            grad = (p - y).astype(np.float32)
            hess = (p * (1 - p)).astype(np.float32)
            _check(lib, lib.LGBM_BoosterUpdateOneIterCustom(
                bh, grad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                hess.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                ctypes.byref(fin)))
            pred = (ctypes.c_double * n)()
            plen = ctypes.c_int64()
            _check(lib, lib.LGBM_BoosterPredictForMat(
                bh, X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
                ctypes.c_int32(n), ctypes.c_int32(X.shape[1]),
                ctypes.c_int(1), C_API_PREDICT_RAW_SCORE, ctypes.c_int(0),
                b"", ctypes.byref(plen), pred))
            score = np.ctypeslib.as_array(pred).copy()
        acc = ((1 / (1 + np.exp(-score)) > 0.5) == (y > 0.5)).mean()
        assert acc > 0.8

    def test_network_init(self, lib):
        _check(lib, lib.LGBM_NetworkInit(b"127.0.0.1:12400", 12400, 120, 1))
        _check(lib, lib.LGBM_NetworkFree())
        # single-machine injected collectives are a no-op success
        assert lib.LGBM_NetworkInitWithFunctions(1, 0, None, None) == 0
        # real multi-machine injection must fail loudly
        assert lib.LGBM_NetworkInitWithFunctions(4, 0, None, None) == -1


class TestCAPIDatasetBinary:
    def test_save_binary(self, lib, data, tmp_path):
        X, y = data
        h = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromMat(
            X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(X.shape[0]), ctypes.c_int32(X.shape[1]),
            ctypes.c_int(1), b"max_bin=63", None, ctypes.byref(h)))
        out = str(tmp_path / "ds.bin").encode()
        _check(lib, lib.LGBM_DatasetSaveBinary(h, out))
        assert os.path.exists(out.decode())
        from lightgbm_tpu.io.dataset import TrainingData
        td = TrainingData.from_binary(out.decode())
        assert td.num_data == X.shape[0]
        _check(lib, lib.LGBM_DatasetFree(h))


class TestCAPIBreadth:
    """Round-3 additions: booster mutation, file predict, dataset subset
    and feature names (reference c_api.h:286-470,644-720,905-960)."""

    def _make_booster(self, lib, data, rounds=5):
        X, y = data
        dh = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromMat(
            X.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(X.shape[0]), ctypes.c_int32(X.shape[1]),
            ctypes.c_int32(1), b"max_bin=32", None, ctypes.byref(dh)))
        _check(lib, lib.LGBM_DatasetSetField(
            dh, b"label", y.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(len(y)), C_API_DTYPE_FLOAT32))
        bh = ctypes.c_void_p()
        _check(lib, lib.LGBM_BoosterCreate(
            dh, b"objective=binary num_leaves=7 min_data_in_leaf=5",
            ctypes.byref(bh)))
        fin = ctypes.c_int32()
        for _ in range(rounds):
            _check(lib, lib.LGBM_BoosterUpdateOneIter(bh, ctypes.byref(fin)))
        return dh, bh

    def test_leaf_value_get_set(self, lib, data):
        _, bh = self._make_booster(lib, data)
        val = ctypes.c_double()
        _check(lib, lib.LGBM_BoosterGetLeafValue(bh, 0, 0,
                                                 ctypes.byref(val)))
        _check(lib, lib.LGBM_BoosterSetLeafValue(bh, 0, 0,
                                                 ctypes.c_double(1.25)))
        val2 = ctypes.c_double()
        _check(lib, lib.LGBM_BoosterGetLeafValue(bh, 0, 0,
                                                 ctypes.byref(val2)))
        assert val2.value == 1.25 and val2.value != val.value

    def test_merge_and_shuffle(self, lib, data):
        _, bh1 = self._make_booster(lib, data, rounds=3)
        _, bh2 = self._make_booster(lib, data, rounds=2)
        n1, n2 = ctypes.c_int32(), ctypes.c_int32()
        _check(lib, lib.LGBM_BoosterNumberOfTotalModel(
            bh1, ctypes.byref(n1)))
        _check(lib, lib.LGBM_BoosterNumberOfTotalModel(
            bh2, ctypes.byref(n2)))
        _check(lib, lib.LGBM_BoosterMerge(bh1, bh2))
        n3 = ctypes.c_int32()
        _check(lib, lib.LGBM_BoosterNumberOfTotalModel(
            bh1, ctypes.byref(n3)))
        assert n3.value == n1.value + n2.value
        _check(lib, lib.LGBM_BoosterShuffleModels(bh1, 0, -1))

    def test_reset_parameter(self, lib, data):
        _, bh = self._make_booster(lib, data)
        _check(lib, lib.LGBM_BoosterResetParameter(
            bh, b"learning_rate=0.05"))

    def test_predict_for_file(self, lib, data, tmp_path):
        X, y = data
        _, bh = self._make_booster(lib, data)
        src = tmp_path / "pred_in.tsv"
        np.savetxt(src, np.column_stack([y, X]), delimiter="\t")
        out = tmp_path / "pred_out.txt"
        _check(lib, lib.LGBM_BoosterPredictForFile(
            bh, str(src).encode(), 0, C_API_PREDICT_NORMAL, -1, b"",
            str(out).encode()))
        got = np.loadtxt(out)
        assert got.shape == (len(y),)
        assert 0.0 <= got.min() and got.max() <= 1.0

    def test_feature_names_roundtrip(self, lib, data):
        dh, _ = self._make_booster(lib, data)
        names = [b"alpha", b"beta", b"gamma", b"delta", b"eps", b"zeta"]
        arr = (ctypes.c_char_p * len(names))(*names)
        _check(lib, lib.LGBM_DatasetSetFeatureNames(
            dh, arr, ctypes.c_int32(len(names))))
        bufs = [ctypes.create_string_buffer(64) for _ in names]
        ptrs = (ctypes.c_char_p * len(names))(
            *[ctypes.cast(b, ctypes.c_char_p) for b in bufs])
        cnt = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetFeatureNames(
            dh, ptrs, ctypes.byref(cnt)))
        assert cnt.value == len(names)
        assert [b.value for b in bufs] == names

    def test_dataset_subset(self, lib, data):
        dh, _ = self._make_booster(lib, data)
        idx = np.arange(0, 600, 2, dtype=np.int32)
        sub = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetGetSubset(
            dh, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int32(len(idx)), b"", ctypes.byref(sub)))
        n = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(sub, ctypes.byref(n)))
        assert n.value == len(idx)


class TestCAPIBreadth2:
    """Second breadth batch: single-row / CSR predict, multi-mat dataset,
    booster introspection, SetLastError."""

    def test_set_last_error(self, lib):
        lib.LGBM_SetLastError(b"custom message")
        assert lib.LGBM_GetLastError() == b"custom message"

    def test_num_model_per_iteration_and_names(self, lib, data):
        helper = TestCAPIBreadth()
        _, bh = helper._make_booster(lib, data)
        k = ctypes.c_int32()
        _check(lib, lib.LGBM_BoosterNumModelPerIteration(bh, ctypes.byref(k)))
        assert k.value == 1
        bufs = [ctypes.create_string_buffer(64) for _ in range(6)]
        ptrs = (ctypes.c_char_p * 6)(
            *[ctypes.cast(b, ctypes.c_char_p) for b in bufs])
        cnt = ctypes.c_int32()
        # NOTE reference v2.3.2 order: (handle, out_len, out_strs)
        _check(lib, lib.LGBM_BoosterGetFeatureNames(bh, ctypes.byref(cnt),
                                                    ptrs))
        assert cnt.value == 6
        assert bufs[0].value == b"Column_0"

    def test_predict_single_row_and_csr(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        _, bh = helper._make_booster(lib, data)
        # dense single row
        row = np.ascontiguousarray(X[0])
        out_len = ctypes.c_int64()
        out = np.zeros(1, np.float64)
        _check(lib, lib.LGBM_BoosterPredictForMatSingleRow(
            bh, row.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1),
            C_API_PREDICT_NORMAL, -1, b"", ctypes.byref(out_len),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        assert out_len.value == 1
        # CSR of the first 5 rows must reproduce dense predictions
        import scipy.sparse as sp
        Xs = sp.csr_matrix(X[:5])
        out5 = np.zeros(5, np.float64)
        len5 = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForCSR(
            bh, Xs.indptr.astype(np.int32).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_INT32),
            Xs.indices.astype(np.int32).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)),
            Xs.data.astype(np.float64).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_FLOAT64),
            ctypes.c_int64(len(Xs.indptr)), ctypes.c_int64(Xs.nnz),
            ctypes.c_int64(X.shape[1]), C_API_PREDICT_NORMAL, -1, b"",
            ctypes.byref(len5),
            out5.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        assert len5.value == 5
        dense_out = np.zeros(5, np.float64)
        dl = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh, np.ascontiguousarray(X[:5]).ctypes.data_as(ctypes.c_void_p),
            C_API_DTYPE_FLOAT64, ctypes.c_int32(5),
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1),
            C_API_PREDICT_NORMAL, -1, b"", ctypes.byref(dl),
            dense_out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        np.testing.assert_allclose(out5, dense_out, rtol=1e-12)

    def test_dataset_from_mats(self, lib, data):
        X, y = data
        a = np.ascontiguousarray(X[:400])
        b = np.ascontiguousarray(X[400:])
        ptrs = (ctypes.c_void_p * 2)(a.ctypes.data_as(ctypes.c_void_p),
                                     b.ctypes.data_as(ctypes.c_void_p))
        nrows = np.asarray([len(a), len(b)], np.int32)
        dh = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromMats(
            ctypes.c_int32(2), ptrs, C_API_DTYPE_FLOAT64,
            nrows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1), b"max_bin=32",
            None, ctypes.byref(dh)))
        n = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(dh, ctypes.byref(n)))
        assert n.value == len(X)


class TestCAPIBreadth3:
    """Third batch: maintained-score retrieval, param updates, streaming
    row push, text dump."""

    def test_get_predict_matches_scores(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        dh, bh = helper._make_booster(lib, data)
        n_len = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterGetNumPredict(bh, 0,
                                                  ctypes.byref(n_len)))
        assert n_len.value == len(y)
        out = np.zeros(len(y), np.float64)
        got = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterGetPredict(
            bh, 0, ctypes.byref(got),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        assert got.value == len(y)
        # maintained train scores == raw predictions on training data
        pred = np.zeros(len(y), np.float64)
        pl = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh, np.ascontiguousarray(X).ctypes.data_as(ctypes.c_void_p),
            C_API_DTYPE_FLOAT64, ctypes.c_int32(len(y)),
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1),
            C_API_PREDICT_NORMAL, -1, b"", ctypes.byref(pl),
            pred.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        # GetPredict applies ConvertOutput (sigmoid here), like the
        # reference GBDT::GetPredictAt
        np.testing.assert_allclose(out, pred, rtol=1e-5, atol=1e-5)

    def test_update_param_guards_frozen_keys(self, lib, data):
        helper = TestCAPIBreadth()
        dh, _ = helper._make_booster(lib, data)
        _check(lib, lib.LGBM_DatasetUpdateParam(dh, b"learning_rate=0.2"))
        assert lib.LGBM_DatasetUpdateParam(dh, b"max_bin=64") != 0
        assert b"max_bin" in lib.LGBM_GetLastError()

    def test_push_rows_roundtrip(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        ref_dh, _ = helper._make_booster(lib, data)
        out = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateByReference(
            ref_dh, ctypes.c_int64(200), ctypes.byref(out)))
        a = np.ascontiguousarray(X[:120])
        b = np.ascontiguousarray(X[120:200])
        _check(lib, lib.LGBM_DatasetPushRows(
            out, a.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(120), ctypes.c_int32(X.shape[1]),
            ctypes.c_int32(0)))
        _check(lib, lib.LGBM_DatasetPushRows(
            out, b.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(80), ctypes.c_int32(X.shape[1]),
            ctypes.c_int32(120)))
        n = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(out, ctypes.byref(n)))
        assert n.value == 200

    def test_push_rows_incomplete_rejected(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        ref_dh, _ = helper._make_booster(lib, data)
        out = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateByReference(
            ref_dh, ctypes.c_int64(100), ctypes.byref(out)))
        a = np.ascontiguousarray(X[:60])
        _check(lib, lib.LGBM_DatasetPushRows(
            out, a.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(60), ctypes.c_int32(X.shape[1]),
            ctypes.c_int32(0)))
        n = ctypes.c_int32()
        assert lib.LGBM_DatasetGetNumData(out, ctypes.byref(n)) != 0
        assert b"never pushed" in lib.LGBM_GetLastError()

    def test_dump_text(self, lib, data, tmp_path):
        helper = TestCAPIBreadth()
        dh, _ = helper._make_booster(lib, data)
        path = str(tmp_path / "dump.txt")
        _check(lib, lib.LGBM_DatasetDumpText(dh, path.encode()))
        lines = open(path).read().splitlines()
        assert lines[0].startswith("num_data: ")
        assert len(lines) == 3 + 1200


class TestCAPIBreadth4:
    """Fourth batch: CSC create/predict, single-row CSR, AddFeaturesFrom."""

    def test_csc_create_and_predict(self, lib, data):
        import scipy.sparse as sp
        X, y = data
        helper = TestCAPIBreadth()
        _, bh = helper._make_booster(lib, data)
        Xc = sp.csc_matrix(X[:50])
        out = np.zeros(50, np.float64)
        n = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForCSC(
            bh, Xc.indptr.astype(np.int32).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_INT32),
            Xc.indices.astype(np.int32).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)),
            Xc.data.astype(np.float64).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_FLOAT64),
            ctypes.c_int64(len(Xc.indptr)), ctypes.c_int64(Xc.nnz),
            ctypes.c_int64(50), C_API_PREDICT_NORMAL, -1, b"",
            ctypes.byref(n),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        assert n.value == 50
        dense = np.zeros(50, np.float64)
        dl = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh, np.ascontiguousarray(X[:50]).ctypes.data_as(ctypes.c_void_p),
            C_API_DTYPE_FLOAT64, ctypes.c_int32(50),
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1),
            C_API_PREDICT_NORMAL, -1, b"", ctypes.byref(dl),
            dense.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        np.testing.assert_allclose(out, dense, rtol=1e-12)
        # dataset creation from the same CSC must match the mat dataset size
        dh = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromCSC(
            Xc.indptr.astype(np.int32).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_INT32),
            Xc.indices.astype(np.int32).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)),
            Xc.data.astype(np.float64).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_FLOAT64),
            ctypes.c_int64(len(Xc.indptr)), ctypes.c_int64(Xc.nnz),
            ctypes.c_int64(50), b"max_bin=16", None, ctypes.byref(dh)))
        nd = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(dh, ctypes.byref(nd)))
        assert nd.value == 50

    def test_csr_single_row(self, lib, data):
        import scipy.sparse as sp
        X, y = data
        helper = TestCAPIBreadth()
        _, bh = helper._make_booster(lib, data)
        row = sp.csr_matrix(X[:1])
        out = np.zeros(1, np.float64)
        n = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForCSRSingleRow(
            bh, row.indptr.astype(np.int32).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_INT32),
            row.indices.astype(np.int32).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)),
            row.data.astype(np.float64).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_FLOAT64),
            ctypes.c_int64(2), ctypes.c_int64(row.nnz),
            ctypes.c_int64(X.shape[1]), C_API_PREDICT_NORMAL, -1, b"",
            ctypes.byref(n),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        assert n.value == 1 and 0.0 <= out[0] <= 1.0

    def test_add_features_from(self, lib, data):
        X, y = data
        a1 = np.ascontiguousarray(X[:, :3])
        a2 = np.ascontiguousarray(X[:, 3:])
        handles = []
        for arr in (a1, a2):
            h = ctypes.c_void_p()
            _check(lib, lib.LGBM_DatasetCreateFromMat(
                arr.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
                ctypes.c_int32(arr.shape[0]), ctypes.c_int32(arr.shape[1]),
                ctypes.c_int32(1), b"max_bin=32", None, ctypes.byref(h)))
            handles.append(h)
        _check(lib, lib.LGBM_DatasetAddFeaturesFrom(handles[0], handles[1]))
        nf = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumFeature(handles[0],
                                                  ctypes.byref(nf)))
        assert nf.value == X.shape[1]


class TestCAPIBreadth5:
    """Fifth batch: reset training data (continued training on new rows),
    multi-matrix predict."""

    def test_reset_training_data_continues(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        dh, bh = helper._make_booster(lib, data, rounds=3)
        # new dataset aligned with the old one's mappers
        new = ctypes.c_void_p()
        half = np.ascontiguousarray(X[:600])
        _check(lib, lib.LGBM_DatasetCreateFromMat(
            half.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(600), ctypes.c_int32(X.shape[1]),
            ctypes.c_int32(1), b"max_bin=32", dh, ctypes.byref(new)))
        yh = np.ascontiguousarray(y[:600])
        _check(lib, lib.LGBM_DatasetSetField(
            new, b"label", yh.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(600), C_API_DTYPE_FLOAT32))
        _check(lib, lib.LGBM_BoosterResetTrainingData(bh, new))
        fin = ctypes.c_int32()
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bh, ctypes.byref(fin)))
        total = ctypes.c_int32()
        _check(lib, lib.LGBM_BoosterNumberOfTotalModel(bh,
                                                       ctypes.byref(total)))
        assert total.value == 4
        n_len = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterGetNumPredict(bh, 0,
                                                  ctypes.byref(n_len)))
        assert n_len.value == 600

    def test_predict_for_mats(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        _, bh = helper._make_booster(lib, data)
        a = np.ascontiguousarray(X[:30])
        b = np.ascontiguousarray(X[30:80])
        ptrs = (ctypes.c_void_p * 2)(a.ctypes.data_as(ctypes.c_void_p),
                                     b.ctypes.data_as(ctypes.c_void_p))
        nrows = np.asarray([30, 50], np.int32)
        out = np.zeros(80, np.float64)
        n = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMats(
            bh, ptrs, C_API_DTYPE_FLOAT64, ctypes.c_int32(80),
            nrows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int32(2), ctypes.c_int32(X.shape[1]),
            C_API_PREDICT_NORMAL, -1, b"", ctypes.byref(n),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        assert n.value == 80
        dense = np.zeros(80, np.float64)
        dl = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh, np.ascontiguousarray(X[:80]).ctypes.data_as(ctypes.c_void_p),
            C_API_DTYPE_FLOAT64, ctypes.c_int32(80),
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1),
            C_API_PREDICT_NORMAL, -1, b"", ctypes.byref(dl),
            dense.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        np.testing.assert_allclose(out, dense, rtol=1e-12)


class TestCAPIBreadth6:
    """Final batch: leaf-pred refit, CSR row push, sampled-column
    creation, std::function CSR callback."""

    def test_refit_by_leaf_preds(self, lib, data):
        X, y = data
        helper = TestCAPIBreadth()
        dh, bh = helper._make_booster(lib, data, rounds=4)
        total = ctypes.c_int32()
        _check(lib, lib.LGBM_BoosterNumberOfTotalModel(bh,
                                                       ctypes.byref(total)))
        # leaf assignment of the training rows under the current model
        leaves = np.zeros((len(y), total.value), np.float64)
        ll = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bh, np.ascontiguousarray(X).ctypes.data_as(ctypes.c_void_p),
            C_API_DTYPE_FLOAT64, ctypes.c_int32(len(y)),
            ctypes.c_int32(X.shape[1]), ctypes.c_int32(1),
            2, -1, b"", ctypes.byref(ll),  # 2 = leaf-index predict
            leaves.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
        lp = np.ascontiguousarray(leaves.astype(np.int32))
        v0 = ctypes.c_double()
        _check(lib, lib.LGBM_BoosterGetLeafValue(bh, 0, 0,
                                                 ctypes.byref(v0)))
        _check(lib, lib.LGBM_BoosterRefit(
            bh, lp.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int32(len(y)), ctypes.c_int32(total.value)))
        v1 = ctypes.c_double()
        _check(lib, lib.LGBM_BoosterGetLeafValue(bh, 0, 0,
                                                 ctypes.byref(v1)))
        assert v0.value != v1.value  # decay-blended toward the refit value

    def test_push_rows_by_csr(self, lib, data):
        import scipy.sparse as sp
        X, y = data
        helper = TestCAPIBreadth()
        ref_dh, _ = helper._make_booster(lib, data)
        out = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateByReference(
            ref_dh, ctypes.c_int64(90), ctypes.byref(out)))
        blk = sp.csr_matrix(X[:90])
        _check(lib, lib.LGBM_DatasetPushRowsByCSR(
            out, blk.indptr.astype(np.int32).ctypes.data_as(
                ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_INT32),
            blk.indices.astype(np.int32).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)),
            blk.data.astype(np.float64).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(C_API_DTYPE_FLOAT64),
            ctypes.c_int64(len(blk.indptr)), ctypes.c_int64(blk.nnz),
            ctypes.c_int64(X.shape[1]), ctypes.c_int64(0)))
        n = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(out, ctypes.byref(n)))
        assert n.value == 90

    def test_create_from_sampled_column(self, lib, data):
        X, y = data
        ncol = X.shape[1]
        nsample = 300
        cols = [np.ascontiguousarray(X[:nsample, c]) for c in range(ncol)]
        idxs = [np.arange(nsample, dtype=np.int32) for _ in range(ncol)]
        col_ptrs = (ctypes.c_void_p * ncol)(
            *[c.ctypes.data_as(ctypes.c_void_p) for c in cols])
        idx_ptrs = (ctypes.c_void_p * ncol)(
            *[i.ctypes.data_as(ctypes.c_void_p) for i in idxs])
        counts = np.full(ncol, nsample, np.int32)
        out = ctypes.c_void_p()
        _check(lib, lib.LGBM_DatasetCreateFromSampledColumn(
            col_ptrs, idx_ptrs, ctypes.c_int32(ncol),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int32(nsample), ctypes.c_int32(500), b"max_bin=32",
            ctypes.byref(out)))
        blk = np.ascontiguousarray(X[:500])
        _check(lib, lib.LGBM_DatasetPushRows(
            out, blk.ctypes.data_as(ctypes.c_void_p), C_API_DTYPE_FLOAT64,
            ctypes.c_int32(500), ctypes.c_int32(ncol), ctypes.c_int32(0)))
        n = ctypes.c_int32()
        _check(lib, lib.LGBM_DatasetGetNumData(out, ctypes.byref(n)))
        assert n.value == 500
