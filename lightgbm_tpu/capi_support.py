"""Python-side backend for the native C API shim (src/capi).

The reference stacks ctypes-Python ON TOP of a C++ core (reference
python-package/lightgbm/basic.py:24-47 binding src/c_api.cpp).  This
framework's engine is Python/JAX (the XLA program IS the native core), so
the C ABI layer inverts: `lib_lightgbm_tpu.so` (src/capi/
lightgbm_tpu_c_api.cpp) embeds CPython and routes each `LGBM_*` call here.
Handles crossing the ABI are integer ids into `_registry`; raw buffer
pointers are converted with ctypes/numpy on this side so the C++ stays a
thin marshalling layer.

Mirrors the behavior of reference src/c_api.cpp:98-320 (Booster wrapper)
and the dataset creation entry points (reference include/LightGBM/
c_api.h:52-256).
"""

from __future__ import annotations

import ctypes
import itertools
import threading
from typing import Dict, List, Tuple

import numpy as np

from .basic import Booster, Dataset
from .config import Config

# C_API_DTYPE_* (reference include/LightGBM/c_api.h:26-35)
DTYPE_FLOAT32 = 0
DTYPE_FLOAT64 = 1
DTYPE_INT32 = 2
DTYPE_INT64 = 3
DTYPE_INT8 = 4

_CTYPES = {
    DTYPE_FLOAT32: ctypes.c_float,
    DTYPE_FLOAT64: ctypes.c_double,
    DTYPE_INT32: ctypes.c_int32,
    DTYPE_INT64: ctypes.c_int64,
    DTYPE_INT8: ctypes.c_int8,
}

# C_API_PREDICT_* (c_api.h:37-40)
PREDICT_NORMAL = 0
PREDICT_RAW_SCORE = 1
PREDICT_LEAF_INDEX = 2
PREDICT_CONTRIB = 3

_registry: Dict[int, object] = {}
_handles = itertools.count(1)
_lock = threading.Lock()
# pinned arrays returned by dataset_get_field: the caller reads the raw
# pointer after we return, so the array must outlive the call
_field_pins: Dict[Tuple[int, str], np.ndarray] = {}


def _put(obj) -> int:
    with _lock:
        h = next(_handles)
        _registry[h] = obj
    return h


def _get(handle: int):
    try:
        return _registry[handle]
    except KeyError:
        raise ValueError(f"invalid handle {handle}") from None


def free_handle(handle: int) -> None:
    with _lock:
        _registry.pop(handle, None)
        for key in [k for k in _field_pins if k[0] == handle]:
            _field_pins.pop(key, None)


def _params_dict(params_str: str) -> dict:
    return Config.str_to_map(params_str or "")


def _mat_from_ptr(ptr: int, data_type: int, nrow: int, ncol: int,
                  is_row_major: int) -> np.ndarray:
    ct = _CTYPES[data_type]
    buf = ctypes.cast(ptr, ctypes.POINTER(ct))
    arr = np.ctypeslib.as_array(buf, shape=(nrow * ncol,))
    if is_row_major:
        return arr.reshape(nrow, ncol).astype(np.float64)
    return arr.reshape(ncol, nrow).T.astype(np.float64)


def _vec_from_ptr(ptr: int, data_type: int, n: int) -> np.ndarray:
    ct = _CTYPES[data_type]
    buf = ctypes.cast(ptr, ctypes.POINTER(ct))
    return np.ctypeslib.as_array(buf, shape=(n,)).copy()


# ---------------------------------------------------------------- dataset
def dataset_create_from_mat(ptr: int, data_type: int, nrow: int, ncol: int,
                            is_row_major: int, params: str,
                            ref_handle: int) -> int:
    X = _mat_from_ptr(ptr, data_type, nrow, ncol, is_row_major)
    ref = _get(ref_handle) if ref_handle else None
    ds = Dataset(X, reference=ref, params=_params_dict(params))
    ds.construct()
    return _put(ds)


def dataset_create_from_csr(indptr_ptr: int, indptr_type: int, indices_ptr: int,
                            data_ptr: int, data_type: int, nindptr: int,
                            nelem: int, num_col: int, params: str,
                            ref_handle: int) -> int:
    X = _scipy_csr(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                   data_type, nindptr, nelem, num_col)
    ref = _get(ref_handle) if ref_handle else None
    ds = Dataset(X, reference=ref, params=_params_dict(params))
    ds.construct()
    return _put(ds)


def dataset_create_from_file(filename: str, params: str,
                             ref_handle: int) -> int:
    p = _params_dict(params)
    from .io.parser import load_text_file

    X, y, weight, group, _, _ = load_text_file(
        filename, label_column=str(p.get("label_column", "")))
    ref = _get(ref_handle) if ref_handle else None
    ds = Dataset(X, label=y, weight=weight, group=group, reference=ref,
                 params=p)
    ds.construct()
    return _put(ds)


def dataset_num_data(handle: int) -> int:
    return int(_get(handle).num_data())


def dataset_num_feature(handle: int) -> int:
    return int(_get(handle).num_feature())


def dataset_set_field(handle: int, name: str, ptr: int, n: int,
                      data_type: int) -> None:
    ds = _get(handle)
    data = _vec_from_ptr(ptr, data_type, n) if n > 0 else None
    ds.set_field(name, data)


def dataset_get_field(handle: int, name: str) -> Tuple[int, int, int]:
    """(ptr, len, dtype) of the pinned field array; (0, 0, -1) if absent."""
    ds = _get(handle)
    data = ds.get_field(name)
    if data is None:
        return 0, 0, -1
    if name == "group":
        arr = np.ascontiguousarray(data, dtype=np.int32)
        dt = DTYPE_INT32
    else:
        arr = np.ascontiguousarray(data, dtype=np.float32)
        dt = DTYPE_FLOAT32
    _field_pins[(handle, name)] = arr
    return arr.ctypes.data, int(arr.shape[0]), dt


def dataset_save_binary(handle: int, filename: str) -> None:
    ds = _get(handle)
    ds.construct()
    ds._inner.save_binary(filename)


# ---------------------------------------------------------------- booster
def booster_create(train_handle: int, params: str) -> int:
    ds = _get(train_handle)
    bst = Booster(params=_params_dict(params), train_set=ds)
    return _put(bst)


def booster_create_from_modelfile(filename: str) -> Tuple[int, int]:
    bst = Booster(model_file=filename)
    return _put(bst), int(bst.current_iteration())


def booster_load_from_string(model_str: str) -> Tuple[int, int]:
    bst = Booster(model_str=model_str)
    return _put(bst), int(bst.current_iteration())


def booster_add_valid(bh: int, dh: int) -> None:
    bst = _get(bh)
    n = len(bst._valid_names) + 1
    bst.add_valid(_get(dh), f"valid_{n}")


def booster_num_classes(bh: int) -> int:
    return int(_get(bh).num_model_per_iteration())


def booster_update(bh: int) -> int:
    finished = _get(bh).update()
    return 1 if finished else 0


def booster_update_custom(bh: int, grad_ptr: int, hess_ptr: int) -> int:
    bst = _get(bh)
    n = bst._train_set.num_data() * bst.num_model_per_iteration()
    grad = _vec_from_ptr(grad_ptr, DTYPE_FLOAT32, n).astype(np.float64)
    hess = _vec_from_ptr(hess_ptr, DTYPE_FLOAT32, n).astype(np.float64)
    finished = bst.update(fobj=lambda score, ds: (grad, hess))
    return 1 if finished else 0


def booster_rollback(bh: int) -> None:
    _get(bh).rollback_one_iter()


def booster_current_iteration(bh: int) -> int:
    return int(_get(bh).current_iteration())


def booster_num_total_model(bh: int) -> int:
    return int(_get(bh).num_trees())


def booster_num_feature(bh: int) -> int:
    return int(_get(bh).num_feature())


def _eval_results(bst: Booster, data_idx: int) -> List[Tuple[str, float]]:
    if data_idx == 0:
        res = bst.eval_train()
    else:
        res = [r for r in bst.eval_valid()
               if r[0] == f"valid_{data_idx}"]
    return [(r[1], float(r[2])) for r in res]


def booster_eval_counts(bh: int) -> int:
    return len(_eval_results(_get(bh), 0))


def booster_get_eval(bh: int, data_idx: int, out_ptr: int) -> int:
    res = _eval_results(_get(bh), data_idx)
    out = np.ctypeslib.as_array(
        ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_double)),
        shape=(len(res),))
    for i, (_, v) in enumerate(res):
        out[i] = v
    return len(res)


def booster_get_eval_names(bh: int) -> str:
    return "\n".join(name for name, _ in _eval_results(_get(bh), 0))


def booster_predict_for_mat(bh: int, ptr: int, data_type: int, nrow: int,
                            ncol: int, is_row_major: int, predict_type: int,
                            num_iteration: int, params: str,
                            out_ptr: int) -> int:
    X = _mat_from_ptr(ptr, data_type, nrow, ncol, is_row_major)
    return _predict_into(_get(bh), X, predict_type, num_iteration, out_ptr,
                         params)


def booster_calc_num_predict(bh: int, nrow: int, predict_type: int,
                             num_iteration: int) -> int:
    bst = _get(bh)
    k = bst.num_model_per_iteration()
    if predict_type == PREDICT_LEAF_INDEX:
        ni = num_iteration if num_iteration > 0 else max(
            1, bst.num_trees() // max(k, 1))
        return nrow * k * ni
    if predict_type == PREDICT_CONTRIB:
        return nrow * k * (bst.num_feature() + 1)
    return nrow * k


def booster_save_model(bh: int, num_iteration: int, filename: str) -> None:
    ni = num_iteration if num_iteration > 0 else None
    _get(bh).save_model(filename, num_iteration=ni)


def booster_save_to_string(bh: int, num_iteration: int) -> str:
    ni = num_iteration if num_iteration > 0 else None
    return _get(bh).model_to_string(num_iteration=ni)


def booster_dump_model(bh: int, num_iteration: int) -> str:
    import json

    ni = num_iteration if num_iteration > 0 else None
    return json.dumps(_get(bh).dump_model(num_iteration=ni))


def booster_feature_importance(bh: int, num_iteration: int,
                               importance_type: int, out_ptr: int) -> int:
    bst = _get(bh)
    itype = "split" if importance_type == 0 else "gain"
    imp = np.asarray(bst.feature_importance(importance_type=itype),
                     dtype=np.float64)
    out = np.ctypeslib.as_array(
        ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_double)),
        shape=(imp.shape[0],))
    out[:] = imp
    return int(imp.shape[0])


# ---------------------------------------------------------------- network
_network: Dict[str, int] = {"num_machines": 1, "rank": 0}


def network_init(machines: str, local_listen_port: int, listen_time_out: int,
                 num_machines: int) -> None:
    """Record the network config; the actual collective transport is the
    jax.distributed / mesh layer (reference LGBM_NetworkInit c_api.h:999
    maps to Linkers; here ICI/DCN collectives are compiled into the XLA
    program, so init only validates and stores the topology request)."""
    if num_machines > 1:
        from .parallel.mesh import available_devices

        if num_machines > available_devices():
            raise ValueError(
                f"num_machines={num_machines} exceeds available devices")
    _network["num_machines"] = int(num_machines)
    _network["rank"] = 0


def network_free() -> None:
    _network["num_machines"] = 1
    _network["rank"] = 0


def booster_reset_parameter(bh: int, params: str) -> None:
    _get(bh).reset_parameter(_params_dict(params))


def booster_merge(bh: int, other_bh: int) -> None:
    """Append the other booster's trees (reference GBDT::MergeFrom,
    gbdt.h:60)."""
    other = _get(other_bh)
    _get(bh)._driver.merge_from_model_string(other.model_to_string())


def booster_shuffle_models(bh: int, start: int, end: int) -> None:
    _get(bh).shuffle_models(start, end)


def booster_get_leaf_value(bh: int, tree_idx: int, leaf_idx: int) -> float:
    drv = _get(bh)._driver
    drv._materialize()  # trees are built lazily from device records
    return float(drv.models[tree_idx].leaf_value[leaf_idx])


def booster_set_leaf_value(bh: int, tree_idx: int, leaf_idx: int,
                           val: float) -> None:
    drv = _get(bh)._driver
    drv._materialize()
    drv.models[tree_idx].set_leaf_value(leaf_idx, float(val))
    drv._invalidate_tables()


def booster_predict_for_file(bh: int, data_filename: str, has_header: int,
                             predict_type: int, num_iteration: int,
                             params: str, result_filename: str) -> None:
    """Reference LGBM_BoosterPredictForFile (c_api.h:644): parse, predict,
    write the text result file like the CLI predictor."""
    from .config import Config
    from .io.parser import load_text_file

    bst = _get(bh)
    p = _params_dict(params)
    ni = num_iteration if num_iteration > 0 else None
    kw = {}
    if predict_type == PREDICT_RAW_SCORE:
        kw["raw_score"] = True
    elif predict_type == PREDICT_LEAF_INDEX:
        kw["pred_leaf"] = True
    elif predict_type == PREDICT_CONTRIB:
        kw["pred_contrib"] = True
    pcfg = Config({**bst.params, **p})
    for key in ("pred_early_stop", "pred_early_stop_freq",
                "pred_early_stop_margin", "predict_disable_shape_check"):
        kw[key] = getattr(pcfg, key)
    X = load_text_file(data_filename,
                       label_column=str(pcfg.label_column or ""),
                       header=bool(has_header) or None)[0]
    out = np.asarray(bst.predict(X, num_iteration=ni, **kw))
    with open(result_filename, "w") as f:
        if out.ndim == 1:
            for v in out:
                f.write(f"{v:g}\n")
        else:
            for row in out:
                f.write("\t".join(f"{v:g}" for v in row) + "\n")


def dataset_set_feature_names(dh: int, names: str) -> None:
    ds = _get(dh)
    parts = names.split("\t") if names else []
    nf = ds._inner.num_total_features if ds._inner is not None else None
    if nf is not None and len(parts) != nf:
        raise ValueError(
            f"{len(parts)} feature names for {nf} features")
    ds.feature_name = parts
    if ds._inner is not None:
        ds._inner.feature_names = list(parts)


def dataset_get_feature_names(dh: int) -> str:
    ds = _get(dh)
    if ds._inner is not None:
        return "\t".join(str(n) for n in ds._inner.feature_names)
    fn = ds.feature_name
    return "\t".join(fn) if isinstance(fn, (list, tuple)) else ""


def dataset_get_subset(dh: int, idx_ptr: int, n_idx: int,
                       params: str) -> int:
    """Row subset sharing the parent's mappers (reference
    Dataset::CopySubset via LGBM_DatasetGetSubset, c_api.h:286)."""
    ds = _get(dh)
    idx = np.ctypeslib.as_array(
        ctypes.cast(idx_ptr, ctypes.POINTER(ctypes.c_int32)),
        shape=(n_idx,)).copy()
    sub = ds.subset(idx, params=_params_dict(params) or None)
    return _put(sub)


def booster_num_model_per_iteration(bh: int) -> int:
    return booster_num_classes(bh)


def booster_get_feature_names(bh: int) -> str:
    return "\t".join(str(n) for n in _get(bh).feature_name())


def _densify_csr(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                 data_type, nindptr, nelem, num_col):
    """CSR pointers -> dense [nrow, num_col] f64 (block-bounded callers
    only: the streaming push path; whole-matrix ingest goes through
    _scipy_csr)."""
    indptr = _vec_from_ptr(indptr_ptr, indptr_type, nindptr).astype(np.int64)
    indices = _vec_from_ptr(indices_ptr, DTYPE_INT32, nelem).astype(np.int64)
    vals = _vec_from_ptr(data_ptr, data_type, nelem).astype(np.float64)
    nrow = nindptr - 1
    X = np.zeros((nrow, num_col), np.float64)
    row_of = np.repeat(np.arange(nrow), np.diff(indptr))
    # duplicate coordinates must SUM like scipy toarray(), not
    # last-write-win — the scipy and scipy-less paths must bin alike
    np.add.at(X, (row_of, indices), vals)
    return X


def _warn_no_scipy(kind: str) -> None:
    from .utils.log import Log

    Log.warning(f"scipy is unavailable; the {kind} C-API path densifies "
                "the matrix on the host (O(nrow*ncol) memory instead of "
                "O(nnz)) — install scipy for sparse ingest at scale")


def _scipy_csr(indptr_ptr, indptr_type, indices_ptr, data_ptr,
               data_type, nindptr, nelem, num_col):
    """CSR pointers -> scipy.sparse.csr_matrix, O(nnz), no densify.
    Without scipy the path falls back to the dense decode with a loud
    warning rather than an ImportError — the C ABI caller cannot see a
    Python traceback."""
    try:
        from scipy import sparse as sps
    except ImportError:
        _warn_no_scipy("CSR")
        return _densify_csr(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                            data_type, nindptr, nelem, num_col)

    indptr = _vec_from_ptr(indptr_ptr, indptr_type, nindptr).astype(np.int64)
    indices = _vec_from_ptr(indices_ptr, DTYPE_INT32, nelem).astype(np.int32)
    vals = _vec_from_ptr(data_ptr, data_type, nelem).astype(np.float64)
    return sps.csr_matrix((vals, indices, indptr),
                          shape=(nindptr - 1, num_col))


def _predict_kwargs(predict_type: int) -> dict:
    if predict_type == PREDICT_RAW_SCORE:
        return {"raw_score": True}
    if predict_type == PREDICT_LEAF_INDEX:
        return {"pred_leaf": True}
    if predict_type == PREDICT_CONTRIB:
        return {"pred_contrib": True}
    return {}


def _predict_into(bst, X, predict_type: int, num_iteration: int,
                  out_ptr: int, params: str = "") -> int:
    ni = num_iteration if num_iteration > 0 else None
    kw = _predict_kwargs(predict_type)
    if params:
        # forward the predict-time keys from the C params string
        # (reference c_api.cpp predict paths parse the full Config)
        pcfg = Config({**bst.params, **_params_dict(params)})
        for key in ("pred_early_stop", "pred_early_stop_freq",
                    "pred_early_stop_margin", "predict_disable_shape_check"):
            kw[key] = getattr(pcfg, key)
    pred = np.asarray(
        bst.predict(X, num_iteration=ni, **kw),
        dtype=np.float64).reshape(-1)
    out = np.ctypeslib.as_array(
        ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_double)),
        shape=(pred.shape[0],))
    out[:] = pred
    return int(pred.shape[0])


def booster_predict_for_csr(bh: int, indptr_ptr: int, indptr_type: int,
                            indices_ptr: int, data_ptr: int, data_type: int,
                            nindptr: int, nelem: int, num_col: int,
                            predict_type: int, num_iteration: int,
                            params: str, out_ptr: int) -> int:
    """Sparse rows ride Booster.predict's chunked-densify path
    (reference c_api.h:644 PredictForCSR)."""
    X = _scipy_csr(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                   data_type, nindptr, nelem, num_col)
    return _predict_into(_get(bh), X, predict_type, num_iteration, out_ptr,
                         params)


def dataset_create_from_mats(ptrs_ptr: int, data_type: int, nrows_ptr: int,
                             nmat: int, ncol: int, is_row_major: int,
                             params: str, ref_handle: int) -> int:
    """Stack several row-major blocks into one dataset (reference
    LGBM_DatasetCreateFromMats, c_api.h:160)."""
    # read the pointer array as raw uint64 words: numpy's buffer
    # protocol has no PEP-3118 code for void*
    ptrs = np.ctypeslib.as_array(
        ctypes.cast(ptrs_ptr, ctypes.POINTER(ctypes.c_uint64)),
        shape=(nmat,))
    nrows = np.ctypeslib.as_array(
        ctypes.cast(nrows_ptr, ctypes.POINTER(ctypes.c_int32)),
        shape=(nmat,))
    blocks = [_mat_from_ptr(int(ptrs[i]), data_type, int(nrows[i]), ncol,
                            is_row_major)
              for i in range(nmat)]
    X = np.vstack(blocks)
    ref = _get(ref_handle) if ref_handle else None
    ds = Dataset(X, reference=ref, params=_params_dict(params))
    ds.construct()
    return _put(ds)


def _score_state(drv, data_idx: int):
    """data_idx -> maintained score state (0 = train, i+1 = valid i)."""
    if data_idx == 0:
        return drv.train_scores
    if 0 < data_idx <= len(drv.valid_scores):
        return drv.valid_scores[data_idx - 1]
    raise IndexError(f"no dataset at data_idx {data_idx}")


def booster_get_num_predict(bh: int, data_idx: int) -> int:
    """Prediction count for dataset data_idx (reference
    LGBM_BoosterGetNumPredict, c_api.h:608)."""
    st = _score_state(_get(bh)._driver, data_idx)
    return int(st.scores.shape[0] * st.scores.shape[1])


def booster_get_predict(bh: int, data_idx: int, out_ptr: int) -> int:
    """Converted predictions for dataset data_idx (reference
    LGBM_BoosterGetPredict -> GBDT::GetPredictAt, which applies the
    objective's ConvertOutput transform; written class-major)."""
    drv = _get(bh)._driver
    drv._materialize()
    st = _score_state(drv, data_idx)
    scores = st.numpy()
    if drv.objective is not None:
        scores = np.asarray(drv.objective.convert_output(scores),
                            np.float64).reshape(scores.shape)
    scores = scores.reshape(-1)
    out = np.ctypeslib.as_array(
        ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_double)),
        shape=(scores.shape[0],))
    out[:] = scores
    return int(scores.shape[0])


def dataset_update_param(dh: int, params: str) -> None:
    """Merge new params, rejecting changes to bin-defining keys once
    constructed (reference Dataset::ResetConfig, dataset.cpp:395-400)."""
    ds = _get(dh)
    new = _params_dict(params)
    if ds._inner is not None:
        frozen = ("max_bin", "max_bin_by_feature", "bin_construct_sample_cnt",
                  "min_data_in_bin", "use_missing", "zero_as_missing",
                  "categorical_feature", "forcedbins_filename")
        # compare EFFECTIVE values (current config incl. defaults), so
        # restating a default is the no-op the reference accepts
        cur = Config(ds.params)
        eff = Config({**ds.params, **new})
        for k in frozen:
            if k in new and getattr(eff, k) != getattr(cur, k):
                raise ValueError(
                    f"cannot change {k} after the dataset is constructed")
    ds.params.update(new)


def _make_streaming_dataset(reference, num_total_row: int, ncol: int,
                            params: dict) -> "Dataset":
    """NaN-filled pending buffer whose rows arrive via PushRows; refuses
    to construct until every allocated row was pushed (the reference's
    FinishLoad contract — unpushed rows would silently train as NaN)."""
    buf = np.full((int(num_total_row), ncol), np.nan, np.float64)
    ds = Dataset(buf, reference=reference, params=params)
    ds._pushed = np.zeros(int(num_total_row), bool)
    ds._pushed_complete = False
    orig_construct = ds.construct

    def _guarded_construct():
        if not ds._pushed_complete and ds._inner is None:
            missing = int((~ds._pushed).sum())
            raise RuntimeError(
                f"{missing} of {len(ds._pushed)} rows never pushed")
        return orig_construct()

    ds.construct = _guarded_construct
    return ds


def dataset_create_by_reference(ref_handle: int, num_total_row: int) -> int:
    """Allocate an empty row buffer aligned with `ref` for streaming
    construction via PushRows (reference c_api.h:266-311)."""
    ref = _get(ref_handle)
    ref.construct()
    ds = _make_streaming_dataset(ref, num_total_row,
                                 ref._inner.num_total_features,
                                 dict(ref.params))
    return _put(ds)


def dataset_push_rows(dh: int, ptr: int, data_type: int, nrow: int,
                      ncol: int, start_row: int) -> None:
    ds = _get(dh)
    if ds._inner is not None:
        raise RuntimeError("cannot push rows after construction")
    block = _mat_from_ptr(ptr, data_type, nrow, ncol, 1)
    ds.data[start_row:start_row + nrow, :] = block
    ds._pushed[start_row:start_row + nrow] = True
    if bool(ds._pushed.all()):
        # every allocated row arrived: the dataset may construct (the
        # reference's FinishLoad moment)
        ds._pushed_complete = True


def dataset_dump_text(dh: int, filename: str) -> None:
    """Debug text dump: header plus per-row label and binned values
    (reference LGBM_DatasetDumpText, c_api.h:316)."""
    ds = _get(dh)
    ds.construct()
    inner = ds._inner
    with open(filename, "w") as f:
        f.write(f"num_data: {inner.num_data}\n")
        f.write(f"num_features: {inner.num_features}\n")
        f.write("feature_names: " + "\t".join(inner.feature_names) + "\n")
        label = inner.metadata.label
        if label is None:
            label = np.zeros(inner.num_data, np.float64)
        for i in range(inner.num_data):
            row = "\t".join(str(int(b)) for b in inner.bins[i])
            f.write(f"{label[i]:g}\t{row}\n")


def _scipy_csc(col_ptr_p, col_ptr_type, indices_ptr, data_ptr, data_type,
               ncol_ptr, nelem, num_row):
    """CSC pointers -> scipy.sparse.csc_matrix, O(nnz), no densify
    (reference LGBM_DatasetCreateFromCSC keeps columns sparse,
    c_api.cpp CSC path / src/io/sparse_bin.hpp:73).  Falls back to a
    dense decode with a warning when scipy is absent — see _scipy_csr."""
    col_ptr = _vec_from_ptr(col_ptr_p, col_ptr_type, ncol_ptr).astype(np.int64)
    indices = _vec_from_ptr(indices_ptr, DTYPE_INT32, nelem).astype(np.int64)
    vals = _vec_from_ptr(data_ptr, data_type, nelem).astype(np.float64)
    try:
        from scipy import sparse as sps
    except ImportError:
        _warn_no_scipy("CSC")
        X = np.zeros((num_row, ncol_ptr - 1), np.float64)
        col_of = np.repeat(np.arange(ncol_ptr - 1), np.diff(col_ptr))
        np.add.at(X, (indices, col_of), vals)  # duplicates sum, as scipy
        return X
    return sps.csc_matrix((vals, indices.astype(np.int32), col_ptr),
                          shape=(num_row, ncol_ptr - 1))


def dataset_create_from_csc(col_ptr_p: int, col_ptr_type: int,
                            indices_ptr: int, data_ptr: int, data_type: int,
                            ncol_ptr: int, nelem: int, num_row: int,
                            params: str, ref_handle: int) -> int:
    X = _scipy_csc(col_ptr_p, col_ptr_type, indices_ptr, data_ptr,
                   data_type, ncol_ptr, nelem, num_row)
    ref = _get(ref_handle) if ref_handle else None
    ds = Dataset(X, reference=ref, params=_params_dict(params))
    ds.construct()
    return _put(ds)


def booster_predict_for_csc(bh: int, col_ptr_p: int, col_ptr_type: int,
                            indices_ptr: int, data_ptr: int, data_type: int,
                            ncol_ptr: int, nelem: int, num_row: int,
                            predict_type: int, num_iteration: int,
                            params: str, out_ptr: int) -> int:
    X = _scipy_csc(col_ptr_p, col_ptr_type, indices_ptr, data_ptr,
                   data_type, ncol_ptr, nelem, num_row)
    return _predict_into(_get(bh), X, predict_type, num_iteration, out_ptr,
                         params)


def dataset_add_features_from(dh: int, other_dh: int) -> None:
    """Merge `other`'s features into `dh` column-wise (reference
    Dataset::AddFeaturesFrom via LGBM_DatasetAddFeaturesFrom,
    c_api.h:297): delegates to Dataset.add_features_from (basic.py)."""
    _get(dh).add_features_from(_get(other_dh))


def booster_reset_training_data(bh: int, dh: int) -> None:
    bst = _get(bh)
    ds = _get(dh)
    ds.construct()
    bst._driver.reset_training_data(ds._inner)
    bst._train_set = ds


def booster_predict_for_mats(bh: int, ptrs_ptr: int, data_type: int,
                             nrows_ptr: int, nmat: int, ncol: int,
                             predict_type: int, num_iteration: int,
                             params: str, out_ptr: int) -> int:
    ptrs = np.ctypeslib.as_array(
        ctypes.cast(ptrs_ptr, ctypes.POINTER(ctypes.c_uint64)),
        shape=(nmat,))
    nrows = np.ctypeslib.as_array(
        ctypes.cast(nrows_ptr, ctypes.POINTER(ctypes.c_int32)),
        shape=(nmat,))
    X = np.vstack([_mat_from_ptr(int(ptrs[i]), data_type, int(nrows[i]),
                                 ncol, 1)
                   for i in range(nmat)])
    return _predict_into(_get(bh), X, predict_type, num_iteration, out_ptr,
                         params)


def booster_refit(bh: int, leaf_preds_ptr: int, nrow: int,
                  ncol: int) -> None:
    """Reference LGBM_BoosterRefit (c_api.h:493 -> GBDT::RefitTree):
    re-fit leaf values on the CURRENT training data given a [nrow, ncol]
    leaf-assignment matrix (one column per model)."""
    drv = _get(bh)._driver
    drv._materialize()
    if drv.train_data is None:
        raise ValueError("refit by leaf predictions needs a booster with "
                         "training data attached")
    if nrow != drv.train_data.num_data:
        raise ValueError(f"leaf_preds has {nrow} rows for "
                         f"{drv.train_data.num_data} training rows")
    if ncol != len(drv.models):
        raise ValueError(f"leaf_preds has {ncol} columns for "
                         f"{len(drv.models)} models")
    leaf_preds = np.ctypeslib.as_array(
        ctypes.cast(leaf_preds_ptr, ctypes.POINTER(ctypes.c_int32)),
        shape=(nrow, ncol)).copy()
    cfg = drv.config or Config({})
    obj = drv.objective
    if obj is None:
        from .models.objectives import create_objective_from_model_string

        obj = create_objective_from_model_string(
            drv.loaded_params.get("objective", ""))
    if obj is None:
        raise ValueError("cannot refit without an objective")
    if getattr(obj, "metadata", None) is None:
        obj.init(drv.train_data.metadata, drv.train_data.num_data)
    drv._refit_by_leaf_preds(leaf_preds, obj,
                             float(cfg.refit_decay_rate), cfg)


def dataset_push_rows_by_csr(dh: int, indptr_ptr: int, indptr_type: int,
                             indices_ptr: int, data_ptr: int,
                             data_type: int, nindptr: int, nelem: int,
                             num_col: int, start_row: int) -> None:
    ds = _get(dh)
    if ds._inner is not None:
        raise RuntimeError("cannot push rows after construction")
    block = _densify_csr(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, num_col)
    nrow = block.shape[0]
    ds.data[start_row:start_row + nrow, :] = block
    ds._pushed[start_row:start_row + nrow] = True
    if bool(ds._pushed.all()):
        ds._pushed_complete = True


def dataset_create_from_sampled_column(sample_ptrs: int, indices_ptrs: int,
                                       ncol: int, num_per_col_ptr: int,
                                       num_sample_row: int,
                                       num_total_row: int,
                                       params: str) -> int:
    """Reference LGBM_DatasetCreateFromSampledColumn (c_api.h:69):
    mappers from per-column value samples, rows pushed afterwards.
    Unsampled entries are zero, like the reference's sparse sampling."""
    sp = np.ctypeslib.as_array(
        ctypes.cast(sample_ptrs, ctypes.POINTER(ctypes.c_uint64)),
        shape=(ncol,))
    ip = np.ctypeslib.as_array(
        ctypes.cast(indices_ptrs, ctypes.POINTER(ctypes.c_uint64)),
        shape=(ncol,))
    counts = np.ctypeslib.as_array(
        ctypes.cast(num_per_col_ptr, ctypes.POINTER(ctypes.c_int32)),
        shape=(ncol,))
    sample = np.zeros((int(num_sample_row), int(ncol)), np.float64)
    for c in range(int(ncol)):
        m = int(counts[c])
        if m == 0:
            continue
        vals = _vec_from_ptr(int(sp[c]), DTYPE_FLOAT64, m)
        rows = _vec_from_ptr(int(ip[c]), DTYPE_INT32, m).astype(np.int64)
        sample[rows, c] = vals
    p = _params_dict(params)
    # mapper donor found ONCE on the sample, the near-unsplittable filter
    # scaled against the FULL row count; constraints derive from the
    # donor's own used-feature set, so nothing is swapped post-hoc
    from .io.dataset import Metadata, TrainingData, _parse_column_spec

    donor_td = TrainingData()
    donor_td.config = Config(p)
    donor_td.num_data = int(num_sample_row)
    donor_td.num_total_features = int(ncol)
    donor_td.feature_names = [f"Column_{i}" for i in range(int(ncol))]
    cat = _parse_column_spec(donor_td.config.categorical_feature,
                             donor_td.feature_names)
    donor_td._find_mappers(sample, donor_td.config, cat or [], {},
                           total_rows=int(num_total_row))
    donor_td._set_constraints(donor_td.config)
    donor_td.metadata = Metadata(int(num_sample_row))
    donor = Dataset.__new__(Dataset)
    donor.data = None
    donor.label = None
    donor.reference = None
    donor.weight = donor.group = donor.init_score = None
    donor.feature_name = "auto"
    donor.categorical_feature = p.get("categorical_feature", "auto")
    donor.params = dict(p)
    donor.free_raw_data = True
    donor.used_indices = None
    donor._inner = donor_td
    ds = _make_streaming_dataset(donor, int(num_total_row), int(ncol), p)
    return _put(ds)
