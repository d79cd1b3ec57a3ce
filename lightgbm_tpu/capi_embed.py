"""Embedding bridge for the native training C API.

The reference exposes its full training surface through C
(/root/reference/src/c_api.cpp: LGBM_DatasetCreateFromMat :~900,
LGBM_BoosterCreate :1600, LGBM_BoosterUpdateOneIter :1686,
LGBM_BoosterSaveModel...).  In the TPU rebuild the training core is a JAX
program, so the native shim (native/capi_train.cpp) embeds CPython and
calls these thin adapters; zero-copy views of the caller's buffers come in
as memoryviews.

Functions here must stay exception-safe-by-contract: the C++ caller
converts any raised exception into LGBM_GetLastError().
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

if os.environ.get("LGBM_TPU_FORCE_CPU"):
    # embedded hosts (pure-C callers) can't run the test conftest; honor an
    # env switch so they never claim the chip (one process per chip)
    import jax
    jax.config.update("jax_platforms", "cpu")

from .booster import Booster
from .config import kv2map
from .dataset import Dataset

_F32, _F64, _I32, _I64 = 0, 1, 2, 3
_NP_OF = {_F32: np.float32, _F64: np.float64, _I32: np.int32, _I64: np.int64}


def _params(s: str) -> dict:
    return kv2map((s or "").replace("\n", " ").split())


def dataset_create_from_mat(mv, nrow: int, ncol: int, params: str,
                            reference: Optional[Dataset] = None) -> Dataset:
    arr = np.frombuffer(mv, np.float64).reshape(int(nrow), int(ncol)).copy()
    return Dataset(arr, params=_params(params), reference=reference)


def dataset_create_from_file(path: str, params: str,
                             reference: Optional[Dataset] = None) -> Dataset:
    from .data_io import load_text
    p = _params(params)
    # binary dataset cache (the reference detects its binary magic the
    # same way, dataset_loader.cpp LoadFromBinFile): the npz container
    # starts with the zip magic
    real = path if os.path.exists(path) else (
        path + ".npz" if os.path.exists(path + ".npz") else path)
    try:
        with open(real, "rb") as f:
            if f.read(2) == b"PK":
                return Dataset.load_binary(real)
    except OSError:
        pass
    x, y = load_text(path, has_header=str(p.get("header", "")).lower()
                     in ("true", "1"),
                     label_column=str(p.get("label_column", "")))
    return Dataset(x, label=y, params=p, reference=reference)


def dataset_set_field(ds, name: str, mv, n: int, dtype: int) -> None:
    arr = np.frombuffer(mv, _NP_OF[int(dtype)])[:int(n)].copy()
    if isinstance(ds, _StreamingDataset) and ds.ds is None:
        # SetField is valid at any point of the streaming protocol in the
        # reference C API; it must not finalize the dataset mid-stream
        ds.pending_fields[name] = arr
        return
    ds = _as_dataset(ds)
    if name == "label":
        ds.set_label(arr)
    elif name == "weight":
        ds.set_weight(arr)
    elif name in ("group", "query"):
        ds.set_group(arr)
    elif name == "init_score":
        nrows = ds.num_data if getattr(ds, "num_data", 0) else (
            ds._raw_input.shape[0]
            if getattr(ds, "_raw_input", None) is not None
            and hasattr(ds._raw_input, "shape") else len(arr))
        if nrows and len(arr) > nrows:
            # multiclass: the C API ships class-major blocks
            # ([all rows class 0, all rows class 1, ...], c_api.h);
            # internal storage is [rows, classes]
            arr = np.ascontiguousarray(arr.reshape((-1, nrows)).T)
        ds.set_init_score(arr)
    else:
        raise ValueError(f"unknown field {name!r}")


def dataset_num_data(ds) -> int:
    ds = _as_dataset(ds)
    ds.construct()
    return int(ds.num_data)


def dataset_num_feature(ds) -> int:
    ds = _as_dataset(ds)
    ds.construct()
    return int(ds.num_total_features)


def booster_create(ds, params: str) -> Booster:
    return Booster(params=_params(params), train_set=_as_dataset(ds))


def booster_create_from_model_string(s: str) -> Booster:
    return Booster(model_str=s)


def booster_add_valid(bst: Booster, ds, name: str) -> None:
    bst.add_valid(_as_dataset(ds), name)


def booster_update(bst: Booster) -> int:
    return 1 if bst.update() else 0


def booster_rollback(bst: Booster) -> None:
    bst.rollback_one_iter()


def booster_current_iteration(bst: Booster) -> int:
    return int(bst.current_iteration)


def booster_num_classes(bst: Booster) -> int:
    return int(bst._num_class)


def booster_save_model_to_string(bst: Booster, start_iteration: int,
                                 num_iteration: int) -> str:
    num = num_iteration if num_iteration > 0 else None
    return bst.model_to_string(num_iteration=num,
                               start_iteration=int(start_iteration))


def booster_save_model(bst: Booster, start_iteration: int,
                       num_iteration: int, filename: str) -> None:
    # utf-8 to match Booster's load side and the artifact-checksum
    # convention (snapshot manifests hash utf-8 bytes); the locale
    # default would break the round-trip on non-utf-8 hosts
    with open(filename, "w", encoding="utf-8") as f:
        f.write(booster_save_model_to_string(bst, start_iteration,
                                             num_iteration))


def booster_get_eval(bst: Booster) -> str:
    """One eval sweep, rendered as 'name metric value' lines."""
    rows = bst.eval_valid() + bst.eval_train()
    return "\n".join(f"{dn}\t{mn}\t{val!r}" for dn, mn, val, _ in rows)


def _predict_dispatch(bst: Booster, x, predict_type: int,
                      start_iteration: int, num_iteration: int) -> np.ndarray:
    """predict_type: 0 normal, 1 raw, 2 leaf index, 3 contrib
    (C_API_PREDICT_* values, c_api.h:527-535) — the single dispatch used
    by every C prediction entry point."""
    num = int(num_iteration) if int(num_iteration) > 0 else None
    kw = dict(start_iteration=int(start_iteration), num_iteration=num)
    predict_type = int(predict_type)
    if predict_type == 2:
        res = bst.predict(x, pred_leaf=True, **kw)
    elif predict_type == 3:
        res = bst.predict(x, pred_contrib=True, **kw)
    else:
        res = bst.predict(x, raw_score=(predict_type == 1), **kw)
    return np.asarray(res, np.float64)


def _predict_out(bst: Booster, x, predict_type: int, start_iteration: int,
                 num_iteration: int, out_mv) -> int:
    res = _predict_dispatch(bst, x, predict_type, start_iteration,
                            num_iteration)
    flat = np.ascontiguousarray(res).reshape(-1)
    out = np.frombuffer(out_mv, np.float64)
    if len(flat) > len(out):
        raise ValueError(f"output buffer too small: need {len(flat)}, "
                         f"have {len(out)}")
    out[:len(flat)] = flat
    return int(len(flat))


def booster_predict_mat(bst: Booster, mv, nrow: int, ncol: int,
                        predict_type: int, start_iteration: int,
                        num_iteration: int, out_mv) -> int:
    """predict_type: 0 normal, 1 raw, 2 leaf index, 3 contrib
    (C_API_PREDICT_* values, c_api.h:527-535)."""
    x = np.frombuffer(mv, np.float64).reshape(int(nrow), int(ncol))
    return _predict_out(bst, x, predict_type, start_iteration,
                        num_iteration, out_mv)


# ---------------------------------------------------------------------------
# CSR / CSC dataset construction + prediction
# (LGBM_DatasetCreateFromCSR/CSC c_api.h:200-268;
#  LGBM_BoosterPredictForCSR c_api.h:815)
# ---------------------------------------------------------------------------

def _sparse_parts(indptr_mv, n_indptr, indices_mv, data_mv, nelem):
    indptr = np.frombuffer(indptr_mv, np.int32)[:int(n_indptr)].copy()
    indices = np.frombuffer(indices_mv, np.int32)[:int(nelem)].copy()
    data = np.frombuffer(data_mv, np.float64)[:int(nelem)].copy()
    return indptr, indices, data


def _csr(indptr_mv, n_indptr, indices_mv, data_mv, nelem, ncol):
    from scipy.sparse import csr_matrix
    indptr, indices, data = _sparse_parts(indptr_mv, n_indptr, indices_mv,
                                          data_mv, nelem)
    return csr_matrix((data, indices, indptr),
                      shape=(int(n_indptr) - 1, int(ncol)))


def dataset_create_from_csr(indptr_mv, n_indptr, indices_mv, data_mv,
                            nelem, ncol, params: str,
                            reference: Optional[Dataset] = None) -> Dataset:
    return Dataset(_csr(indptr_mv, n_indptr, indices_mv, data_mv, nelem,
                        ncol), params=_params(params), reference=reference)


def dataset_create_from_csc(indptr_mv, n_indptr, indices_mv, data_mv,
                            nelem, nrow, params: str,
                            reference: Optional[Dataset] = None) -> Dataset:
    from scipy.sparse import csc_matrix
    indptr, indices, data = _sparse_parts(indptr_mv, n_indptr, indices_mv,
                                          data_mv, nelem)
    mat = csc_matrix((data, indices, indptr),
                     shape=(int(nrow), int(n_indptr) - 1))
    return Dataset(mat, params=_params(params), reference=reference)


def booster_predict_csr(bst: Booster, indptr_mv, n_indptr, indices_mv,
                        data_mv, nelem, ncol, predict_type: int,
                        start_iteration: int, num_iteration: int,
                        out_mv) -> int:
    x = _csr(indptr_mv, n_indptr, indices_mv, data_mv, nelem, ncol)
    return _predict_out(bst, x, predict_type, start_iteration,
                        num_iteration, out_mv)


# ---------------------------------------------------------------------------
# Streaming dataset construction
# (LGBM_DatasetCreateFromSampledColumn + LGBM_DatasetPushRows[ByCSR],
#  c_api.h:109-313).  The reference pre-builds bin mappers from the sample
#  and bins rows as they are pushed; here rows are accumulated and binned
#  at finalize — same API contract and final Dataset, with peak memory one
#  float64 copy of the raw matrix (the TPU learner keeps a dense binned
#  matrix in HBM anyway, so sampled-column binning would not change the
#  steady-state footprint).
# ---------------------------------------------------------------------------

class _StreamingDataset:
    def __init__(self, nrow: int, ncol: int, params: str):
        self.buf = np.full((int(nrow), int(ncol)), np.nan, np.float64)
        self.filled = 0
        self.params = _params(params)
        self.pending_fields: dict = {}
        self.ds: Optional[Dataset] = None
        self.mappers = None            # CreateFromSampledColumn pre-fit
        self.reference = None          # CreateByReference alignment

    def finish(self) -> Dataset:
        if self.ds is None:
            self.ds = Dataset(self.buf[:self.filled], params=self.params,
                              bin_mappers=self.mappers,
                              reference=self.reference)
            for name, arr in self.pending_fields.items():
                dataset_set_field(self.ds, name, memoryview(arr.tobytes()),
                                  len(arr),
                                  {np.dtype(np.float32): _F32,
                                   np.dtype(np.float64): _F64,
                                   np.dtype(np.int32): _I32,
                                   np.dtype(np.int64): _I64}[arr.dtype])
        return self.ds


def dataset_create_streaming(nrow: int, ncol: int,
                             params: str) -> _StreamingDataset:
    return _StreamingDataset(nrow, ncol, params)


def dataset_push_rows(sd: _StreamingDataset, mv, nrow: int, ncol: int,
                      start_row: int) -> None:
    if sd.ds is not None:
        raise ValueError("dataset already finalized")
    arr = np.frombuffer(mv, np.float64).reshape(int(nrow), int(ncol))
    sd.buf[int(start_row):int(start_row) + int(nrow), :int(ncol)] = arr
    sd.filled = max(sd.filled, int(start_row) + int(nrow))


def dataset_push_rows_by_csr(sd: _StreamingDataset, indptr_mv, n_indptr,
                             indices_mv, data_mv, nelem,
                             start_row: int) -> None:
    if sd.ds is not None:
        raise ValueError("dataset already finalized")
    x = _csr(indptr_mv, n_indptr, indices_mv, data_mv, nelem,
             sd.buf.shape[1]).toarray()
    sd.buf[int(start_row):int(start_row) + x.shape[0]] = x
    sd.filled = max(sd.filled, int(start_row) + x.shape[0])


def _as_dataset(ds):
    """Streaming handles are accepted anywhere a Dataset is (finalized on
    first use, like the reference's mark-finished semantics)."""
    return ds.finish() if isinstance(ds, _StreamingDataset) else ds


# ---------------------------------------------------------------------------
# Booster getters / reset (c_api.h booster introspection surface)
# ---------------------------------------------------------------------------

def booster_num_feature(bst: Booster) -> int:
    return int(bst.num_feature())


def booster_get_eval_names(bst: Booster) -> str:
    """Metadata-only (the reference's GetEvalNames does not evaluate)."""
    names = []
    for m in bst._train_metrics:
        if m.name not in names:
            names.append(m.name)
    return "\t".join(names)


def booster_feature_importance(bst: Booster, importance_type: int,
                               out_mv) -> int:
    """importance_type: 0 split, 1 gain (C_API_FEATURE_IMPORTANCE_*)."""
    imp = bst.feature_importance(
        importance_type="gain" if importance_type == 1 else "split")
    out = np.frombuffer(out_mv, np.float64)
    if len(imp) > len(out):
        raise ValueError("output buffer too small")
    out[:len(imp)] = imp.astype(np.float64)
    return int(len(imp))


def booster_reset_parameter(bst: Booster, params: str) -> None:
    bst.reset_parameter(_params(params))


def booster_dump_model(bst: Booster, start_iteration: int,
                       num_iteration: int) -> str:
    """JSON model dump (LGBM_BoosterDumpModel, c_api.h; DumpModel)."""
    import json
    num = num_iteration if num_iteration > 0 else None
    return json.dumps(bst.dump_model(num_iteration=num,
                                     start_iteration=int(start_iteration)))


def booster_refit(bst: Booster, mv, nrow: int, ncol: int, label_mv,
                  decay_rate: float) -> Booster:
    """Refit existing tree structures on new data
    (LGBM_BoosterRefit, c_api.h; GBDT::RefitTree gbdt.cpp:287)."""
    x = np.frombuffer(mv, np.float64).reshape(int(nrow), int(ncol)).copy()
    label = np.frombuffer(label_mv, np.float32)[:int(nrow)].copy()
    return bst.refit(x, label, decay_rate=float(decay_rate))


def dataset_get_field(ds, name: str):
    """(address, length, type_code) of a metadata field, or length 0 when
    unset (LGBM_DatasetGetField, c_api.h).  'group' returns the QUERY
    BOUNDARIES array [num_queries+1] like the reference.  The backing
    array is pinned on the Dataset so the pointer stays valid until the
    next GetField call on the same handle."""
    ds = _as_dataset(ds)
    ds.construct()
    md = ds.metadata
    if name == "label":
        arr, code = md.label, _F32
    elif name == "weight":
        arr, code = md.weight, _F32
    elif name in ("group", "query"):
        arr, code = md.query_boundaries, _I32
    elif name == "init_score":
        arr, code = md.init_score, _F64
    else:
        raise ValueError(f"unknown field {name!r}")
    if arr is None:
        # empty field: valid dtype code + null pointer, like the reference
        return (0, 0, code)
    arr = np.asarray(arr, _NP_OF[code])
    if arr.ndim == 2:
        # multiclass init_score: the C API contract is CLASS-MAJOR
        # ([all rows class 0, all rows class 1, ...], c_api.h GetField)
        arr = arr.flatten(order="F")
    arr = np.ascontiguousarray(arr)
    ds._field_out = arr            # keep the buffer alive for the caller
    return (int(arr.ctypes.data), int(arr.size), code)


def dataset_save_binary(ds, filename: str) -> None:
    """Binary dataset cache (LGBM_DatasetSaveBinary, c_api.h;
    Dataset::SaveBinaryFile)."""
    ds = _as_dataset(ds)
    ds.construct()
    ds.save_binary(filename)


def dataset_get_feature_names(ds) -> str:
    ds = _as_dataset(ds)
    ds.construct()
    names = ds.feature_names or [
        f"Column_{i}" for i in range(ds.num_total_features)]
    return "\t".join(names)


def dataset_set_feature_names(ds, names: str) -> None:
    ds = _as_dataset(ds)
    lst = names.split("\t")
    nf = getattr(ds, "num_total_features", 0)
    if not nf:
        # pre-construct: the raw input's width is already known
        raw = getattr(ds, "_raw_input", None)
        nf = raw.shape[1] if raw is not None \
            and hasattr(raw, "shape") and len(raw.shape) == 2 else 0
    if nf and len(lst) != nf:
        # fail at the API call, not later inside dump_model/save
        raise ValueError(f"{len(lst)} feature names for {nf} features")
    # set the constructor-style input too: construct()'s _resolve_names
    # would otherwise overwrite the assignment with Column_N defaults
    ds._feature_name_in = lst
    ds.feature_names = lst


# ---------------------------------------------------------------------------
# Network init (LGBM_NetworkInit, c_api.h:1350).  The reference builds its
# socket-collective mesh from a machine list; the TPU framework's
# collectives are XLA's, so this maps onto the jax.distributed runtime:
# coordinator = first machine, rank = position of the entry whose port
# matches local_listen_port (the reference derives rank by matching local
# addresses the same way, src/network/linkers_socket.cpp).
# ---------------------------------------------------------------------------

def network_init(machines: str, local_listen_port: int, listen_time_out: int,
                 num_machines: int) -> None:
    if num_machines <= 1:
        return
    entries = [m.strip() for m in machines.replace("\n", ",").split(",")
               if m.strip()]
    if len(entries) != num_machines:
        raise ValueError(
            f"machines lists {len(entries)} entries, num_machines="
            f"{num_machines}")
    from .parallel import launch
    # multi-process-per-host (the reference's distributed test topology,
    # tests/distributed/_test_distributed.py): every entry is the same
    # host with a DISTINCT port, so the port identifies the rank.  Only
    # safe when exactly one entry matches — the canonical multi-host
    # layout reuses one port on every machine, where the port would match
    # entry 0 everywhere; that case goes to launch.init's local-address
    # matching instead.
    matches = [i for i, e in enumerate(entries)
               if e.endswith(f":{local_listen_port}")]
    # the reference's listen_time_out is MINUTES (config.h time_out);
    # it bounds the resilience layer's bring-up watchdog + retry deadline
    timeout_s = max(0.0, float(listen_time_out)) * 60.0
    if len(matches) == 1:
        launch.init(coordinator_address=entries[0],
                    num_processes=num_machines, process_id=matches[0],
                    timeout_s=timeout_s)
    else:
        launch.init(machines=",".join(entries),
                    local_listen_port=local_listen_port,
                    timeout_s=timeout_s)


def network_free() -> None:
    import jax
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        pass  # never initialized


# ---------------------------------------------------------------------------
# Reference-exact ABI adapters (VERDICT r3 task 5): the typed/positional
# variants the reference's own c_api.h prototypes use (c_api.h:109,203,
# 248,272,472,567,701,749,1072,1141-1199,1220), driven by the LGBM_*-named
# exports in native/capi_train.cpp so reference bindings and apps link
# against libcapi_train.so unmodified.
# ---------------------------------------------------------------------------

def _typed_matrix(mv, data_type: int, nrow: int, ncol: int,
                  is_row_major: int) -> np.ndarray:
    dt = _NP_OF[int(data_type)]
    arr = np.frombuffer(mv, dt)[:int(nrow) * int(ncol)]
    if int(is_row_major):
        arr = arr.reshape(int(nrow), int(ncol))
    else:
        arr = arr.reshape(int(ncol), int(nrow)).T
    return np.array(arr, np.float64, copy=True, order="C")


def dataset_create_from_mat2(mv, data_type: int, nrow: int, ncol: int,
                             is_row_major: int, params: str,
                             reference=None) -> Dataset:
    return Dataset(_typed_matrix(mv, data_type, nrow, ncol, is_row_major),
                   params=_params(params), reference=_as_dataset(reference)
                   if reference is not None else None)


def _typed_sparse_parts(indptr_mv, indptr_type, n_indptr, indices_mv,
                        data_mv, data_type, nelem):
    indptr = np.frombuffer(indptr_mv,
                           _NP_OF[int(indptr_type)])[:int(n_indptr)]
    indices = np.frombuffer(indices_mv, np.int32)[:int(nelem)]
    data = np.frombuffer(data_mv, _NP_OF[int(data_type)])[:int(nelem)]
    return (indptr.astype(np.int64), indices.copy(),
            data.astype(np.float64))


def dataset_create_from_csr2(indptr_mv, indptr_type, indices_mv, data_mv,
                             data_type, n_indptr, nelem, ncol, params: str,
                             reference=None) -> Dataset:
    from scipy.sparse import csr_matrix
    indptr, indices, data = _typed_sparse_parts(
        indptr_mv, indptr_type, n_indptr, indices_mv, data_mv, data_type,
        nelem)
    mat = csr_matrix((data, indices, indptr),
                     shape=(int(n_indptr) - 1, int(ncol)))
    return Dataset(mat, params=_params(params),
                   reference=_as_dataset(reference)
                   if reference is not None else None)


def dataset_create_from_csc2(colptr_mv, colptr_type, indices_mv, data_mv,
                             data_type, ncol_ptr, nelem, nrow, params: str,
                             reference=None) -> Dataset:
    from scipy.sparse import csc_matrix
    colptr, indices, data = _typed_sparse_parts(
        colptr_mv, colptr_type, ncol_ptr, indices_mv, data_mv, data_type,
        nelem)
    mat = csc_matrix((data, indices, colptr),
                     shape=(int(nrow), int(ncol_ptr) - 1))
    return Dataset(mat, params=_params(params),
                   reference=_as_dataset(reference)
                   if reference is not None else None)


def booster_num_total_model(bst: Booster) -> int:
    return int(len(bst.trees))


def booster_num_model_per_iteration(bst: Booster) -> int:
    return int(bst._num_tree_per_iteration)


def booster_get_eval_counts(bst: Booster) -> int:
    return len(booster_get_eval_names(bst).split("\t")) \
        if booster_get_eval_names(bst) else 0


def booster_get_eval_values(bst: Booster, data_idx: int, out_mv) -> int:
    """LGBM_BoosterGetEval (c_api.h:701): data_idx 0 = training data,
    i >= 1 = (i-1)-th validation set; one double per eval metric."""
    if int(data_idx) == 0:
        rows = bst.eval_train()
    else:
        names = bst._valid_names
        i = int(data_idx) - 1
        if i >= len(names):
            raise ValueError(f"data_idx {data_idx} out of range "
                             f"({len(names)} validation sets)")
        rows = [r for r in bst.eval_valid() if r[0] == names[i]]
    vals = np.asarray([v for _, _, v, _ in rows], np.float64)
    out = np.frombuffer(out_mv, np.float64)
    if len(vals) > len(out):
        raise ValueError("output buffer too small")
    out[:len(vals)] = vals
    return int(len(vals))


def booster_predict_mat2(bst: Booster, mv, data_type: int, nrow: int,
                         ncol: int, is_row_major: int, predict_type: int,
                         start_iteration: int, num_iteration: int,
                         out_mv) -> int:
    x = _typed_matrix(mv, data_type, nrow, ncol, is_row_major)
    return _predict_out(bst, x, predict_type, start_iteration,
                        num_iteration, out_mv)


def booster_predict_csr2(bst: Booster, indptr_mv, indptr_type, indices_mv,
                         data_mv, data_type, n_indptr, nelem, ncol,
                         predict_type: int, start_iteration: int,
                         num_iteration: int, out_mv) -> int:
    from scipy.sparse import csr_matrix
    indptr, indices, data = _typed_sparse_parts(
        indptr_mv, indptr_type, n_indptr, indices_mv, data_mv, data_type,
        nelem)
    x = csr_matrix((data, indices, indptr),
                   shape=(int(n_indptr) - 1, int(ncol)))
    return _predict_out(bst, x, predict_type, start_iteration,
                        num_iteration, out_mv)


def booster_predict_for_file(bst: Booster, data_filename: str,
                             has_header: int, predict_type: int,
                             start_iteration: int, num_iteration: int,
                             result_filename: str) -> None:
    """LGBM_BoosterPredictForFile (c_api.h:749): text rows follow the
    training convention (label in the first column unless the width
    already matches the model)."""
    from .data_io import load_text
    x, y = load_text(data_filename, has_header=bool(int(has_header)))
    nf = bst.num_feature()
    if x.shape[1] == nf - 1 and y is not None:
        # the file had NO label column: load_text treated feature 0 as
        # the label — put it back
        x = np.column_stack([y, x])
    res = np.atleast_1d(_predict_dispatch(bst, x, predict_type,
                                          start_iteration, num_iteration))
    with open(result_filename, "w") as f:
        if res.ndim == 1:
            for v in res:
                f.write(f"{v:.17g}\n")
        else:
            for row in res:
                f.write("\t".join(f"{v:.17g}" for v in row) + "\n")


def booster_add_valid_auto(bst: Booster, ds) -> None:
    booster_add_valid(bst, ds, f"valid_{len(bst._valid_names)}")


def booster_update_custom(bst: Booster, grad_mv, hess_mv, n: int) -> int:
    g = np.frombuffer(grad_mv, np.float32)[:int(n)].copy()
    h = np.frombuffer(hess_mv, np.float32)[:int(n)].copy()
    nc = int(bst._model.num_class)
    if nc > 1:
        # the C contract ships class-major blocks ([all rows class 0,
        # all rows class 1, ...], c_api.h:589); internal layout is
        # [rows, classes]
        nd = int(bst._model.num_data)
        g = np.ascontiguousarray(g.reshape(nc, nd).T)
        h = np.ascontiguousarray(h.reshape(nc, nd).T)
    return 1 if bst.update(fobj=lambda preds, ds: (g, h)) else 0


def booster_train_num_data(bst: Booster) -> int:
    """Gradient buffer length for LGBM_BoosterUpdateOneIterCustom:
    num_data * num_class (c_api.h:589-595 contract)."""
    return int(bst._model.num_data * bst._model.num_class)


# ---------------------------------------------------------------------------
# The remaining reference entry points (c_api.h full-surface closure):
# sampled-column/by-reference construction, subset, feature merge, text
# dump, per-feature bin counts, model surgery (merge/shuffle/leaf get-set),
# leaf-pred refit, reset-training-data, bound values, sparse-output
# predict, param-alias dump, log forwarding.
# ---------------------------------------------------------------------------

def dump_param_aliases() -> str:
    """LGBM_DumpParamAliases (c_api.h:62): JSON param -> [aliases]."""
    import json
    from .config import _PARAMS
    return json.dumps({name: list(spec[2]) if len(spec) > 2 else []
                       for name, spec in _PARAMS.items()})


def sample_count(num_total_row: int, params: str) -> int:
    """LGBM_GetSampleCount: min(bin_construct_sample_cnt, total)."""
    p = _params(params)
    cnt = int(p.get("bin_construct_sample_cnt", 200000))
    return int(min(cnt, int(num_total_row)))


def sample_indices(num_total_row: int, params: str, out_mv) -> int:
    """LGBM_SampleIndices: the binning sample row ids (sorted, like the
    reference's Random::Sample)."""
    p = _params(params)
    n = sample_count(num_total_row, params)
    seed = int(p.get("data_random_seed", 1))
    rng = np.random.RandomState(seed)
    idx = np.sort(rng.choice(int(num_total_row), size=n, replace=False)
                  .astype(np.int32))
    out = np.frombuffer(out_mv, np.int32)
    out[:n] = idx
    return n


def register_log_forward(addr: int) -> None:
    """Route Log output to a C callback (LGBM_RegisterLogCallback)."""
    import ctypes
    from .utils import log as log_mod
    if addr == 0:
        log_mod._callback = None
        return
    cb = ctypes.CFUNCTYPE(None, ctypes.c_char_p)(int(addr))
    log_mod._callback = lambda msg: cb(msg.encode())


def dataset_create_from_sampled_column(cols, num_sample_row: int,
                                       num_total_row: int,
                                       params: str) -> "_StreamingDataset":
    """LGBM_DatasetCreateFromSampledColumn (c_api.h:126): pre-size the
    dataset and fit the bin mappers NOW from the per-column samples, so
    pushed rows bin against a fixed layout (the reference streams the
    same way); ``cols`` is a list of per-column sampled value arrays.
    find_bin's total count is the SAMPLE size (zeros are inferred as
    num_sample_row - len(col), not against the full dataset)."""
    from .binning import BinMapper
    from .config import Config
    p = _params(params)
    cfg = Config(p)
    mappers = []
    for vals in cols:
        m = BinMapper()
        m.find_bin(np.asarray(vals, np.float64), int(num_sample_row),
                   cfg.max_bin, cfg.min_data_in_bin,
                   use_missing=cfg.use_missing,
                   zero_as_missing=cfg.zero_as_missing)
        mappers.append(m)
    sd = _StreamingDataset(num_total_row, len(cols), params)
    sd.mappers = mappers
    return sd


def dataset_create_by_reference(ref, num_total_row: int) -> "_StreamingDataset":
    """LGBM_DatasetCreateByReference (c_api.h:142): pre-sized streaming
    dataset aligned to the reference's bin mappers."""
    ref = _as_dataset(ref)
    ref.construct()
    sd = _StreamingDataset(num_total_row, ref.num_total_features, "")
    sd.reference = ref
    return sd


def dataset_push_rows2(sd, mv, data_type: int, nrow: int, ncol: int,
                       start_row: int) -> None:
    """Typed LGBM_DatasetPushRows (c_api.h:156)."""
    arr = _typed_matrix(mv, data_type, nrow, ncol, 1)
    if sd.ds is not None:
        raise ValueError("dataset already finalized")
    sd.buf[int(start_row):int(start_row) + int(nrow), :int(ncol)] = arr
    sd.filled = max(sd.filled, int(start_row) + int(nrow))


def dataset_push_rows_by_csr2(sd, indptr_mv, indptr_type, indices_mv,
                              data_mv, data_type, nindptr, nelem,
                              start_row: int) -> None:
    """Typed LGBM_DatasetPushRowsByCSR (c_api.h:177)."""
    from scipy.sparse import csr_matrix
    indptr, indices, data = _typed_sparse_parts(
        indptr_mv, indptr_type, nindptr, indices_mv, data_mv, data_type,
        nelem)
    x = csr_matrix((data, indices, indptr),
                   shape=(int(nindptr) - 1, sd.buf.shape[1])).toarray()
    if sd.ds is not None:
        raise ValueError("dataset already finalized")
    sd.buf[int(start_row):int(start_row) + x.shape[0]] = x
    sd.filled = max(sd.filled, int(start_row) + x.shape[0])


def dataset_get_subset(ds, idx_mv, num: int, params: str):
    """LGBM_DatasetGetSubset (c_api.h:313)."""
    ds = _as_dataset(ds)
    ds.construct()
    idx = np.frombuffer(idx_mv, np.int32)[:int(num)].copy()
    return ds.subset(idx)


def dataset_add_features_from(target, source) -> None:
    """LGBM_DatasetAddFeaturesFrom (c_api.h:452): append source's
    feature columns to target (Dataset.add_features_from).  A C-API
    dataset handle is semantically always constructed (the reference's
    LGBM_DatasetCreateFromMat bins eagerly); only the PYTHON Dataset is
    lazy, so construct before delegating — the lazy-API strictness
    check is for python callers."""
    _as_dataset(target).construct()
    _as_dataset(source).construct()
    _as_dataset(target).add_features_from(_as_dataset(source))


def dataset_dump_text(ds, filename: str) -> None:
    """LGBM_DatasetDumpText (c_api.h:371): binned values, one row per
    line (the reference's debugging dump).  The header lists only the
    USED features — feature_binned() has no columns for trivial ones."""
    ds = _as_dataset(ds)
    ds.construct()
    binned = ds.feature_binned()
    names = ds.feature_names or [
        f"Column_{i}" for i in range(ds.num_total_features)]
    used_names = [names[f] for f in ds.used_features]
    with open(filename, "w") as f:
        f.write("\t".join(used_names) + "\n")
        for row in binned:
            f.write("\t".join(str(int(v)) for v in row) + "\n")


def dataset_update_param_checking(old_params: str, new_params: str) -> None:
    """LGBM_DatasetUpdateParamChecking (c_api.h:414): raise when a
    dataset-affecting parameter changed (config.cpp dataset param set).
    Compared on RESOLVED Config values (aliases applied, absent keys at
    their defaults) like the reference — an explicit value equal to the
    default is not a change."""
    from .config import Config
    dataset_keys = (
        "max_bin", "min_data_in_bin", "bin_construct_sample_cnt",
        "use_missing", "zero_as_missing", "categorical_feature",
        "feature_pre_filter", "enable_bundle", "data_random_seed",
        "is_enable_sparse", "header", "two_round", "label_column",
        "weight_column", "group_column", "ignore_column",
        "forcedbins_filename", "precise_float_parser",
        "max_conflict_rate", "linear_tree")
    o, n = Config(_params(old_params)), Config(_params(new_params))
    changed = [k for k in dataset_keys
               if getattr(o, k, None) != getattr(n, k, None)]
    if changed:
        raise ValueError(
            "cannot change dataset parameters after construction: "
            + ", ".join(changed))


def dataset_feature_num_bin(ds, feature: int) -> int:
    """LGBM_DatasetGetFeatureNumBin (c_api.h:442).  ``bin_mappers`` is
    indexed by TOTAL feature id (trivial features keep their single-bin
    mapper), not by used-feature slot."""
    ds = _as_dataset(ds)
    ds.construct()
    f = int(feature)
    if not 0 <= f < len(ds.bin_mappers):
        raise ValueError(f"feature index {f} out of range "
                         f"({len(ds.bin_mappers)} features)")
    return int(ds.bin_mappers[f].num_bin)


def booster_get_linear(bst: Booster) -> int:
    return 1 if getattr(bst.config, "linear_tree", False) else 0


def booster_get_leaf_value(bst: Booster, tree_idx: int,
                           leaf_idx: int) -> float:
    return float(bst.trees[int(tree_idx)].leaf_value[int(leaf_idx)])


def booster_set_leaf_value(bst: Booster, tree_idx: int, leaf_idx: int,
                           val: float) -> None:
    """LGBM_BoosterSetLeafValue (Tree::SetLeafOutput): updates the host
    tree and, when the booster is mid-training, its device copy (train/
    valid score caches are NOT retro-adjusted — same as the reference,
    which applies the new value from the next AddScore on)."""
    bst.trees[int(tree_idx)].leaf_value[int(leaf_idx)] = float(val)
    m = getattr(bst, "_model", None)
    if m is not None and int(tree_idx) < len(getattr(m, "device_trees", [])):
        import jax.numpy as jnp
        dt = m.device_trees[int(tree_idx)]
        lv = np.asarray(dt.leaf_value).copy()
        lv[int(leaf_idx)] = float(val)
        dt.leaf_value = jnp.asarray(lv, jnp.float32)


def booster_merge(bst: Booster, other: Booster) -> None:
    """LGBM_BoosterMerge (c_api.h:522): append other's models."""
    bst._merge_from(other)


def booster_shuffle_models(bst: Booster, start_iter: int,
                           end_iter: int) -> None:
    bst._shuffle_models(int(start_iter), int(end_iter))


def booster_num_predict(bst: Booster, data_idx: int) -> int:
    m = bst._model
    if int(data_idx) == 0:
        n = m.num_data
    else:
        i = int(data_idx) - 1
        if i >= len(m.valid_sets):
            raise ValueError(f"data_idx {data_idx} out of range")
        n = m.valid_sets[i][0].num_data
    return int(n * m.num_class)


def booster_get_predict(bst: Booster, data_idx: int, out_mv) -> int:
    """LGBM_BoosterGetPredict (c_api.h:728): transformed scores for the
    train (0) / valid (i>=1) data."""
    import jax.numpy as jnp
    m = bst._model
    if int(data_idx) == 0:
        score = m.train_score()
    else:
        score = m.valid_score(int(data_idx) - 1)
    score = np.asarray(score)
    if m.objective is not None:
        s = score[:, 0] if m.num_class == 1 else score
        score = np.asarray(m.objective.convert_output(jnp.asarray(s)))
        score = score.reshape(len(score), -1)
    flat = np.ascontiguousarray(score.astype(np.float64)).reshape(-1)
    out = np.frombuffer(out_mv, np.float64)
    if len(flat) > len(out):
        raise ValueError("output buffer too small")
    out[:len(flat)] = flat
    return int(len(flat))


def booster_reset_training_data(bst: Booster, ds) -> None:
    bst.reset_training_data(_as_dataset(ds))


def booster_refit_leaf_preds(bst: Booster, leaf_mv, nrow: int,
                             ncol: int) -> None:
    """LGBM_BoosterRefit (c_api.h:578): re-fit leaf values from given
    per-tree leaf assignments (GBDT::RefitTree, gbdt.cpp:287-323) using
    the booster's training data labels."""
    leaves = np.frombuffer(leaf_mv, np.int32)[:int(nrow) * int(ncol)] \
        .reshape(int(nrow), int(ncol)).copy()
    bst.refit_with_leaves(leaves)


def booster_upper_bound(bst: Booster) -> float:
    return bst._bounds()[1]


def booster_lower_bound(bst: Booster) -> float:
    return bst._bounds()[0]


def booster_predict_csc2(bst: Booster, colptr_mv, colptr_type, indices_mv,
                         data_mv, data_type, ncol_ptr, nelem, nrow,
                         predict_type: int, start_iteration: int,
                         num_iteration: int, out_mv) -> int:
    from scipy.sparse import csc_matrix
    colptr, indices, data = _typed_sparse_parts(
        colptr_mv, colptr_type, ncol_ptr, indices_mv, data_mv, data_type,
        nelem)
    x = csc_matrix((data, indices, colptr),
                   shape=(int(nrow), int(ncol_ptr) - 1)).tocsr()
    return _predict_out(bst, x, predict_type, start_iteration,
                        num_iteration, out_mv)


def booster_predict_sparse(bst: Booster, indptr_mv, indptr_type,
                           indices_mv, data_mv, data_type, nindptr, nelem,
                           num_col_or_row, predict_type: int,
                           start_iteration: int, num_iteration: int,
                           matrix_type: int):
    """LGBM_BoosterPredictSparseOutput (c_api.h:859): contrib
    predictions as sparse CSR (matrix_type 0) / CSC (1) triples.
    Returns (indptr int64 array, indices int32 array, data float64
    array) pinned on the booster until the next call."""
    from scipy.sparse import csr_matrix, csc_matrix
    indptr, indices, data = _typed_sparse_parts(
        indptr_mv, indptr_type, nindptr, indices_mv, data_mv, data_type,
        nelem)
    x = csr_matrix((data, indices, indptr),
                   shape=(int(nindptr) - 1, int(num_col_or_row)))
    dense = _predict_dispatch(bst, x, predict_type, start_iteration,
                              num_iteration)
    dense = dense.reshape(x.shape[0], -1)
    out = csc_matrix(dense) if int(matrix_type) == 1 else csr_matrix(dense)
    # output buffers TYPED to the caller's input types, like the
    # reference (c_api.cpp:504-507): int32/int64 indptr, f32/f64 data
    trip = (np.ascontiguousarray(out.indptr, _NP_OF[int(indptr_type)]),
            np.ascontiguousarray(out.indices, np.int32),
            np.ascontiguousarray(out.data, _NP_OF[int(data_type)]))
    bst._sparse_out = trip             # keep buffers alive for the caller
    return (int(trip[0].ctypes.data), int(trip[0].size),
            int(trip[1].ctypes.data),
            int(trip[2].ctypes.data), int(trip[2].size))


def booster_get_feature_names(bst: Booster) -> str:
    return "\t".join(bst.feature_names or [])
