"""Cluster orchestration: the reference's Dask-layer analog, TPU-shaped.

The reference orchestrates multi-machine training from Python with
dask.py (/root/reference/python-package/lightgbm/dask.py:393-810
``_train``: find each worker's data parts, allocate one port per worker
machine, build the ``machines=ip1:port1,ip2:port2`` parameter, then run
one trainer per worker wired through ``LGBM_NetworkInit``).  A TPU
cluster's unit of scheduling is a process per host over a device mesh,
so the analog here has two halves:

- :func:`run` — the *launcher* (dask._train's port-allocation and
  process bring-up role, shaped like torchrun): spawns N coordinated
  worker processes on this machine (or emits the per-host command lines
  for a real multi-host cluster), each bootstrapped through
  ``parallel.launch.init`` with the machines-parameter conventions.
- :func:`train` — the *per-worker trainer* (dask._train_part's role):
  an SPMD entry every process calls identically; it shards rows, fits
  globally-consistent bin mappers (sharded FindBin + allgather,
  parallel/dist_data.py), constructs the local Dataset and trains with
  ``tree_learner=data`` over the global mesh.  On a TPU pod slice, call
  :func:`train` directly from your per-host script — the JAX runtime is
  the launcher there.

Worker functions are addressed as ``"module:function"`` (the launcher
re-imports them in each spawned process), receive a
:class:`WorkerContext` and may return any picklable result;
:func:`run` returns the per-rank results rank-ordered.
"""

from __future__ import annotations

import os
import pickle
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, List, NamedTuple, Optional

import numpy as np

from .sklearn import (LGBMClassifier as _SkClassifier,
                      LGBMRanker as _SkRanker,
                      LGBMRegressor as _SkRegressor)


class WorkerContext(NamedTuple):
    """What every spawned worker receives (dask.py passes the same facts
    through its closure: rank via worker address, machines string,
    listen port)."""
    rank: int
    num_workers: int
    machines: str            # "host1:port1,host2:port2" (config.h machines)
    local_listen_port: int


def _free_ports(n: int) -> List[int]:
    """Allocate n distinct free localhost ports (dask.py:_find_n_open_ports
    role)."""
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


def build_machines(hosts: List[str], ports: List[int]) -> str:
    """The reference ``machines`` parameter (config.h; dask.py:700)."""
    return ",".join(f"{h}:{p}" for h, p in zip(hosts, ports))


def run(entry: str, num_workers: int = 2, *,
        hosts: Optional[List[str]] = None,
        base_port: Optional[int] = None,
        backend: str = "cpu",
        args: Any = None,
        rank_args: Optional[List[Any]] = None,
        timeout: int = 600,
        extra_pythonpath: Optional[List[str]] = None) -> List[Any]:
    """Spawn ``num_workers`` coordinated training processes on this
    machine and return their results rank-ordered.

    entry: ``"module:function"`` — imported in each worker; called as
      ``function(ctx)``, ``function(ctx, args)`` when ``args`` given, or
      ``function(ctx, args, rank_args[rank])`` when ``rank_args`` given.
    rank_args: one value PER RANK, serialized separately so each worker
      unpickles only its own (a worker's data partition must not be
      shipped to — or held by — every other worker).
    hosts: one entry per worker for a REAL cluster (the function then
      only prints the per-host command lines — a cluster scheduler, not
      this process, must start them); default localhost spawning.
    backend: "cpu" pins workers to the CPU backend with gloo collectives
      (the test topology; also what the reference's distributed tests
      do over localhost sockets); "" leaves device selection to JAX and
      is for one worker per HOST of a real cluster — on localhost it is
      refused (see below).
    """
    local = hosts is None or not (set(hosts) - {"127.0.0.1", "localhost"})
    if local and backend != "cpu":
        # a chip belongs to one process: N local workers that leave
        # device selection to JAX would each try to claim every chip of
        # this host, and all but the first fail or hang
        raise ValueError(
            f"distributed.run(backend={backend!r}) would start "
            f"{num_workers} processes on this host, each claiming every "
            "local chip; a chip belongs to one process.  The multi-chip "
            "topology of one host is ONE process over an in-process "
            "mesh: call lgb.train with tree_learner=data|feature|voting "
            "(GBDTModel._resolve_mesh).  backend=\"cpu\" runs the "
            "local N-process topology on CPU devices.")
    if not local:
        ports = [base_port or 12400] * len(hosts)
        machines = build_machines(hosts, ports)
        lines = [
            f"{sys.executable} -m lightgbm_tpu.distributed "
            f"--entry {entry} --rank {i} --num-workers {len(hosts)} "
            f"--machines {machines}" for i in range(len(hosts))]
        raise SystemExit(
            "multi-host cluster: start one process per host:\n  "
            + "\n  ".join(lines))

    ports = _free_ports(num_workers)
    machines = build_machines(["127.0.0.1"] * num_workers, ports)
    tmp = tempfile.mkdtemp(prefix="lgbm_tpu_dist_")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # worker sets its own device count
    if extra_pythonpath:
        env["PYTHONPATH"] = os.pathsep.join(
            list(extra_pythonpath) + [env.get("PYTHONPATH", "")])
    args_path = ""
    if args is not None:
        args_path = os.path.join(tmp, "args.pkl")
        with open(args_path, "wb") as f:
            pickle.dump(args, f)
    rank_args_paths = [""] * num_workers
    if rank_args is not None:
        if len(rank_args) != num_workers:
            raise ValueError(f"rank_args has {len(rank_args)} entries "
                             f"for {num_workers} workers")
        for rank, ra in enumerate(rank_args):
            rank_args_paths[rank] = os.path.join(tmp, f"rank{rank}.pkl")
            with open(rank_args_paths[rank], "wb") as f:
                pickle.dump(ra, f)

    # worker output goes to FILES, not pipes: the workers run coordinated
    # collectives, so blocking on one worker's full pipe buffer would
    # stall its collectives and deadlock the whole cluster
    procs, logs = [], []
    for rank in range(num_workers):
        cmd = [sys.executable, "-m", "lightgbm_tpu.distributed",
               "--entry", entry, "--rank", str(rank),
               "--num-workers", str(num_workers),
               "--machines", machines,
               "--result", os.path.join(tmp, f"r{rank}.pkl"),
               "--backend", backend]
        if args_path:
            cmd += ["--args", args_path]
        if rank_args_paths[rank]:
            cmd += ["--rank-args", rank_args_paths[rank]]
        log = open(os.path.join(tmp, f"r{rank}.log"), "w+")
        logs.append(log)
        procs.append(subprocess.Popen(cmd, env=env, stdout=log,
                                      stderr=subprocess.STDOUT, text=True))
    deadline = time.monotonic() + timeout
    try:
        for p in procs:
            p.wait(timeout=max(deadline - time.monotonic(), 1.0))
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    outs = []
    for log in logs:
        log.flush()
        log.seek(0)
        outs.append(log.read())
        log.close()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"worker {rank} failed (rc={p.returncode}):\n{out[-3000:]}")
    results = []
    for rank in range(num_workers):
        with open(os.path.join(tmp, f"r{rank}.pkl"), "rb") as f:
            results.append(pickle.load(f))
    return results


def train(params: dict, x: np.ndarray, y: Optional[np.ndarray] = None, *,
          weight: Optional[np.ndarray] = None,
          num_boost_round: int = 100,
          shard_rows: bool = True,
          sample_count: int = 200_000,
          valid: Optional[tuple] = None):
    """SPMD per-worker trainer (dask.py:_train_part analog): every
    process calls this identically; returns the (replicated) Booster.

    params may carry the reference's network parameters — ``machines`` +
    ``local_listen_port`` (config.h) — in which case the network is
    initialized here exactly like ``LGBM_NetworkInit``; under :func:`run`
    or on an already-initialized pod that step is a no-op.

    shard_rows: x/y are the GLOBAL arrays and each process keeps its
    contiguous shard (dataset_loader.cpp:203-298 per-rank partition);
    pass False when each process loaded only its own rows already.
    """
    from . import Dataset, train as _engine_train
    from .config import Config
    from .parallel import launch

    p = dict(params)
    machines = str(p.pop("machines", "") or "")
    port = int(p.pop("local_listen_port", 12400) or 12400)
    if machines and not getattr(launch.init, "_done", False):
        # honor the fault-tolerance bring-up params (config.py) here the
        # same way GBDTModel._resolve_mesh does for the mesh claim
        launch.init(machines=machines, local_listen_port=port,
                    retries=int(p.get("dist_init_retries", 2)),
                    timeout_s=float(p.get("dist_init_timeout_s", 300.0)))

    import jax
    pc = jax.process_count()
    if pc > 1:
        p.setdefault("num_machines", pc)
        p.setdefault("tree_learner", "data")
        if shard_rows:
            sh = launch.row_shard(x, y)
            if weight is not None:
                # same deterministic contiguous partition as row_shard
                parts = np.array_split(np.arange(len(x)), pc)
                weight = np.asarray(weight)[parts[sh.process_index]]
        else:
            sh = launch.RowShard(x=x, y=y,
                                 process_index=jax.process_index(),
                                 process_count=pc)
        cfg = Config(dict(p, num_iterations=num_boost_round))
        cat_spec = str(getattr(cfg, "categorical_feature", "") or "")
        cat = {int(t) for t in cat_spec.split(",") if t.strip().isdigit()} \
            or None
        mappers = launch.global_bin_mappers(sh.sample(sample_count), cfg,
                                            cat_idx=cat)
        ds = Dataset(sh.x, label=sh.y, weight=weight, params=p,
                     bin_mappers=mappers)
    else:
        ds = Dataset(x, label=y, weight=weight, params=p)
    kw = {}
    if valid is not None:
        vx, vy = valid
        kw["valid_sets"] = [Dataset(vx, label=vy, params=p, reference=ds)]
    return _engine_train(p, ds, num_boost_round=num_boost_round, **kw)


# ---------------------------------------------------------------------------
# Estimator layer (dask.py:1092-1417 DaskLGBMClassifier/Regressor/Ranker
# analog, minus Dask itself): sklearn-style estimators whose fit() runs
# over a pod of coordinated worker processes via :func:`run`, training
# directly on PRE-PARTITIONED per-worker data (the dask-collection
# partition model) or partitioning a global array for you.

def _fit_worker(ctx: WorkerContext, args: dict, part: tuple):
    """Per-worker fit body (dask.py:_train_part analog): spawned by
    :func:`run` inside an initialized pod with ONLY this rank's data
    part (run's rank_args — no worker ever holds another's partition);
    trains with globally-consistent bin mappers and returns the
    (replicated) model plus fit-result attributes."""
    from . import Dataset, train as _engine_train
    from .callback import record_evaluation
    from .config import Config
    from .parallel import launch
    import jax

    pc = jax.process_count()
    x, y, w, g = part
    p = dict(args["params"])
    p.setdefault("num_machines", pc)
    rounds = args["rounds"]

    cfg = Config(dict(p, num_iterations=rounds))
    # categorical columns participate in the distributed FindBin as
    # categories, mirroring the single-process sklearn path (and
    # distributed.train's cat_idx handling)
    cat_spec = str(getattr(cfg, "categorical_feature", "") or "")
    cat = {int(t) for t in cat_spec.split(",") if t.strip().isdigit()} \
        or None
    k_sample = int(p.get("bin_construct_sample_cnt", 200000))
    if _is_sparse(x):
        x = x.tocsr()
        # densifying the sample is bounded by an ELEMENT budget — the
        # floor is 1 row, not a fixed row count, or the budget would be
        # defeated exactly on the very-wide input it exists for
        # (256 rows x 5M columns is already ~10 GB dense)
        k_sample = min(k_sample,
                       max(1, 50_000_000 // max(1, x.shape[1])))
        sample = x[:k_sample].toarray()
    else:
        sample = np.asarray(x)[:k_sample]
    mappers = launch.global_bin_mappers(sample, cfg, cat_idx=cat)
    ds = Dataset(x, label=y, weight=w, group=g, params=p,
                 bin_mappers=mappers)

    valid_sets, valid_names, evals = [], [], {}
    for i, (vx, vy, vw, vg) in enumerate(args.get("eval_set") or []):
        valid_sets.append(Dataset(vx, label=vy, weight=vw, group=vg,
                                  reference=ds))
        names = args.get("eval_names")
        valid_names.append(names[i] if names else f"valid_{i}")
    cbs = [record_evaluation(evals)] if valid_sets else None
    bst = _engine_train(p, ds, num_boost_round=rounds,
                        valid_sets=valid_sets or None,
                        valid_names=valid_names or None, callbacks=cbs)
    return {"model": bst.model_to_string(),
            "evals": evals,
            "best_iteration": bst.best_iteration,
            "best_score": dict(bst.best_score),
            "n_features": int(x.shape[1])}


def _is_sparse(a) -> bool:
    try:
        import scipy.sparse as sp
        return sp.issparse(a)
    except ImportError:
        return False


def _split_parts(arr, n: int, row_splits: Optional[List[np.ndarray]]):
    """Contiguous per-worker row parts; scipy-sparse matrices pass
    through row-sliced (the Dataset consumes CSR/CSC natively — see
    sparse_data.py — so densifying here would defeat the k-hot binned
    storage on exactly the wide inputs that need it)."""
    if arr is None:
        return [None] * n
    if isinstance(arr, (list, tuple)):
        if len(arr) != n:
            raise ValueError(
                f"pre-partitioned input has {len(arr)} parts for "
                f"{n} workers — one part per worker")
        return [a.tocsr() if _is_sparse(a) else np.asarray(a)
                for a in arr]
    # CSR row-slices/indexes like an ndarray; COO/DOK/BSR do not
    arr = arr.tocsr() if _is_sparse(arr) else np.asarray(arr)
    if row_splits is not None:
        return [arr[idx] for idx in row_splits]
    bounds = np.linspace(0, arr.shape[0], n + 1).astype(int)
    return [arr[bounds[i]:bounds[i + 1]] for i in range(n)]


class _DistLGBMModel:
    """Mixin carrying the distributed fit (dask.py:_DaskLGBMModel role:
    the launcher knobs ride the estimator, fit fans out, the fitted
    state loads back into the plain sklearn estimator)."""

    def _set_dist(self, n_workers: int, backend: str, timeout: int):
        self.n_workers = int(n_workers)
        self._dist_backend = backend
        self._dist_timeout = int(timeout)

    def _encode_eval_label(self, y: np.ndarray) -> np.ndarray:
        """eval_set labels through the same transform as the training
        labels (classifier overrides with the fitted class encoding)."""
        return self._process_label(y)

    def _dist_fit(self, X, y, sample_weight=None, group=None,
                  eval_set=None, eval_names=None):
        params = self._lgb_params()
        tl = params.setdefault("tree_learner", "data")
        if tl == "feature":
            raise ValueError(
                "the estimator layer partitions ROWS across workers; "
                "tree_learner=feature replicates rows and shards "
                "features — use lightgbm_tpu.distributed.train directly "
                "for that topology, or tree_learner=data|voting here")
        n = self.n_workers
        pre_partitioned = isinstance(X, (list, tuple))
        row_splits = None
        if not pre_partitioned and group is not None:
            # partition at query boundaries (dask requires group-aligned
            # partitions the same way, dask.py _train group handling)
            sizes = np.asarray(group, np.int64)
            if len(sizes) < n:
                raise ValueError(
                    f"cannot partition {len(sizes)} query groups across "
                    f"{n} workers — every worker needs at least one "
                    "whole group (reduce n_workers)")
            bounds = np.concatenate([[0], np.cumsum(sizes)])
            gsplil = np.array_split(np.arange(len(sizes)), n)
            row_splits = [np.arange(bounds[gi[0]], bounds[gi[-1] + 1])
                          for gi in gsplil]
            group = [sizes[gi] for gi in gsplil]
        xp = _split_parts(X, n, row_splits)
        yp = _split_parts(y, n, row_splits)
        wp = _split_parts(sample_weight, n, row_splits)
        gp = _split_parts(group, n, None) if group is not None \
            else [None] * n
        evs = None
        if eval_set:
            evs = []
            for tup in eval_set:
                vx, vy = tup[0], tup[1]
                vx = vx.tocsr() if _is_sparse(vx) else np.asarray(vx)
                evs.append((vx,
                            self._encode_eval_label(np.asarray(vy)), None,
                            None))
        args = {"params": params, "rounds": self.n_estimators,
                "eval_set": evs, "eval_names": eval_names}
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        results = run("lightgbm_tpu.distributed:_fit_worker",
                      num_workers=n, backend=self._dist_backend,
                      args=args,
                      rank_args=[(xp[i], yp[i], wp[i], gp[i])
                                 for i in range(n)],
                      timeout=self._dist_timeout,
                      extra_pythonpath=[repo_root])
        r0 = results[0]
        from .booster import Booster
        self._Booster = Booster(model_str=r0["model"])
        self._n_features = r0["n_features"]
        self.best_iteration_ = r0["best_iteration"]
        self.best_score_ = r0["best_score"]
        self._evals_result = r0["evals"]
        self.fitted_ = True
        self.n_iter_ = (self.best_iteration_
                        if self.best_iteration_ and self.best_iteration_ > 0
                        else self._Booster.current_iteration)
        self.objective_ = params.get("objective")
        return self

    def to_local(self):
        """The plain single-process estimator carrying the fitted model
        (dask.py to_local analog)."""
        from . import sklearn as _sk
        cls = getattr(_sk, type(self).__name__.replace("Distributed", ""))
        local = cls(**self.get_params())
        for attr in ("_Booster", "_n_features", "_classes", "_n_classes",
                     "best_iteration_", "best_score_", "_evals_result",
                     "fitted_", "n_iter_", "objective_"):
            if hasattr(self, attr):
                setattr(local, attr, getattr(self, attr))
        return local


class DistributedLGBMRegressor(_DistLGBMModel, _SkRegressor):
    """Distributed version of LGBMRegressor (dask.py:1268
    DaskLGBMRegressor analog): ``fit(X, y)`` trains over ``n_workers``
    coordinated processes; ``X``/``y`` may be global arrays (partitioned
    for you) or lists of per-worker parts (pre-distributed data)."""

    def __init__(self, *args, n_workers: int = 2, backend: str = "cpu",
                 timeout: int = 600, **kwargs):
        super().__init__(*args, **kwargs)
        self._set_dist(n_workers, backend, timeout)

    def fit(self, X, y, sample_weight=None, eval_set=None,
            eval_names=None, **_):
        y = [np.asarray(p, np.float32) for p in y] \
            if isinstance(y, (list, tuple)) \
            else np.asarray(y, np.float32)
        return self._dist_fit(X, y, sample_weight=sample_weight,
                              eval_set=eval_set, eval_names=eval_names)


class DistributedLGBMClassifier(_DistLGBMModel, _SkClassifier):
    """Distributed version of LGBMClassifier (dask.py:1092 analog)."""

    def __init__(self, *args, n_workers: int = 2, backend: str = "cpu",
                 timeout: int = 600, **kwargs):
        super().__init__(*args, **kwargs)
        self._set_dist(n_workers, backend, timeout)

    def fit(self, X, y, sample_weight=None, eval_set=None,
            eval_names=None, **_):
        parts = isinstance(y, (list, tuple))
        sizes = [len(p) for p in y] if parts else None
        y_all = np.concatenate([np.asarray(p) for p in y]) if parts \
            else np.asarray(y)
        self._classes, y_enc = np.unique(y_all, return_inverse=True)
        self._n_classes = len(self._classes)
        if self._n_classes > 2:
            self._other_params.setdefault("num_class", self._n_classes)
        if isinstance(sample_weight, (list, tuple)):
            # per-part weights concatenate for the (global) class-weight
            # multiply, then re-split with the labels below
            sample_weight = np.concatenate(
                [np.asarray(p) for p in sample_weight])
        w = self._class_weights(sample_weight, y_enc)
        y_enc = y_enc.astype(np.float32)
        if parts:
            cuts = np.cumsum(sizes)[:-1]
            y_enc = list(np.split(y_enc, cuts))
            if w is not None:
                w = list(np.split(np.asarray(w), cuts))
        return self._dist_fit(X, y_enc, sample_weight=w,
                              eval_set=eval_set, eval_names=eval_names)

    def _encode_eval_label(self, y: np.ndarray) -> np.ndarray:
        idx = np.searchsorted(self._classes, y)
        idx = np.clip(idx, 0, len(self._classes) - 1)
        if not np.array_equal(self._classes[idx], y):
            raise ValueError(
                "eval_set contains labels not present in the training "
                f"classes {list(self._classes)}")
        return idx.astype(np.float32)


class DistributedLGBMRanker(_DistLGBMModel, _SkRanker):
    """Distributed version of LGBMRanker (dask.py:1417 analog): global
    input is partitioned at query-group boundaries; pre-partitioned
    input takes one ``group`` array per part."""

    def __init__(self, *args, n_workers: int = 2, backend: str = "cpu",
                 timeout: int = 600, **kwargs):
        super().__init__(*args, **kwargs)
        self._set_dist(n_workers, backend, timeout)

    def fit(self, X, y, group=None, sample_weight=None, eval_set=None,
            eval_names=None, **_):
        if group is None:
            raise ValueError("DistributedLGBMRanker requires group")
        y = [np.asarray(p, np.float32) for p in y] \
            if isinstance(y, (list, tuple)) \
            else np.asarray(y, np.float32)
        return self._dist_fit(X, y, sample_weight=sample_weight,
                              group=group, eval_set=eval_set,
                              eval_names=eval_names)


def _main(argv: List[str]) -> None:
    """Worker bootstrap (what ``run`` spawns): init the collective
    runtime BEFORE any backend exists, then hand control to the entry."""
    import argparse
    ap = argparse.ArgumentParser(prog="python -m lightgbm_tpu.distributed")
    ap.add_argument("--entry", required=True)
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--num-workers", type=int, required=True)
    ap.add_argument("--machines", required=True)
    ap.add_argument("--result", default="")
    ap.add_argument("--args", default="")
    ap.add_argument("--rank-args", default="")
    ap.add_argument("--backend", default="cpu")
    ns = ap.parse_args(argv)

    if ns.backend == "cpu":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        from .utils.compile_cache import enable_persistent_cache
        enable_persistent_cache()

    from .parallel import launch
    entries = [m for m in ns.machines.split(",") if m]
    launch.init(coordinator_address=entries[0],
                num_processes=ns.num_workers, process_id=ns.rank)

    mod_name, fn_name = ns.entry.split(":")
    import importlib
    fn = getattr(importlib.import_module(mod_name), fn_name)
    ctx = WorkerContext(rank=ns.rank, num_workers=ns.num_workers,
                        machines=ns.machines,
                        local_listen_port=int(
                            entries[ns.rank].rsplit(":", 1)[1]))
    shared = None
    if ns.args:
        with open(ns.args, "rb") as f:
            shared = pickle.load(f)
    if ns.rank_args:
        with open(ns.rank_args, "rb") as f:
            result = fn(ctx, shared, pickle.load(f))
    elif ns.args:
        result = fn(ctx, shared)
    else:
        result = fn(ctx)
    if ns.result:
        with open(ns.result, "wb") as f:
            pickle.dump(result, f)


if __name__ == "__main__":
    _main(sys.argv[1:])
