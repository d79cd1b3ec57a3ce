"""Multi-host training orchestration — the Dask-layer analog.

The reference ships a process-orchestration layer
(/root/reference/python-package/lightgbm/dask.py:393-810: allocate ports,
build the ``machines`` parameter, run one trainer per worker wired through
``LGBM_NetworkInit``; docs/Parallel-Learning-Guide.rst:45-140 for
MPI/Kubeflow).  On TPU pods the runtime already provides process bring-up,
so the analog collapses to: initialize ``jax.distributed`` (one process per
host, auto-detected on TPU), build the global mesh, and run the SAME
training call on every process with per-process data shards — SPMD instead
of a task scheduler.

Typical pod usage (same script on every host)::

    import lightgbm_tpu as lgb
    from lightgbm_tpu.parallel import launch

    launch.init()                      # no-op off-pod / single process
    shard = launch.row_shard(load_my_rows())   # this host's rows
    mappers = launch.global_bin_mappers(shard.sample(200_000), config)
    ds = lgb.Dataset(shard.x, label=shard.y, bin_mappers=mappers)
    bst = lgb.train({"tree_learner": "data", ...}, ds)
"""

from __future__ import annotations

import time
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from ..config import Config


class RowShard(NamedTuple):
    """This process's row partition.  ``weight`` and the global row
    range ``[row_start, row_stop)`` are populated by :func:`row_shard`
    (``row_stop == 0`` on direct per-host wraps where the global
    placement is unknown) — keeping row/label/weight partitioning in
    ONE authority so they cannot drift."""
    x: np.ndarray
    y: Optional[np.ndarray]
    process_index: int
    process_count: int
    weight: Optional[np.ndarray] = None
    row_start: int = 0
    row_stop: int = 0

    def sample(self, cnt: int, seed: int = 3) -> np.ndarray:
        from ..dataset import _sample_rows
        rng = np.random.RandomState(seed + self.process_index)
        n = len(self.x)
        if cnt >= n:
            return self.x
        return self.x[_sample_rows(rng, n, cnt)]


def init(coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         machines: Optional[str] = None,
         local_listen_port: int = 12400,
         retries: int = 2,
         timeout_s: float = 300.0) -> None:
    """Bring up jax.distributed (LGBM_NetworkInit / dask._train machinery
    analog).  ``machines`` accepts the reference's "ip1:port1,ip2:port2"
    parameter format (config.h machines / dask.py:700) — the first entry
    becomes the coordinator; rank is inferred by matching the local host.
    On TPU pods, call with no arguments: everything is auto-detected.

    The initialize attempt runs under the resilience layer
    (utils/resilience.py — the reference's socket linker retries its
    connect loop the same way, network/linkers_socket.cpp):
    ``retries`` jittered-backoff re-attempts for classified-transient
    failures (UNAVAILABLE, timeouts, refused connections), a hard
    ``timeout_s`` deadline, and a faulthandler watchdog so a hung
    bring-up dumps stacks instead of hanging silently.  Fatal errors
    (bad arguments) surface immediately.

    MUST run before any other JAX call (jax.distributed.initialize refuses
    to run once XLA backends exist) — so no jax.* probing happens here
    before the initialize attempt."""
    import jax

    from ..utils import faultinject
    from ..utils.resilience import RetryPolicy, Watchdog, retry_call

    if getattr(init, "_done", False):
        return
    if machines:
        entries = [m.strip() for m in machines.split(",") if m.strip()]
        if coordinator_address is None:
            coordinator_address = entries[0]
        if num_processes is None:
            num_processes = len(entries)
        if process_id is None:
            import socket
            names = {socket.gethostname(), "127.0.0.1", "localhost"}
            try:
                names.add(socket.gethostbyname(socket.gethostname()))
            except OSError:
                pass
            process_id = next(
                (i for i, e in enumerate(entries)
                 if e.rsplit(":", 1)[0] in names), None)
            if process_id is None:
                raise ValueError(
                    f"local host not found in machines={machines!r}")
    fail_t = getattr(init, "_fail_t", None)
    if coordinator_address is None and fail_t is not None \
            and timeout_s > 0 \
            and time.monotonic() - fail_t < timeout_s:
        # a recent AUTO bring-up failure: proceed solo without burning
        # another full retry/watchdog budget per train() call.  The
        # pre-elastic code latched _done here PERMANENTLY; a cooldown
        # (one deadline's worth) keeps the failure retryable for the
        # elastic ladder without re-paying the deadline every call
        return

    def _bring_up():
        faultinject.check("device_claim")
        if coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        else:
            jax.distributed.initialize()

    policy = RetryPolicy.for_bringup(retries, timeout_s)
    try:
        with Watchdog(timeout_s, label="jax.distributed bring-up"):
            retry_call(_bring_up, policy=policy,
                       label="jax.distributed bring-up")
        # latched ONLY on successful bring-up: a failed or timed-out
        # initialize must stay retryable — the elastic recovery ladder
        # (parallel/elastic.py) re-attempts bring-up after a claim
        # wedge, and a latched failure would permanently short-circuit
        # every later attempt into the degraded path
        init._done = True
        init._fail_t = None
    except (RuntimeError, ValueError) as e:
        if coordinator_address is not None:
            # an explicitly-requested multi-host launch failing must be
            # loud: silently degrading to single-process would later hang
            # in collectives or fit divergent bin mappers per host
            raise RuntimeError(
                f"jax.distributed.initialize failed for explicit "
                f"coordinator {coordinator_address!r}: {e}") from e
        # auto-detect path on single-process / already-initialized
        # runtimes: proceed solo, the same way the reference CLI falls
        # back to serial when num_machines=1 — but say so (and do NOT
        # latch _done: the next caller may retry the bring-up once the
        # cooldown above lapses)
        init._fail_t = time.monotonic()
        from ..utils.log import Log
        Log.warning(f"jax.distributed auto-init unavailable ({e}); "
                    "continuing single-process")


def row_shard(x: np.ndarray, y: Optional[np.ndarray] = None,
              process_index: Optional[int] = None,
              process_count: Optional[int] = None,
              weight: Optional[np.ndarray] = None) -> RowShard:
    """Deterministic contiguous row partition of a globally-loaded array
    (the per-rank partitioning of dataset_loader.cpp:203-298).  When data
    is already loaded per-host, wrap it in a RowShard directly."""
    import jax
    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    parts = np.array_split(np.arange(len(x)), pc)
    idx = parts[pi]
    return RowShard(x=x[idx], y=None if y is None else y[idx],
                    process_index=pi, process_count=pc,
                    weight=None if weight is None
                    else np.asarray(weight)[idx],
                    row_start=int(idx[0]) if len(idx) else 0,
                    row_stop=int(idx[-1]) + 1 if len(idx) else 0)


def global_bin_mappers(local_sample: np.ndarray, config: Config,
                       cat_idx: Optional[set] = None,
                       allgather: Optional[Callable] = None) -> List:
    """Globally-consistent bin mappers from per-host samples
    (dist_data.distributed_bin_mappers; dataset_loader.cpp:1104-1186)."""
    from .dist_data import distributed_bin_mappers
    return distributed_bin_mappers(local_sample, config, cat_idx=cat_idx,
                                   allgather=allgather)
