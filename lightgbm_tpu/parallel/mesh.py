"""Device-mesh construction for distributed training.

Replaces the reference's whole communication stack
(/root/reference/src/network/: hand-rolled Bruck allgather
network.cpp:156, recursive-halving reduce-scatter :249, socket/MPI linkers)
with ``jax.sharding.Mesh`` + XLA collectives over ICI/DCN — the schedule is
owned by the compiler (SURVEY.md §2.5 TPU mapping).  Multi-host
initialization goes through ``jax.distributed`` (the ``LGBM_NetworkInit``
analog, c_api.h:1350) which wires the same collectives across hosts.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(shape: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = ("data",),
              devices=None) -> Mesh:
    """Build a mesh over the available devices.

    shape=None uses all devices on one ``data`` axis (the GBDT scale axis —
    rows; SURVEY.md §2.6: data-parallel is the reference's main distributed
    mode, docs/Experiments.rst Criteo scaling).
    """
    devs = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devs),)
    shape = tuple(int(s) for s in shape)
    n = int(np.prod(shape))
    if n > len(devs):
        raise ValueError(f"mesh needs {n} devices, have {len(devs)}")
    mesh_devs = np.asarray(devs[:n]).reshape(shape)
    if len(axis_names) != len(shape):
        axis_names = tuple(f"axis{i}" for i in range(len(shape)))
    return Mesh(mesh_devs, tuple(axis_names))


def default_mesh(num: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    num = num or len(devs)
    return make_mesh((num,), ("data",), devs)


class OwnerShardPlan(NamedTuple):
    """Owner-shard chunking of the histogram (feature-group) axis for the
    data-parallel reduce-scatter (data_parallel_tree_learner.cpp:174-186:
    after ``Network::ReduceScatter`` each rank holds only ITS features'
    global histograms).

    chunk:      histogram rows owned per shard, ``ceil(G / n_shards)``
                (G = EFB group count, or F without bundling) — the dp
                grower's per-shard histogram carry is [L, 3, chunk, B]
    fmax:       split-scan width per shard = max features owned by any
                shard (> chunk only when EFB bundles several features
                into one owned group)
    shard_feat: [n_shards, fmax] int32 — GLOBAL feature id behind each
                shard's local scan slot; -1 = padding (scan-masked)
    """
    chunk: int
    fmax: int
    shard_feat: np.ndarray

    @property
    def n_shards(self) -> int:
        return self.shard_feat.shape[0]

    def hist_bytes(self, num_leaves: int, padded_bins: int,
                   scratch: int = 0) -> int:
        """Per-shard histogram-state bytes at a leaf budget (f32 g/h/c)."""
        return (num_leaves + scratch) * self.chunk * padded_bins * 3 * 4


def owner_shard_plan(group_of: np.ndarray, n_shards: int) -> OwnerShardPlan:
    """Partition the histogram axis (EFB groups; features when unbundled,
    where ``group_of`` is the identity) into ``n_shards`` equal chunks and
    map every owned group back to its global feature ids.  Host-side and
    cheap — computed once per (feature count, mesh) pair."""
    group_of = np.asarray(group_of, np.int64)
    g = int(group_of.max()) + 1 if group_of.size else 1
    chunk = -(-g // n_shards)
    owned = [np.nonzero((group_of >= s * chunk)
                        & (group_of < (s + 1) * chunk))[0]
             for s in range(n_shards)]
    fmax = max(1, max(len(o) for o in owned))
    shard_feat = np.full((n_shards, fmax), -1, np.int32)
    for s, o in enumerate(owned):
        shard_feat[s, :len(o)] = o
    return OwnerShardPlan(chunk=chunk, fmax=fmax, shard_feat=shard_feat)


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     retries: int = 2,
                     timeout_s: float = 300.0) -> None:
    """Multi-host bring-up (jax.distributed) — the ``Network::Init`` /
    ``LGBM_NetworkInit`` analog (network.cpp, c_api.h:1350).  On TPU pods
    arguments are auto-detected from the runtime environment.

    Runs under the resilience layer (utils/resilience.py): transient
    bring-up failures are retried ``retries`` times with jittered
    backoff under a ``timeout_s`` deadline, and a faulthandler watchdog
    dumps all-thread stacks if the blocking initialize wedges (the
    round-5 failure mode: a 10 h silent hang)."""
    from ..utils import faultinject
    from ..utils.resilience import RetryPolicy, Watchdog, retry_call

    kwargs = {}
    if coordinator_address is not None:
        kwargs.update(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)

    def _bring_up():
        faultinject.check("device_claim")
        jax.distributed.initialize(**kwargs)

    policy = RetryPolicy.for_bringup(retries, timeout_s)
    with Watchdog(timeout_s, label="jax.distributed bring-up"):
        retry_call(_bring_up, policy=policy,
                   label="jax.distributed bring-up")
