"""Distributed dataset construction: sketch-merged bin-mapper fitting.

Analog of the reference's distributed binning
(/root/reference/src/io/dataset_loader.cpp:1104-1186), upgraded to the
shape arXiv:1804.06755 ("Exact Distributed Training ... Billions of
Examples") prescribes: every process folds its OWN ROWS into mergeable
per-feature quantile sketches (``binning.QuantileSketch``), the
serialized sketches are allgathered, and every process deterministically
merges them in rank order and fits FindBin over the merged summaries —
so the global bin bounds see EVERY row of every shard, no host ever
materializes another shard's samples, and the wire carries
capacity-bounded sketches instead of raw sample matrices
(arXiv:1611.01276's ship-summaries-not-samples argument).

The legacy feature-sharded mode (``method="shard"``: features split
across ranks, each rank FindBins its slice on its LOCAL rows only, then
mappers are allgathered) is retained for comparison; its bounds only
reflect the fitting rank's shard.

Wire format: every allgathered payload is framed —
``LGTF | version u16 | length u64 | sha256[32] | body`` — and unframing
VERIFIES before unpickling (:func:`frame_payload` /
:func:`unframe_payload`).  A corrupt or truncated peer payload raises
:class:`PayloadIntegrityError`, whose message carries the resilience
classifier's retryable patterns so ``elastic.failure_kind`` classifies
it instead of the process dying inside arbitrary unpickle behavior.

The collective rides jax.distributed (multihost_utils.process_allgather)
instead of the reference's hand-rolled socket Allgather (network.cpp:156);
an injectable ``allgather`` hook keeps it testable in-process.
"""

from __future__ import annotations

import hashlib
import pickle
from typing import Callable, List, Optional

import numpy as np

from ..binning import (BinMapper, BinType, QuantileSketch,
                       fit_mappers_from_sketches, sketch_features)
from ..config import Config

# framed-payload wire format (docs/Distributed-Learning.md)
_FRAME_MAGIC = b"LGTF"
_FRAME_VERSION = 1
_HEADER_LEN = len(_FRAME_MAGIC) + 2 + 8 + 32

# running count of payload bytes this process has allgathered for
# binning
_WIRE_BYTES = {"sent": 0}


def wire_bytes_sent() -> int:
    """Framed binning payload bytes this process has sent (monotonic)."""
    return _WIRE_BYTES["sent"]


def reset_wire_bytes() -> None:
    _WIRE_BYTES["sent"] = 0


class PayloadIntegrityError(RuntimeError):
    """An allgathered peer payload failed framing verification.  The
    message deliberately matches the resilience classifier's retryable
    patterns (UNAVAILABLE) — a torn payload is a transport failure the
    elastic ladder may retry/shrink around, not a programming error."""

    def __init__(self, detail: str):
        super().__init__(
            f"UNAVAILABLE: corrupt allgathered payload ({detail})")


def frame_payload(body: bytes) -> bytes:
    """``LGTF | version | length | sha256 | body`` — self-verifying."""
    return (_FRAME_MAGIC
            + _FRAME_VERSION.to_bytes(2, "little")
            + len(body).to_bytes(8, "little")
            + hashlib.sha256(body).digest()
            + body)


def unframe_payload(blob: bytes) -> bytes:
    """Verify and strip a :func:`frame_payload` frame.  Raises
    :class:`PayloadIntegrityError` on magic/version/length/sha mismatch
    — BEFORE any byte of the body reaches ``pickle.loads``."""
    if len(blob) < _HEADER_LEN:
        raise PayloadIntegrityError(
            f"truncated header: {len(blob)} bytes < {_HEADER_LEN}")
    if blob[:4] != _FRAME_MAGIC:
        raise PayloadIntegrityError(f"bad magic {blob[:4]!r}")
    version = int.from_bytes(blob[4:6], "little")
    if version != _FRAME_VERSION:
        raise PayloadIntegrityError(
            f"unsupported frame version {version}")
    n = int.from_bytes(blob[6:14], "little")
    body = blob[_HEADER_LEN:_HEADER_LEN + n]
    if len(body) != n:
        raise PayloadIntegrityError(
            f"truncated body: header says {n} bytes, got {len(body)}")
    if hashlib.sha256(body).digest() != blob[14:46]:
        raise PayloadIntegrityError("sha256 mismatch")
    return body


def shard_features(num_features: int, num_machines: int):
    """Contiguous balanced feature slices (dataset_loader.cpp:1106-1117)."""
    step = max((num_features + num_machines - 1) // num_machines, 1)
    start, length = [0] * num_machines, [0] * num_machines
    for i in range(num_machines - 1):
        length[i] = min(step, num_features - start[i])
        start[i + 1] = start[i] + length[i]
    length[num_machines - 1] = num_features - start[num_machines - 1]
    return start, length


def _jax_allgather_bytes(payload: bytes) -> List[bytes]:
    """Variable-length byte allgather over jax.distributed processes."""
    import jax
    from jax.experimental import multihost_utils

    arr = np.frombuffer(payload, np.uint8)
    n = np.int64(len(arr))
    sizes = np.asarray(multihost_utils.process_allgather(n))
    maxlen = int(sizes.max())
    padded = np.zeros(maxlen, np.uint8)
    padded[:len(arr)] = arr
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    gathered = gathered.reshape(jax.process_count(), maxlen)
    return [gathered[i, :int(sizes[i])].tobytes()
            for i in range(jax.process_count())]


def _exchange(obj, allgather: Callable[[bytes], List[bytes]]) -> List:
    """pickle -> frame -> allgather -> verify each peer -> unpickle."""
    payload = frame_payload(pickle.dumps(obj, protocol=4))
    _WIRE_BYTES["sent"] += len(payload)
    out = []
    for rank, blob in enumerate(allgather(payload)):
        try:
            body = unframe_payload(blob)
        except PayloadIntegrityError as e:
            raise PayloadIntegrityError(
                f"rank {rank}: {e}") from None
        out.append(pickle.loads(body))
    return out


def distributed_bin_mappers(
        local_sample: np.ndarray, config: Config,
        cat_idx: Optional[set] = None,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        allgather: Optional[Callable[[bytes], List[bytes]]] = None,
        method: str = "sketch",
) -> List[BinMapper]:
    """Fit globally-consistent bin mappers from per-process row shards.

    local_sample: this process's sampled raw rows [n_local_sample, F]
    Returns the full list of F bin mappers, identical on every process.

    ``method="sketch"`` (default): every process sketches ALL features
    over its rows; sketches are allgathered and merged in rank order —
    deterministic, sees every shard's rows, wire size bounded by
    ``ingest_sketch_size``.  ``method="shard"``: the legacy
    feature-sharded FindBin (each feature's bounds reflect one rank's
    rows only).
    """
    cat_idx = cat_idx or set()
    if process_index is None or process_count is None:
        import jax
        process_index = jax.process_index()
        process_count = jax.process_count()
    if allgather is None:
        allgather = _jax_allgather_bytes
    if method == "sketch":
        return _sketch_bin_mappers(local_sample, config, cat_idx,
                                   allgather)
    if method != "shard":
        raise ValueError(f"unknown distributed binning method "
                         f"{method!r} (want sketch or shard)")

    f_total = local_sample.shape[1]
    start, length = shard_features(f_total, process_count)
    lo = start[process_index]
    hi = lo + length[process_index]
    own: List[dict] = []
    n = len(local_sample)
    mbf = config.max_bin_by_feature
    for f in range(lo, hi):
        m = BinMapper()
        mb = int(mbf[f]) if mbf else config.max_bin
        bt = BinType.CATEGORICAL if f in cat_idx else BinType.NUMERICAL
        m.find_bin(local_sample[:, f], n, mb, config.min_data_in_bin,
                   min_split_data=config.min_data_in_leaf,
                   pre_filter=config.feature_pre_filter, bin_type=bt,
                   use_missing=config.use_missing,
                   zero_as_missing=config.zero_as_missing)
        own.append(m.to_state())
    shards = _exchange(own, allgather)
    mappers: List[BinMapper] = []
    for states in shards:
        for st in states:
            mappers.append(BinMapper.from_state(st))
    if len(mappers) != f_total:
        raise RuntimeError(
            f"distributed binning produced {len(mappers)} mappers for "
            f"{f_total} features — rank slices out of sync")
    return mappers


def _sketch_bin_mappers(local_sample: np.ndarray, config: Config,
                        cat_idx: set,
                        allgather: Callable[[bytes], List[bytes]]
                        ) -> List[BinMapper]:
    f_total = local_sample.shape[1]
    cap = int(getattr(config, "ingest_sketch_size", 2048))
    own = [QuantileSketch(cap, categorical=(f in cat_idx))
           for f in range(f_total)]
    sketch_features(np.asarray(local_sample, np.float64), own)
    shards = _exchange([s.to_state() for s in own], allgather)
    merged: Optional[List[QuantileSketch]] = None
    for rank, states in enumerate(shards):
        if len(states) != f_total:
            raise PayloadIntegrityError(
                f"rank {rank} sent {len(states)} sketches for "
                f"{f_total} features")
        sks = [QuantileSketch.from_state(st) for st in states]
        if merged is None:
            merged = sks
        else:
            # rank-order merge: identical on every process, so the
            # fitted bounds are byte-identical fleet-wide
            for m, s in zip(merged, sks):
                m.merge(s)
    assert merged is not None
    return fit_mappers_from_sketches(merged, config, cat_idx)
