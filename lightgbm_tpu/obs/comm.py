"""Static bytes-on-the-wire accounting for collective call sites.

"GPU-acceleration for Large-scale Tree Boosting" (arXiv:1706.08359)
validates its scaling claims by instrumenting bytes moved per
iteration; the reference's distributed learners get the same number
implicitly from their hand-rolled ReduceScatter buffers.  Here the
collectives are XLA ops inside jitted shard_map programs, so runtime
counting would need host syncs — instead the byte math is derived
STATICALLY from the traced shapes: a ``CommLedger`` wraps each
``lax.psum`` / ``psum_scatter`` / ``all_gather`` call site, records
(site, collective, payload bytes, wire-byte estimate, cadence) once at
trace time, and returns the *identical* lax op.  Zero runtime cost,
zero extra syncs; registration re-runs idempotently on retrace.

Wire-byte model (ring algorithms, the standard cost model XLA's ICI
collectives follow to within the protocol constant):

- ``psum`` (all-reduce):        ``2 * (n-1)/n * payload`` per chip
- ``psum_scatter``:             ``(n-1)/n * input payload`` per chip
- ``all_gather``:               ``(n-1)/n * output payload`` per chip

Cadence tells the host-side accounting how often a site executes:
``"step"`` sites run once per grower super-step (histogram reduce,
best-split sync), ``"tree"`` sites once per tree (root totals) — the
driver multiplies by the fetched ``n_steps`` it already holds, so the
per-iteration counters cost nothing beyond arithmetic.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

from jax import lax


class CommSite(NamedTuple):
    site: str             # stable call-site name, e.g. "dp.hist_reduce"
    collective: str       # psum | psum_scatter | all_gather
    payload_bytes: int    # tensor bytes entering the collective
    wire_bytes: int       # estimated bytes crossing the interconnect/chip
    axis_size: int
    cadence: str          # "step" | "tree"


def _nbytes(x: Any) -> int:
    """Tensor bytes from a traced value or pytree of traced values."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(x):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = getattr(dtype, "itemsize", 4) if dtype is not None else 4
        total += int(math.prod(shape)) * itemsize
    return total


def wire_bytes(collective: str, payload: int, n: int) -> int:
    """Per-chip wire bytes under the ring model (module docstring).
    ``pmax`` follows the all-reduce cost (same ring, max combiner)."""
    if n <= 1:
        return 0
    frac = (n - 1) / n
    if collective in ("psum", "pmax"):
        return int(2 * frac * payload)
    # psum_scatter: payload = input bytes; all_gather: payload = OUTPUT
    # bytes (n * input) — callers pass the right one
    return int(frac * payload)


class CommLedger:
    """Per-grower collective ledger.  Builders create one, route their
    collectives through it, and attach it to the grower callable as
    ``comm`` so the driver can read the static site table."""

    def __init__(self, axis_size: int):
        self.axis_size = int(axis_size)
        self._sites: Dict[str, CommSite] = {}

    def _record(self, site: str, collective: str, payload: int,
                cadence: str, wire_payload: int = None) -> None:
        self._sites[site] = CommSite(
            site=site, collective=collective, payload_bytes=payload,
            wire_bytes=wire_bytes(collective,
                                  payload if wire_payload is None
                                  else wire_payload, self.axis_size),
            axis_size=self.axis_size, cadence=cadence)

    # -- wrapped collectives (identical semantics to the lax ops) -------
    def psum(self, x, axis_name: str, *, site: str,
             cadence: str = "step"):
        self._record(site, "psum", _nbytes(x), cadence)
        return lax.psum(x, axis_name)

    def pmax(self, x, axis_name: str, *, site: str,
             cadence: str = "step"):
        self._record(site, "pmax", _nbytes(x), cadence)
        return lax.pmax(x, axis_name)

    def psum_scatter(self, x, axis_name: str, *, site: str,
                     cadence: str = "step", **kw):
        self._record(site, "psum_scatter", _nbytes(x), cadence)
        return lax.psum_scatter(x, axis_name, **kw)

    def all_gather(self, x, axis_name: str, *, site: str,
                   cadence: str = "step", **kw):
        payload = _nbytes(x)
        # wire model wants OUTPUT bytes for all_gather
        self._record(site, "all_gather", payload, cadence,
                     wire_payload=payload * self.axis_size)
        return lax.all_gather(x, axis_name, **kw)

    def note_all_gather(self, x, *, site: str, cadence: str = "step",
                        copies: int = 1) -> None:
        """Record an all_gather performed elsewhere (ops/split.py
        ``gather_best`` stays collective-owning; the learner builders
        note its payload here at trace time).  ``copies``: how many of
        ``x`` one execution gathers, where the site is traced under a
        ``vmap`` that hides the batch from ``x``'s shape."""
        payload = _nbytes(x) * int(copies)
        self._record(site, "all_gather", payload, cadence,
                     wire_payload=payload * self.axis_size)

    # -- reading --------------------------------------------------------
    def sites(self) -> Tuple[CommSite, ...]:
        return tuple(self._sites[k] for k in sorted(self._sites))

    def bytes_per_iteration(self, n_steps: int) -> int:
        """Estimated wire bytes for one boosting iteration that ran
        ``n_steps`` grower loop steps."""
        return sum(s.wire_bytes * (n_steps if s.cadence == "step" else 1)
                   for s in self.sites())


def dp_hist_bytes_per_iter(n_shards: int, chunk: int, padded_bins: int,
                           n_steps: int, split_batch: int = 1,
                           itemsize: int = 4) -> int:
    """Closed-form wire-byte estimate for the data-parallel owner-shard
    histogram reduce-scatter over one iteration — the PR 1 per-shard
    hist-bytes math (``OwnerShardPlan.hist_bytes``) times the reduce
    cadence, usable without building a mesh.  The
    scattered tensor per step is ``[n_shards * chunk * split_batch,
    padded_bins, 3]`` at ``itemsize``-byte lanes: f32 for the default
    path, int32 for quantized training (quant_train) — 4 bytes either
    way, HALF the reference's f64 ``ReduceScatter`` wire format (its
    hist_t is double; see docs/Quantized-Training.md for why a 16-bit
    wire format is unsafe: local per-bin sums need 8 + log2(rows)
    bits, so int16 lanes would wrap on any real shard)."""
    payload = (n_shards * chunk * split_batch * padded_bins * 3
               * int(itemsize))
    return wire_bytes("psum_scatter", payload, n_shards) * n_steps
