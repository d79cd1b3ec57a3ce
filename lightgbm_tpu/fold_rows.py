"""One ``lgb.cv`` call's training matrix on the device, and its folds' rows
gathered there.

``lgb.cv`` builds one Booster a fold, and each Booster places its fold's
binned rows: four fifths of the training set for training and one fifth,
padded to its row bucket, held out.  Gathered on the host and sent one by
one, five folds send the matrix four times over.  Here the call's binned
matrix is sent once, by the first Booster that asks, in the layout and on
the devices its grower reads, and each fold's matrices are one jitted
gather of that placement: the same bytes, the same shape and the same
placement as the host's gather would give, so the grower runs the same
program on the same input.  The call drops the placement once its folds
are set up (``close``).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .obs.trace import fence

# the device scope of the gathers (benchmarks' ``unscoped_share``)
SCOPE = "lgbtpu.cv.fold_rows"


@functools.partial(jax.jit, static_argnums=2)
def _gather(matrix, rows, col_pad: int):
    """``matrix[rows]``, a row past the end reading as bin 0, with
    ``col_pad`` zero columns appended."""
    with jax.named_scope(SCOPE):
        out = jnp.take(matrix, rows, axis=0, mode="fill", fill_value=0)
        if col_pad:
            out = jnp.pad(out, ((0, 0), (0, col_pad)))
    return out


def _put(x, sharding):
    """``x`` placed as the Booster places a matrix: on the default device
    (``sharding`` None) or committed to ``sharding``."""
    return jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)


class FoldRows:
    """The placements of ``parent``'s binned matrix made for one
    ``lgb.cv`` call, keyed by layout and sharding."""

    def __init__(self, parent):
        self.parent = parent
        self.binned = parent.binned
        self._placed: Dict[Tuple[bool, object], jax.Array] = {}

    def serves(self, ds) -> bool:
        """Whether ``ds``'s binned rows are still rows of this call's
        matrix, ungathered: a ``subset`` of it whose ``binned`` nothing has
        read or replaced."""
        rows = ds._binned_rows
        return self.binned is not None and rows is not None \
            and rows[0] is self.binned

    def place(self, grouped: bool, sharding, obs=None) -> None:
        """Send the matrix in the layout the grower reads (EFB groups where
        ``grouped``, else one column a feature) where ``sharding`` says,
        once a call: a ``booster.to_device`` span of its own (``what=
        "parent"``) in the session of the Booster that asks first.  Fenced,
        unlike a Booster's own placements: every fold's matrices are
        gathered from it, so the device waits for it whatever the host does
        meanwhile, and the wait falls inside the span that names it."""
        key = (grouped, sharding)
        if key in self._placed:
            return
        if obs is not None:
            sp = obs.span("booster.to_device", what="parent")
        host = self.binned if grouped else self.parent.feature_binned()
        self._placed[key] = fence(_put(host, sharding))
        if obs is not None:
            obs.end_to_device(sp, self._placed[key])

    def take(self, ds, grouped: bool, sharding, pad_rows: int = 0,
             col_pad: int = 0):
        """``ds``'s matrix gathered from the placement (``place`` first),
        with ``pad_rows`` rows of bin 0 below and ``col_pad`` columns of
        bin 0 beside; and the row numbers that were sent for it."""
        idx = ds._binned_rows[1]
        if pad_rows:
            idx = np.concatenate(
                [idx, np.full(pad_rows, len(self.binned), idx.dtype)])
        rows = _put(idx.astype(np.int32), sharding)
        return _gather(self._placed[(grouped, sharding)], rows,
                       col_pad), rows

    def close(self) -> None:
        """Drop the placements: the folds hold their own matrices."""
        self._placed.clear()
