"""Seeded inputs of Epsilon's shape: wide, dense, normalised features and
two balanced classes, without Epsilon's bytes, since the run has no network.
A configuration names its generator (``data.generator``) and ``run.py``
finds ``generators/<name>.py`` by that name, so a cell whose columns are of
another kind adds a file here.
"""

import numpy as np


def make(n_rows: int, n_features: int, seed: int):
    """Epsilon's shape: wide, dense, normalised features and a label that
    is linear in the first 16 of them plus noise, drawn in float32 row
    chunks so that the host never holds a float64 copy."""
    if n_features < 16:
        raise ValueError("epsilon_like needs at least 16 features")
    rng = np.random.default_rng(int(seed))
    w = rng.standard_normal(16, dtype=np.float32)
    x = np.empty((n_rows, n_features), np.float32)
    chunk = max(1, 50_000_000 // n_features)
    for lo in range(0, n_rows, chunk):
        rng.standard_normal(out=x[lo:lo + chunk], dtype=np.float32)
    noise = rng.standard_normal(n_rows, dtype=np.float32)
    y = (x[:, :16] @ w + 0.5 * noise > 0).astype(np.float32)
    return x, y

