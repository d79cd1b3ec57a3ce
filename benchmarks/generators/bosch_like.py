"""Seeded inputs of the Bosch production-line table's shape and holes, not
its bytes (the run has no network).

What this takes from Bosch (Kaggle, "Bosch Production Line Performance",
the third dataset of LightGBM's docs/GPU-Performance.rst: 1,000,000 train /
183,747 test x 968 numeric, binary), all of it a builder's recollection,
unchecked: a part moves along one of 4 lines through some of ~50 stations,
and a station's measurements are columns of their own, so a column is
missing exactly where its station was not passed: whole blocks of columns
are missing together, about 81% of all cells; where a station was passed its
columns are dense and real-valued; and about 0.58% of the parts fail.  Here
the columns are cut into ``stations_of(n_features)`` blocks of (all but)
equal width, the stations into four lines by Bosch's shares of stations
(``LINE_SHARE``), and a row passes a station of its own line with
probability ``P_OWN`` and any other with ``P_OTHER``.  What it does not
take: Bosch's unequal station widths, its columns of few distinct values,
the duplicated measurements, the flows' fixed station sequences.

The label is drawn, a part failing with log odds ``OFFSET`` plus what
speaks against it, and that depends on present values *and* on which
stations were passed, so a node's default direction carries signal and both
directions appear in a model's trees: on the first column of the stations at
``HIGH_FAILS`` of the way along a high reading raises the odds (the missing
rows belong with the low readings: left), on those at ``LOW_FAILS`` a low
reading does (the missing rows belong right), and having passed the stations
at ``REWORK`` raises them whatever was measured there.

Every chunk of rows has a stream of its own from ``(seed, chunk)``, as
``higgs_like``'s have.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 16
THREADS = 8
MOST_STATIONS = 52
LEAST_STATION_WIDTH = 8
LINE_SHARE = (24 / 52, 2 / 52, 3 / 52, 23 / 52)   # of the stations, a line
P_OWN, P_OTHER = 0.375, 0.06      # a station passed, on and off the row's line
# stations the label reads, by their place along the stations (0 to 1), and
# the weight of each in the label's margin
HIGH_FAILS = ((0.10, 1.3), (0.70, 0.6))
LOW_FAILS = ((0.30, 0.8), (0.90, 0.5))
REWORK = ((0.50, 0.8), (0.05, 0.5))
OFFSET = -5.7         # a part's log odds of failing with nothing against it
LEAST_FEATURES = 4 * LEAST_STATION_WIDTH     # a station a line


def stations_of(n_features: int) -> int:
    return min(MOST_STATIONS, n_features // LEAST_STATION_WIDTH)


def layout(n_features: int):
    """``(first column of every station and the end of the last, line of
    every station)``."""
    n_st = stations_of(n_features)
    starts = np.r_[0, np.cumsum([len(part) for part in np.array_split(
        np.arange(n_features), n_st)])]
    # the lines by Bosch's shares of the stations, each at least one station
    ends = np.round(np.cumsum(LINE_SHARE) * n_st).astype(int)
    for k in range(4):
        ends[k] = min(max(ends[k], (ends[k - 1] if k else 0) + 1),
                      n_st - 3 + k)
    line = np.searchsorted(ends, np.arange(n_st), side="right")
    return starts, line


def place(share: float, n_st: int) -> int:
    return min(n_st - 1, int(share * n_st))


def make_chunk(seed: int, chunk: int, starts, line, x_out, y_out):
    """Rows ``chunk * CHUNK_ROWS`` on, from a stream of the chunk's own."""
    rng = np.random.default_rng([int(seed), chunk])
    rows, n_st = len(y_out), len(line)
    # a row's line by the lines' shares of the stations
    own = line[rng.integers(0, n_st, rows)][:, None] == line[None, :]
    passed = rng.random((rows, n_st), dtype=np.float32) \
        < np.where(own, np.float32(P_OWN), np.float32(P_OTHER))
    x_out.fill(np.nan)
    for s in range(n_st):           # values are drawn where they are present
        at = np.flatnonzero(passed[:, s])
        x_out[at, starts[s]:starts[s + 1]] = rng.standard_normal(
            (len(at), starts[s + 1] - starts[s]), dtype=np.float32)
    margin = np.full(rows, OFFSET, np.float32)
    for sign, reads in ((1.0, HIGH_FAILS), (-1.0, LOW_FAILS)):
        for share, weight in reads:
            s = place(share, n_st)
            margin += np.float32(sign * weight) * np.where(
                passed[:, s], x_out[:, starts[s]], np.float32(0))
    for share, weight in REWORK:
        margin += np.float32(weight) * passed[:, place(share, n_st)]
    y_out[:] = rng.random(rows, dtype=np.float32) < 1 / (1 + np.exp(-margin))


def make(n_rows: int, n_features: int, seed: int):
    """``x`` float32 ``[n_rows, n_features]`` with NaN where a row did not
    pass a column's station, and ``y`` float32 of 0 and 1."""
    if n_features < LEAST_FEATURES:
        raise ValueError(f"bosch_like needs at least {LEAST_FEATURES} "
                         "features")
    starts, line = layout(n_features)
    x = np.empty((n_rows, n_features), np.float32)
    y = np.empty(n_rows, np.float32)
    with ThreadPoolExecutor(THREADS) as pool:
        jobs = [pool.submit(make_chunk, seed, k, starts, line,
                            x[lo:lo + CHUNK_ROWS], y[lo:lo + CHUNK_ROWS])
                for k, lo in enumerate(range(0, n_rows, CHUNK_ROWS))]
        for job in jobs:
            job.result()
    return x, y
