"""Traffic from a mix file and a seed: the one generator every mix uses.

A mix (``bench/traffic/<name>.json``) is data only:

* ``keys``: the per-table key law.  ``{"law": "zipf", "alpha": a, "top_k":
  k}`` gives rank ``r`` the weight ``r**-a``; the ``k`` hottest ranks are
  drawn by weight and the rest of the mass uniformly over the other rows.
  The hot ids are scattered over the id space by a permutation drawn from
  the seed: real ids are not frequency-ordered unless a system reorders
  them.  ``{"law": "uniform"}`` draws every row alike.
* ``dense``: ``{"law": "normal"}``, N(0, 1) dense features.
* ``loop``: ``"closed"`` (one caller sends a full batch, waits for every
  answer, and repeats) or ``"open"`` (arrivals on a schedule, whatever the
  server does).  An open mix gives ``rate_qps`` (a number, or a list of
  ``[seconds, queries/s]`` phases that repeats) and ``max_wait_s``.
* ``pool_batches``: distinct batches made in set-up and cycled in the
  window; ``history_batches``: a separate sample of the same law, counted
  into the key histogram the planner is told.

The sampler is a copy of the compact Zipf form in the program's
``data/distributions.py`` (explicit hot ranks plus a uniform tail), kept
here so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import math

import numpy as np

# independent random streams drawn from one seed
STREAM_HOT_IDS, STREAM_POOL, STREAM_HISTORY, STREAM_ARRIVALS, STREAM_WEIGHTS = range(5)


def rng(seed: int, stream: int) -> np.random.Generator:
    """The numpy generator of one stream of one seed (any size of seed)."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


class ZipfTable:
    """Zipf(alpha) over the ranks of one table of ``rows`` rows."""

    def __init__(self, rows: int, alpha: float, top_k: int, g: np.random.Generator):
        k = min(int(top_k), rows)
        w = np.arange(1, k + 1, dtype=np.float64) ** (-alpha)
        if rows > k:
            if rows - k <= 1 << 20:
                r = np.arange(k + 1, rows + 1, dtype=np.float64)
                tail_w = float((r ** (-alpha)).sum())
            elif alpha != 1.0:  # Euler-Maclaurin bound for huge tables
                tail_w = ((rows + 0.5) ** (1 - alpha) - (k + 0.5) ** (1 - alpha)) / (1 - alpha)
            else:
                tail_w = math.log((rows + 0.5) / (k + 0.5))
        else:
            tail_w = 0.0
        total = float(w.sum()) + tail_w
        self.rows = rows
        self.hot_ids = g.choice(rows, size=k, replace=False).astype(np.int64)
        self.hot_p = w / total
        self.tail = tail_w / total
        self._sorted_hot = np.sort(self.hot_ids)

    def sample(self, g: np.random.Generator, shape) -> np.ndarray:
        n = int(np.prod(shape))
        out = np.empty(n, np.int64)
        hot_mass = 1.0 - self.tail
        pick = g.random(n) < hot_mass
        n_hot = int(pick.sum())
        if n_hot:
            out[pick] = self.hot_ids[g.choice(len(self.hot_ids), size=n_hot, p=self.hot_p / hot_mass)]
        n_tail = n - n_hot
        if n_tail:
            # uniform over the rows that are not hot: the j-th such row is
            # j plus the number of hot ids at or below it
            draws = g.integers(0, self.rows - len(self.hot_ids), n_tail)
            s = self._sorted_hot
            out[~pick] = draws + np.searchsorted(s - np.arange(len(s)), draws, side="right")
        return out.reshape(shape).astype(np.int32)


class UniformTable:
    def __init__(self, rows: int):
        self.rows = rows

    def sample(self, g: np.random.Generator, shape) -> np.ndarray:
        return g.integers(0, self.rows, shape).astype(np.int32)


def key_laws(keys: dict, rows, seed: int) -> list:
    """One sampler per table, for the mix's ``keys`` spec."""
    g = rng(seed, STREAM_HOT_IDS)
    if keys["law"] == "zipf":
        return [ZipfTable(int(m), float(keys["alpha"]), int(keys["top_k"]), g) for m in rows]
    if keys["law"] == "uniform":
        return [UniformTable(int(m)) for m in rows]
    raise ValueError(f"unknown key law {keys['law']!r}")


def sample_batch(laws, seqs, n_dense: int, batch: int, g: np.random.Generator):
    """One batch: (T, batch, max seq) int32 indices with -1 padding, and
    (batch, n_dense) float32 dense features."""
    s_max = max(seqs)
    idx = np.full((len(laws), batch, s_max), -1, np.int32)
    for t, (law, s) in enumerate(zip(laws, seqs)):
        idx[t, :, :s] = law.sample(g, (batch, s))
    dense = g.standard_normal((batch, n_dense)).astype(np.float32)
    return idx, dense


def make_batches(laws, seqs, n_dense: int, batch: int, n: int, g: np.random.Generator):
    return [sample_batch(laws, seqs, n_dense, batch, g) for _ in range(n)]


def key_counts(batches, n_tables: int):
    """Per table, the (ids, counts) of every key in ``batches``."""
    out = []
    for t in range(n_tables):
        flat = np.concatenate([b[0][t].ravel() for b in batches])
        out.append(np.unique(flat[flat >= 0], return_counts=True))
    return out


def distinct_per_table(indices: np.ndarray) -> list[int]:
    """u_t: the distinct ids of each table in one (T, B, s) batch."""
    return [int(np.unique(row[row >= 0]).size) for row in indices]


def _phases(rate_qps) -> list[tuple[float, float]]:
    if isinstance(rate_qps, (int, float)):
        return [(1.0, float(rate_qps))]
    return [(float(d), float(r)) for d, r in rate_qps]


def mean_rate(rate_qps) -> float:
    ph = _phases(rate_qps)
    return sum(d * r for d, r in ph) / sum(d for d, _ in ph)


def arrivals(rate_qps, n: int, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of ``n`` open-loop
    arrivals.  The gaps are the ``n`` quantiles of a unit exponential, in an
    order drawn from the seed: every seed gets the same set of gaps, so the
    same work over the same time, in another order.  The phases of
    ``rate_qps`` then map the unit-rate times onto the clock."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q)
    rng(seed, STREAM_ARRIVALS).shuffle(gaps)
    u = np.cumsum(gaps)  # unit-rate event times
    ph = _phases(rate_qps)
    period_mass = sum(d * r for d, r in ph)
    period_len = sum(d for d, _ in ph)
    cycles, rem = np.divmod(u, period_mass)
    t = cycles * period_len
    edge_mass = np.cumsum([0.0] + [d * r for d, r in ph])
    edge_time = np.cumsum([0.0] + [d for d, _ in ph])
    k = np.clip(np.searchsorted(edge_mass, rem, side="right") - 1, 0, len(ph) - 1)
    rates = np.array([r for _, r in ph])
    return t + edge_time[k] + (rem - edge_mass[k]) / rates[k]
