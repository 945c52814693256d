"""Whole-run latency, queue-wait and batch-fill counters for the server.

The paper's deployment metric is the P99 latency under an SLA bound
(§IV-A).  The tracker keeps fixed log-spaced histograms of two per-query
quantities over the whole run, so a percentile sees every query the server
answered, not a recent window:

* queue wait — batch release minus enqueue, the time the batching rule
  held the query;
* latency — the end of the batch's step minus enqueue.

It also keeps the admission-queue depth left behind at each release (under
overload a no-admission configuration's latency grows linearly with it, the
signal the bounded-queue policies cap) and the batch fill: queries served
over ``batches x max_batch``.  The server feeds it once per batch with one
vectorised call.
"""
from __future__ import annotations

import numpy as np

# Histogram buckets: 100 per decade from 0.1 us to 10^4 s, so a percentile
# is the bucket's geometric centre, within 1.2% of the sample it stands for.
_PER_DECADE = 100
_LO_S = 1e-7
_DECADES = 11
_N_BUCKETS = _PER_DECADE * _DECADES


def _bucket(seconds: np.ndarray) -> np.ndarray:
    """Bucket index of each sample; below 0.1 us (zero included) and above
    10^4 s clamp into the first and last bucket."""
    with np.errstate(divide="ignore"):
        x = np.log10(np.maximum(seconds, 0.0) / _LO_S) * _PER_DECADE
    return np.clip(x, 0, _N_BUCKETS - 1).astype(np.int64)


def _percentile(counts: np.ndarray, q: float) -> float | None:
    n = int(counts.sum())
    if n == 0:
        return None
    rank = min(int(np.ceil(q / 100.0 * n)), n)
    b = int(np.searchsorted(np.cumsum(counts), max(rank, 1)))
    return _LO_S * 10 ** ((b + 0.5) / _PER_DECADE)


class LatencyTracker:
    def __init__(self, max_batch: int | None = None):
        self.max_batch = max_batch
        self.latency = np.zeros(_N_BUCKETS, np.int64)
        self.queue_wait = np.zeros(_N_BUCKETS, np.int64)
        self.batches = 0
        self.queries = 0  # served in the recorded batches
        self._depth_sum = 0
        self._depth_max = 0

    def record(self, latency_s, queue_wait_s=None) -> None:
        """Add per-query samples, in seconds (scalars or arrays)."""
        self.latency += np.bincount(_bucket(np.atleast_1d(latency_s)), minlength=_N_BUCKETS)
        if queue_wait_s is not None:
            self.queue_wait += np.bincount(
                _bucket(np.atleast_1d(queue_wait_s)), minlength=_N_BUCKETS)

    def record_batch(
        self, t_enqueue: np.ndarray, t_release: float, t_done: float, depth: int
    ) -> None:
        """One served batch: its queries' enqueue times, the release and end
        of its step, and the admission-queue depth left after the release."""
        t_enqueue = np.asarray(t_enqueue, np.float64)
        self.record(t_done - t_enqueue, t_release - t_enqueue)
        self.batches += 1
        self.queries += t_enqueue.size
        self._depth_sum += int(depth)
        self._depth_max = max(self._depth_max, int(depth))

    def percentile(self, q: float) -> float | None:
        """Latency percentile in seconds over the whole run; ``None`` (not
        NaN) with no samples yet — an idle server has *no* latency, and
        ``None`` survives JSON round-trips and ``is None`` guards where NaN
        silently poisons comparisons and formatting."""
        return _percentile(self.latency, q)

    @property
    def p50(self) -> float | None:
        return self.percentile(50)

    @property
    def p99(self) -> float | None:
        return self.percentile(99)

    @property
    def batch_fill(self) -> float | None:
        if not self.batches or not self.max_batch:
            return None
        return self.queries / (self.batches * self.max_batch)

    def summary(self) -> dict:
        def us(v):
            return None if v is None else v * 1e6

        out = {
            "p50_us": us(self.p50),
            "p99_us": us(self.p99),
            "n": int(self.latency.sum()),
            "queue_wait_p50_us": us(_percentile(self.queue_wait, 50)),
            "queue_wait_p99_us": us(_percentile(self.queue_wait, 99)),
            "batch_fill": self.batch_fill,
        }
        if self.batches:
            out["queue_depth_mean"] = self._depth_sum / self.batches
            out["queue_depth_max"] = self._depth_max
        return out
