"""Input hardening: query-index validation policies (DESIGN.md §9).

Every lookup path treats ``-1`` as the padding sentinel — it redirects to
the packed buffer's shared zero row and contributes exactly nothing to the
pooled sum.  Anything else outside ``[0, rows)`` is *invalid traffic*: the
reference path would clamp it into a neighboring row (``jnp.take`` clip
semantics) and the partitioned paths would zero-contribute it, both
silently.  :class:`IndexValidator` makes that policy explicit per engine:

* ``clip``     — today's behavior, now explicit: indices pass through
  untouched (bit-identical outputs by construction), but out-of-vocab and
  negative counts are surfaced in ``Server.stats()`` so bad traffic is at
  least *visible*;
* ``null-row`` — invalid ids are mapped to ``-1`` (the zero row), so a bad
  id contributes nothing to pooling on **every** executor path — the
  reference path's clamp-into-a-real-row behavior included;
* ``reject``   — a query carrying any invalid id fails its own handle with
  :class:`repro.serving.server.InvalidQueryError`; the rest of the batch
  serves normally (blast radius: the offending request only).

The validator runs in the server's pump at batch-release time, on the host
(numpy) side — before any device work is spent on the batch — in one
vectorised pass over the released batch (one per distinct index shape and
dtype), not one check per query.
"""
from __future__ import annotations

from typing import Any, Mapping

import numpy as np

__all__ = ["VALIDATION_MODES", "IndexValidator", "payload_validator"]

VALIDATION_MODES = ("clip", "null-row", "reject")


class IndexValidator:
    """Validates stacked index arrays against per-table vocab sizes.

    ``rows[i]`` is table i's vocabulary size; an index array is ``(N, ...)``
    with the leading axis the table axis.  ``-1`` is the legal padding
    sentinel; ``idx < -1`` counts as ``negative`` and ``idx >= rows[i]`` as
    ``oov``, and their union is ``invalid``.
    """

    def __init__(self, rows, mode: str = "clip"):
        if mode not in VALIDATION_MODES:
            raise ValueError(
                f"unknown validation mode {mode!r}; known: {list(VALIDATION_MODES)}"
            )
        self.rows = np.asarray(rows, np.int64).reshape(-1)
        self.mode = mode

    def masks(self, idx: np.ndarray, table_axis: int = 0):
        """``(negative, oov)`` boolean masks of ``idx``, whose table axis is
        ``table_axis`` (0 for one query, 1 for a stack of queries)."""
        if idx.shape[table_axis] != self.rows.shape[0]:
            raise ValueError(
                f"index array has {idx.shape[table_axis]} tables, validator "
                f"knows {self.rows.shape[0]}"
            )
        rows = self.rows.reshape((-1,) + (1,) * (idx.ndim - table_axis - 1))
        return idx < -1, idx >= rows

    def check(self, idx) -> tuple[np.ndarray, dict]:
        """One index array -> (sanitized, counts).

        ``counts`` has ``oov`` / ``negative`` / ``invalid`` totals.  In
        ``null-row`` mode the returned array has invalid entries replaced by
        ``-1``; ``clip`` and ``reject`` return the input untouched (reject's
        enforcement happens at the request level, from ``counts``).
        """
        idx = np.asarray(idx)
        if idx.size == 0:
            return idx, {"oov": 0, "negative": 0, "invalid": 0}
        negative, oov = self.masks(idx)
        invalid = negative | oov
        counts = {
            "oov": int(oov.sum()),
            "negative": int(negative.sum()),
            "invalid": int(invalid.sum()),
        }
        if self.mode == "null-row" and counts["invalid"]:
            idx = np.where(invalid, np.array(-1, idx.dtype), idx)
        return idx, counts


def _get_indices(payload: Any) -> np.ndarray:
    # the dict test first: this runs once per query, and isinstance against
    # the Mapping ABC is far slower than against dict
    if isinstance(payload, dict) or isinstance(payload, Mapping):
        payload = payload["indices"]
    return np.asarray(payload)


def _set_indices(payload: Any, idx: np.ndarray) -> Any:
    if isinstance(payload, Mapping):
        out = dict(payload)
        out["indices"] = idx
        return out
    return idx


def payload_validator(rows, mode: str = "clip"):
    """Build the batch-level validator :class:`repro.serving.server.Server`
    calls at release time: ``payloads -> (payloads', counts, bad)`` where
    ``counts`` are the batch's oov/negative totals and ``bad`` maps the
    positions of requests to fail (``reject`` mode) to a reason string.

    The batch is checked in one vectorised pass per distinct ``(shape,
    dtype)`` of its index arrays (one pass for uniform traffic), never per
    query; each query's outcome is what :meth:`IndexValidator.check` gives
    it.  When no payload is rewritten, ``payloads'`` is ``payloads`` itself
    (the same list, the same objects)."""
    v = IndexValidator(rows, mode)

    def validate(payloads):
        arrays = [_get_indices(p) for p in payloads]
        groups: dict[tuple, list[int]] = {}
        for i, a in enumerate(arrays):
            groups.setdefault((a.shape, a.dtype), []).append(i)
        oov_total = negative_total = 0
        bad: dict[int, str] = {}
        out = payloads
        for (shape, dtype), pos in groups.items():
            if 0 in shape:
                continue  # an empty query has nothing to check
            # (n, T, ...): one concatenate is cheaper than np.stack's
            # per-array expand_dims
            stacked = np.concatenate([arrays[i] for i in pos]).reshape(
                (len(pos),) + shape
            )
            negative, oov = v.masks(stacked, table_axis=1)
            oov_total += int(oov.sum())
            negative_total += int(negative.sum())
            if v.mode == "clip":
                continue
            invalid = negative | oov
            hit = np.flatnonzero(invalid.reshape(len(pos), -1).any(axis=1)).tolist()
            if v.mode == "reject":
                n_oov = oov.reshape(len(pos), -1).sum(axis=1)
                n_neg = negative.reshape(len(pos), -1).sum(axis=1)
                for j in hit:
                    bad[pos[j]] = (
                        f"{n_oov[j]} out-of-vocab + {n_neg[j]} negative "
                        f"indices in query"
                    )
            elif hit:
                if out is payloads:
                    out = list(payloads)
                for j in hit:
                    out[pos[j]] = _set_indices(
                        payloads[pos[j]],
                        np.where(invalid[j], np.array(-1, dtype), stacked[j]),
                    )
        return out, {"oov": oov_total, "negative": negative_total}, bad

    validate.mode = mode
    return validate
