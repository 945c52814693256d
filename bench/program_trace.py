"""What the program itself names in a profiler trace of the served path.

* Host spans: the ``jax.profiler.TraceAnnotation``s the program opens per
  released batch, all named ``repro.*`` (``Server.pump``: validate, step,
  complete; the DLRM step of ``launch/serve.py``: stage, dispatch, wait,
  fetch).  They sit on the trace's clock, nested inside the harness's
  ``bench.pump``.  ``bench/trace.py`` keeps only the harness's own spans,
  so these are read from the profile here.
* Device name scopes: ``tower`` (``models/dlrm.py`` ``forward_packed``) and
  ``lookup_prep`` (``core/partition.py`` ``_fused_asym_lookup``) appear in
  the op-name path of every device op under them, which
  ``bench.trace.Reduced`` already holds per op.

A program without these spans or scopes reads ``None`` throughout.
"""
from __future__ import annotations

import re

from bench import trace as trace_lib

PROGRAM_PREFIX = "repro."


def program_spans(pd) -> list:
    """``[(name, start_ns, end_ns)]`` of the program's host spans in a
    ``jax.profiler.ProfileData``, by start."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if ev.name.startswith(PROGRAM_PREFIX)]
    return sorted(spans, key=lambda s: s[1])


def span_ms(spans, name: str, n_batches: int) -> float | None:
    """Wall time per batch of the spans called ``name``."""
    walls = [e - s for n, s, e in spans if n == name]
    if not walls or n_batches <= 0:
        return None
    return sum(walls) / n_batches * 1e-6


def idle_gaps(reduced, spans, top: int = 10) -> list:
    """The longest stretches of the traced window in which no chip ran an
    operation, each named by the innermost span (the harness's or the
    program's) over its midpoint: ``[[name, seconds]]``."""
    every = list(reduced.spans) + list(spans)
    idle = trace_lib.gaps_ns([(o.start, o.end) for o in reduced.ops], reduced.lo, reduced.hi)
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        over = [(b - a, n) for n, a, b in every if a <= mid <= b]
        out.append([min(over)[1] if over else "outside-harness-spans", (e - s) * 1e-9])
    return out


def _in_scope(scope: str):
    return re.compile(rf"(?:^|[/ ]){re.escape(scope)}/").search


def scope_ms(ctx, scope: str) -> float | None:
    """Device time per traced batch, on the busiest chip, of the ``other``
    ops whose op-name path passes through the name scope ``scope``."""
    t = ctx.trace
    if t is None or not ctx.traced:
        return None
    inside = _in_scope(scope)
    per_chip = [
        trace_lib.union_ns([(o.start, o.end) for o in t.chip_ops(c, "other") if inside(o.path)],
                           t.lo, t.hi)
        for c in range(t.n_chips)
    ]
    if max(per_chip) <= 0:
        return None
    return max(per_chip) / len(ctx.traced) * 1e-6
