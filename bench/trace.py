"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: per chip, the device operations with their intervals and a
class (lookup kernel, collective, other); the harness's host spans
(``bench.*``), on the same clock; the traced window and the device's busy
time in it.

The classes come from names the program gives its kernels and that XLA
gives collectives, never from a list kept by hand:

* lookup: an operation whose name path passes through the jitted entry of
  a lookup kernel (``LOOKUP_ENTRIES``);
* collective: an all-to-all, all-gather, all-reduce, reduce-scatter or
  collective-permute, started or done.

A device op in the trace is named by its HLO instruction (``%name = ...``);
its name path (``op_name``) is in the compiled module's text, which the
harness hands over as ``hlo_text``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

LOOKUP_ENTRIES = (
    "multi_embedding_bag_ragged",
    "embedding_bag_gm",
    "embedding_bag_ub",
    "embedding_bag_l1",
)
_COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all_to_all|all_gather|all_reduce|reduce_scatter|collective_permute"
)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", re.M)
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def classify(name: str, path: str) -> str:
    if any(e in path for e in LOOKUP_ENTRIES):
        return "lookup"
    if _COLLECTIVE.search(name) or _COLLECTIVE.search(path):
        return "collective"
    return "other"


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]``."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float):
    """The ``(start, end)`` stretches of ``[lo, hi]`` no interval covers."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Op:
    chip: int
    start: float  # ns, trace clock
    end: float
    name: str
    path: str
    cls: str


@dataclasses.dataclass
class Reduced:
    ops: list  # [Op]
    spans: list  # [(name, start_ns, end_ns)] of the harness, host clock of the trace
    n_chips: int
    lo: float  # traced window, ns
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def chip_ops(self, chip: int, cls: str | None = None):
        return [o for o in self.ops if o.chip == chip and (cls is None or o.cls == cls)]

    def busy_ns(self, chip: int, cls: str | None = None) -> float:
        return union_ns([(o.start, o.end) for o in self.chip_ops(chip, cls)], self.lo, self.hi)

    @property
    def busy_s(self) -> float:
        """Device busy time, averaged over the chips."""
        return sum(self.busy_ns(c) for c in range(self.n_chips)) / self.n_chips * 1e-9

    def spans_named(self, name: str):
        return [(s, e) for n, s, e in self.spans if n == name]

    def busy_within_ns(self, intervals) -> float:
        """Time inside ``intervals`` during which any chip ran an operation."""
        dev = [(o.start, o.end) for o in self.ops]
        return sum(union_ns(dev, s, e) for s, e in intervals)

    def breakdown(self) -> dict:
        by_name: dict[str, float] = {}
        for o in self.ops:
            if o.end > self.lo and o.start < self.hi:
                key = f"{o.cls}:{o.name}" + (f" {o.path[-90:]}" if o.path else "")
                by_name[key] = by_name.get(key, 0.0) + (min(o.end, self.hi) - max(o.start, self.lo)) * 1e-9
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        # the longest stretches in which no chip ran anything, named by the
        # harness span the host was in
        idle = gaps_ns([(o.start, o.end) for o in self.ops], self.lo, self.hi)
        named = []
        for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
            mid = (s + e) / 2
            host = [n for n, a, b in self.spans if a <= mid <= b]
            named.append([host[-1] if host else "outside-harness-spans", (e - s) * 1e-9])
        return {"device_ops": [[k, v] for k, v in device_ops], "idle_gaps": named}


def op_names(hlo_text: str) -> dict:
    """HLO instruction name -> its ``op_name`` path, from a module's text."""
    return dict(_INSTR.findall(hlo_text or ""))


def _instr(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _path(event, names: dict) -> str:
    parts = [names.get(_instr(event.name), "")]
    parts += [v for _, v in event.stats if isinstance(v, str)]
    return " ".join(p for p in parts if p)


def reduce_profile(pd, n_chips: int, hlo_text: str = "") -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``; ``hlo_text``: the compiled
    text of the module whose ops the trace holds."""
    names = op_names(hlo_text)
    ops, spans = [], []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, path = _instr(ev.name), _path(ev, names)
                    ops.append(Op(chip, ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name, path, classify(name, path)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda s: s[1])
    if spans:
        lo, hi = spans[0][1], max(e for _, _, e in spans)
    elif ops:
        lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    else:
        lo = hi = 0.0
    return Reduced(ops=ops, spans=spans, n_chips=n_chips, lo=lo, hi=hi)


def reduce_dir(trace_dir: str, n_chips: int, hlo_text: str = "") -> Reduced | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return reduce_profile(ProfileData.from_file(files[-1]), n_chips, hlo_text) if files else None
