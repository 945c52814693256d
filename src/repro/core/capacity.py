"""Per-chip HBM capacity of a placement, priced in the layout the pack
stores it in, and the split of each chip's chunks between the fused
streaming kernel and the row gather.

Every array of a pack has one shape on every chip (``shard_map`` runs one
program), so a chip holds what the fullest chip needs of each store:

* the fused kernel's ragged buffer, ``(T + 1, E)`` rows where ``T`` is the
  largest chip's chunk rows padded to ``block_r``, its buffer-row remap
  (one int32 a row), and two copies the step makes of it: the slice the
  kernel reads and that slice relaid to 128 lanes, the layout the kernel's
  row windows need (a ``(T, 16)`` f32 operand takes 8x its bytes);
* the row gather's buffer, lane-dense: a chip's gathered chunks back to
  back, eight 128-lane lines to a block, so HBM holds their logical bytes;
* the symmetric group, replicated: every table padded to the largest, and
  the largest one's lane-padded kernel operand.

The budget is ``BUDGET_SHARE`` of the chip's HBM.  The quarter left over is
for what the plan does not price: XLA's reserve (258 MB on a v5e), the
step's smaller temporaries (indices, pooled partials, the rejoin's buffers,
the tower's activations), and the allocator's fragmentation.

The fused kernel's step schedule is scalar-prefetched into SMEM, so a chip
streams at most ``step_limit`` row-blocks: half of SMEM at eight int32
words a step (its widest schedule, with access reduction armed); the other
half is for the dedup unique list and Mosaic's own scalars.  A chip whose
chunks need more moves its largest chunks, largest first, to the row
gather until the rest fits (:func:`split_gather`).
"""
from __future__ import annotations

from typing import Mapping, Sequence

BUDGET_SHARE = 0.75
LANES = 128  # lanes of a TPU vector register
SCHEDULE_WORDS = 8  # int32 words one fused-kernel step takes in SMEM, at most
_ROW_PAD = 8  # sublane-friendly row padding of the symmetric group
# ragged fused-kernel row-block candidates, largest first.  Bigger blocks
# mean fewer steps; smaller ones waste fewer padding rows.
_RAGGED_BLOCK_RS = (512, 256, 128, 64)
_RAGGED_PAD_SLACK = 1.125  # padded rows allowed per data row (incl. zero row)

__all__ = [
    "BUDGET_SHARE",
    "CapacityError",
    "chip_bytes",
    "default_block_r",
    "gather_rows_per_block",
    "split_gather",
    "step_limit",
]


class CapacityError(ValueError):
    """No placement fits a chip's HBM budget: the message names the chip,
    the bytes it would need and the budget."""

    def __init__(self, chip: int, need: int, budget: int, detail: str = ""):
        self.chip, self.need, self.budget = int(chip), int(need), int(budget)
        super().__init__(
            f"chip {chip} would need {need:,} bytes of HBM, over its budget of "
            f"{budget:,}" + (f" ({detail})" if detail else "")
        )


def align(n: int, mult: int) -> int:
    return int(-(-n // mult) * mult)


def default_block_r(rows: Sequence[int]) -> int:
    """Largest row-block whose per-chunk padding (each chunk plus its zero
    row rounded up to a block) keeps the buffer within
    ``_RAGGED_PAD_SLACK`` of its data rows."""
    data = sum(r + 1 for r in rows)
    for br in _RAGGED_BLOCK_RS[:-1]:
        if sum(align(r + 1, br) for r in rows) <= _RAGGED_PAD_SLACK * data:
            return br
    return _RAGGED_BLOCK_RS[-1]


def step_limit(smem_bytes: int) -> int | None:
    """Row-blocks one chip's fused schedule may hold; ``None`` when the
    hardware gives no SMEM size."""
    if not smem_bytes:
        return None
    return int(smem_bytes) // 2 // (4 * SCHEDULE_WORDS)


def gather_rows_per_block(e: int, itemsize: int) -> int:
    """Table rows in one ``(sublanes, 128)`` block of the gather buffer:
    one native tile (8 sublanes of 32-bit values, 16 of 16-bit)."""
    if LANES % e:
        raise ValueError(f"the row gather needs E dividing {LANES}, got E={e}")
    return 8 * max(1, 4 // itemsize) * (LANES // e)


def split_gather(
    per_core_rows: Mapping[int, Sequence[int]], block_r: int, limit: int | None
) -> set[tuple[int, int]]:
    """``(core, slot)`` of the chunks the row gather serves: on each core
    whose chunks need more than ``limit`` row-blocks, the largest chunks,
    largest first, until the rest fit."""
    out: set[tuple[int, int]] = set()
    if limit is None:
        return out
    for core, rows in per_core_rows.items():
        steps = [align(r + 1, block_r) // block_r for r in rows]
        total = sum(steps)
        for s_i in sorted(range(len(rows)), key=lambda i: (-rows[i], i)):
            if total <= limit:
                break
            out.add((core, s_i))
            total -= steps[s_i]
    return out


def chip_bytes(
    per_core_rows: Mapping[int, Sequence[int]],
    gathered: set[tuple[int, int]],
    sym_rows: Sequence[int],
    *,
    e: int,
    itemsize: int,
    block_r: int,
) -> dict:
    """Bytes each chip holds while serving a pack of this layout.

    ``per_core_rows[c]`` are core ``c``'s chunk rows in slot order,
    ``gathered`` the ``(core, slot)`` pairs the row gather serves and
    ``sym_rows`` the symmetric group's table rows.  Returns ``per_chip``
    (the same on every chip: each store has the fullest chip's shape),
    ``own`` (each chip's own rows in the fused and the gather store, which
    name the chip that sets the size), and the bytes of each store."""
    cores = sorted(per_core_rows)
    fused = {
        c: sum(align(r + 1, block_r) for s, r in enumerate(per_core_rows[c])
               if (c, s) not in gathered)
        for c in cores
    }
    gath = {
        c: sum(r for s, r in enumerate(per_core_rows[c]) if (c, s) in gathered)
        for c in cores
    }
    row, wide = e * itemsize, align(e, LANES) * itemsize
    t = align(max(fused.values(), default=0), block_r)
    fused_b = (2 * t + 1) * row + t * wide + (t + 1) * 4
    g = max(gath.values(), default=0)
    gather_b = align(g, gather_rows_per_block(e, itemsize)) * row if g else 0
    sym_b = 0
    if sym_rows:
        m = align(max(sym_rows), _ROW_PAD)
        sym_b = len(sym_rows) * (m + 1) * row + (max(sym_rows) + 1) * wide
    total = fused_b + gather_b + sym_b
    return {
        "per_chip": [int(total)] * len(cores),
        "own": [int(fused[c] * (row + wide) + gath[c] * row) for c in cores],
        "fused_bytes": int(fused_b),
        "gather_bytes": int(gather_b),
        "sym_bytes": int(sym_b),
        "fused_rows": [int(fused[c]) for c in cores],
        "gather_rows": [int(gath[c]) for c in cores],
    }
