"""Packed-buffer corruption detection + targeted self-heal (DESIGN.md §9).

The executor's speed comes from long-lived, aggressively packed buffers —
exactly the kind of state silent memory corruption poisons for every
subsequent batch.  :class:`IntegrityManifest` freezes a CRC32 per buffer
*region* at pack time and re-verifies them on a batch cadence and on every
drift hot-swap:

* one region per (core, slot) chunk in the ragged buffer — the slot's
  allocated span ``[slot_row_start, slot_row_start + align(rows+1, block_r))``
  including its redirect/padding rows;
* one tail region per core (the zero padding past the last slot + the
  shared trailing zero row);
* one region per core of the residency cache, and one per symmetric table.

``verify`` returns the list of mismatching region keys; ``repair``
re-materializes exactly those regions from the source tables (bit-exact —
the same rows ``pack_plan`` copied) and rebuilds the cache mini-table from
the repaired buffer through ``cache_remap``.  A region with no source data
(abstract packs) is zeroed and reported as *quarantined*: served as if the
rows were padding until a full re-pack replaces the plan.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any

import numpy as np

__all__ = ["IntegrityManifest", "region_label"]


def _crc(arr) -> int:
    return zlib.crc32(np.ascontiguousarray(np.asarray(arr)).tobytes())


def _align(n: int, b: int) -> int:
    return -(-n // b) * b


def region_label(key: tuple) -> str:
    kind, a, b = key
    return f"{kind}[core={a}]" if b < 0 else f"{kind}[core={a},slot={b}]"


@dataclasses.dataclass
class IntegrityManifest:
    """Frozen pack-time checksums of one :class:`PackedPlan`'s buffers.

    ``checksums`` maps a region key ``(kind, core_or_table, slot)`` to its
    CRC32 (``slot = -1`` for whole-array regions); ``spans`` gives the
    ragged-buffer row range of ``chunk``/``tail`` regions.
    """

    checksums: dict[tuple, int]
    spans: dict[tuple, tuple[int, int]]
    meta: dict

    @classmethod
    def from_packed(cls, packed, plan) -> "IntegrityManifest":
        checksums: dict[tuple, int] = {}
        spans: dict[tuple, tuple[int, int]] = {}
        chunk = np.asarray(packed.chunk_data)
        k = chunk.shape[0]
        if packed.layout == "ragged":
            slot_table = np.asarray(packed.slot_table)
            slot_rows = np.asarray(packed.slot_rows)
            slot_start = np.asarray(packed.slot_row_start)
            br = max(int(packed.block_r), 1)
            for core in range(k):
                end = 0
                for s_i in range(slot_table.shape[1]):
                    if slot_table[core, s_i] < 0:
                        continue
                    lo = int(slot_start[core, s_i])
                    hi = lo + _align(int(slot_rows[core, s_i]) + 1, br)
                    key = ("chunk", core, s_i)
                    spans[key] = (lo, hi)
                    checksums[key] = _crc(chunk[core, lo:hi])
                    end = max(end, hi)
                key = ("tail", core, -1)
                spans[key] = (end, chunk.shape[1])
                checksums[key] = _crc(chunk[core, end:])
        else:  # dense layout: one region per core (no ragged spans to carve)
            for core in range(k):
                checksums[("chunk", core, -1)] = _crc(chunk[core])
        if packed.cache_rows:
            cache = np.asarray(packed.cache_data)
            for core in range(k):
                checksums[("cache", core, -1)] = _crc(cache[core])
        sym = np.asarray(packed.sym_data)
        for i in range(sym.shape[0]):
            checksums[("sym", i, -1)] = _crc(sym[i])
        return cls(
            checksums=checksums,
            spans=spans,
            meta={"layout": packed.layout, "block_r": int(packed.block_r),
                  "regions": len(checksums)},
        )

    # -- verification -------------------------------------------------------

    def verify(self, packed) -> list[tuple]:
        """Re-checksum every region against the live buffers; returns the
        mismatching region keys (empty = clean)."""
        bad: list[tuple] = []
        chunk = np.asarray(packed.chunk_data)
        cache = (
            np.asarray(packed.cache_data) if packed.cache_rows else None
        )
        sym = np.asarray(packed.sym_data)
        for key, crc in self.checksums.items():
            kind, a, _ = key
            if kind in ("chunk", "tail"):
                if key in self.spans:
                    lo, hi = self.spans[key]
                    cur = _crc(chunk[a, lo:hi])
                else:
                    cur = _crc(chunk[a])
            elif kind == "cache":
                cur = _crc(cache[a]) if cache is not None else crc
            else:
                cur = _crc(sym[a])
            if cur != crc:
                bad.append(key)
        return bad

    # -- repair -------------------------------------------------------------

    def repair(self, packed, plan, tables, table_data) -> tuple[Any, dict]:
        """Re-materialize the corrupt regions; returns ``(new_packed,
        report)``.

        Regions are restored bit-exact from ``table_data`` (healed); with no
        source (``table_data is None``) they are zeroed and *quarantined* —
        the manifest checksum is re-pinned to the zeroed bytes so cadence
        checks stop re-flagging the region while a full re-pack is pending.
        ``report`` = ``{"healed": [...], "quarantined": [...], "clean": bool}``
        with keys as :func:`region_label` strings.
        """
        import jax.numpy as jnp

        bad = self.verify(packed)
        if not bad:
            return packed, {"healed": [], "quarantined": [], "clean": True}
        chunk = np.array(packed.chunk_data)
        cache = np.array(packed.cache_data) if packed.cache_rows else None
        sym = np.array(packed.sym_data)
        per_core = plan.per_core()
        healed: list[tuple] = []
        quarantined: list[tuple] = []

        def src(table_idx, lo, n):
            if table_data is None:
                return None
            t = np.asarray(table_data[table_idx][lo : lo + n])
            return t.astype(chunk.dtype)

        # chunk regions first: the cache rebuild below reads from them.
        for key in bad:
            kind, core, s_i = key
            if kind == "tail":
                lo, hi = self.spans[key]
                chunk[core, lo:hi] = 0  # padding is zeros by construction
                healed.append(key)
            elif kind == "chunk" and key in self.spans:
                lo, hi = self.spans[key]
                chunk[core, lo:hi] = 0
                a = per_core[core][s_i]
                rows = src(a.table_idx, a.row_offset, a.rows)
                if rows is not None:
                    chunk[core, lo : lo + a.rows] = rows
                    healed.append(key)
                else:
                    quarantined.append(key)
            elif kind == "chunk":  # dense layout: rebuild the whole core
                chunk[core] = 0
                for s, a in enumerate(per_core.get(core, [])):
                    rows = src(a.table_idx, a.row_offset, a.rows)
                    if rows is not None:
                        chunk[core, s, : a.rows] = rows
                (healed if table_data is not None else quarantined).append(key)
            elif kind == "sym":
                ti = packed.sym_static[core][0]
                sym[core] = 0
                rows = src(ti, 0, tables[ti].rows)
                if rows is not None:
                    sym[core, : rows.shape[0]] = rows
                    healed.append(key)
                else:
                    quarantined.append(key)
        # cache regions: the mini-table is a copy of buffer rows — rebuild it
        # from the (now repaired) buffer through the row -> position remap.
        cache_bad = [key for key in bad if key[0] == "cache"]
        if cache_bad and cache is not None:
            remap = np.asarray(packed.cache_remap)
            for key in cache_bad:
                _, core, _ = key
                rows = np.nonzero(remap[core] >= 0)[0]
                cache[core] = 0
                cache[core, remap[core, rows]] = chunk[core, rows]
                healed.append(key)

        new_packed = dataclasses.replace(
            packed,
            chunk_data=jnp.asarray(chunk),
            sym_data=jnp.asarray(sym),
            **(
                {"cache_data": jnp.asarray(cache)}
                if cache is not None
                else {}
            ),
        )
        # quarantined (zeroed, no source) regions get their checksum
        # re-pinned; healed regions must match the original CRC again.
        for key in quarantined:
            kind, a, _ = key
            if kind == "chunk" and key in self.spans:
                lo, hi = self.spans[key]
                self.checksums[key] = _crc(chunk[a, lo:hi])
            elif kind == "chunk":
                self.checksums[key] = _crc(chunk[a])
            elif kind == "sym":
                self.checksums[key] = _crc(sym[a])
        report = {
            "healed": [region_label(key) for key in healed],
            "quarantined": [region_label(key) for key in quarantined],
            "clean": not self.verify(new_packed),
        }
        return new_packed, report
