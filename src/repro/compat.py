"""The jax settings every entry point and kernel of this repo shares.

* :func:`make_mesh` — device meshes with ``Auto`` axes (``jax.make_mesh``
  defaults to ``Explicit``, which the ``shard_map`` executor does not use);
* :func:`tpu_compiler_params` — Mosaic compiler parameters, asking for more
  scoped VMEM when a kernel's padded working set needs it;
* :func:`pallas_interpret` — the one place that decides whether Pallas
  kernels compile (TPU) or run in interpret mode (CPU tests);
* :func:`enable_compilation_cache` — JAX's persistent compile cache at a
  fixed path, so repeated runs of the same program skip compilation.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Sequence

import jax

# v5e's default scoped-VMEM limit.  A kernel whose padded working set is
# larger asks for more, up to _VMEM_LIMIT_CAP (the chip has 128 MiB).
SCOPED_VMEM_DEFAULT = 16 << 20
_VMEM_LIMIT_CAP = 100 << 20
_LANES = 128


def vmem_bytes(shape: Sequence[int], itemsize: int = 4) -> int:
    """Bytes one VMEM buffer of ``shape`` takes on a TPU: the last dim pads
    to 128 lanes and the one before it to the sublane tile (8 rows of
    32-bit values, 16 of 16-bit).  A ``(m, 16)`` f32 block therefore takes
    8x its logical bytes."""
    shape = tuple(int(d) for d in shape) or (1,)
    if len(shape) == 1:
        shape = (1,) + shape
    sub = 8 * max(1, 4 // itemsize)
    *lead, rows, cols = shape
    n = -(-rows // sub) * sub * (-(-cols // _LANES) * _LANES) * itemsize
    for d in lead:
        n *= d
    return int(n)


def tpu_compiler_params(
    *, dimension_semantics: tuple[str, ...], vmem_bytes: int = 0
):
    """``pltpu.CompilerParams`` for one kernel.  ``vmem_bytes`` is the
    kernel's padded working set (:func:`vmem_bytes` per buffer, double
    buffers included); past the default scoped limit the kernel asks for
    that much plus half again for Mosaic's own scratch."""
    from jax.experimental.pallas import tpu as pltpu

    limit = None
    if vmem_bytes > SCOPED_VMEM_DEFAULT // 2:
        limit = min(
            _VMEM_LIMIT_CAP, max(SCOPED_VMEM_DEFAULT, vmem_bytes * 3 // 2)
        )
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics, vmem_limit_bytes=limit
    )


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode.

    On a TPU they compile (``False``); on the CPU backend, where the tests
    run, they are interpreted (``True``).  Any other backend has no Mosaic
    lowering, so this raises rather than quietly interpreting there."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels target TPU; backend {backend!r} can neither "
        "compile them nor is it the CPU test backend"
    )


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives in ``.jax_cache/`` at the
    checkout root: a fixed path, because the path is part of each entry's
    key, so a directory named after a process or a time never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compile, not only the slow ones: a chip run is short
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
