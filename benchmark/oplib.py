"""What the op files share: tiled matrices from host arrays, the fresh
on-device copy a donated call consumes, seeded probes and the fence. Copied
from chip_smoke.py's helpers (``_matrix``, ``_fresh``, ``_probe``, ``_frob``,
the fence of ``_timed``), which stay where they are."""

from __future__ import annotations

import numpy as np


def make_grid(config: dict, devices):
    """``Grid(rows, cols)`` over ``devices`` for a distributed configuration,
    None for a local one (``"grid": [1, 1]``)."""
    rows, cols = config["grid"]
    if rows * cols != len(devices):
        raise ValueError(f"grid {rows}x{cols} needs {rows * cols} devices, "
                         f"got {len(devices)}")
    if rows * cols == 1:
        return None
    from dlaf_tpu.comm.grid import Grid

    return Grid(rows, cols, devices=list(devices))


def matrix(a: np.ndarray, nb: int, dtype, grid=None):
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    return Matrix.from_global(np.asarray(a, dtype=dtype),
                              TileElementSize(nb, nb), grid=grid)


def fresh(m):
    """A new on-device copy of ``m`` (the caller fences it, untimed)."""
    return m.with_storage(m.storage + 0)


def fence(out):
    """``hard_fence`` on the device arrays behind a call's result (a Matrix,
    an array or a tuple of them); returns ``out``."""
    from dlaf_tpu.common.sync import hard_fence

    items = out if isinstance(out, tuple) else (out,)
    hard_fence(*(getattr(o, "storage", o) for o in items))
    return out


def probe(n: int, seed: int, k: int = 8) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, k))


def frob(x) -> float:
    return float(np.linalg.norm(x))
