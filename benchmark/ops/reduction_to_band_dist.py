"""``B = Q^H A Q`` through ``dlaf_tpu.eigensolver.reduction_to_band`` on a 2x2
process grid (``red2band-d-n16384-nb512-b128-2x2``: the distributed branch,
the scan-form ``_build_dist_red2band_scan``, one ``shard_map`` program a call
on four chips). Input, fresh copy, call, flop model and the three plain
checks are ``ops/reduction_to_band.py``'s; what this file adds:

* ``build`` refuses at once unless the configuration's grid has four ranks
  and the run four devices: on fewer the entry would take its local branch
  and the cell would measure the one-chip cell's program under another
  name. It makes the one-chip op's input and leaves ``A``'s eigenvalues to
  the first check (they are the reference's seconds, not set-up).
* ``host`` fetches the result's tile storage once and cuts it into EVERY
  DEVICE'S SHARD as that device holds it (``addressable_shards``, keyed by
  the device's position in the grid's mesh); the global matrix the plain
  checks read is put together from those shards on the host by
  ``benchmark/reference/cholesky_block_cyclic.py``'s map (ScaLAPACK's
  owner and slot of each tile, no code of the library, nothing sent back
  to a device). So a tile on the wrong chip or in the wrong slot fails the
  plain checks. It also brings every device's copy of the taus: they are
  returned as replicated and nothing else holds them to it.
* ``check`` adds to the plain checks the largest difference of any chip's
  taus from chip (0, 0)'s, relative to the largest tau: exactly 0 when
  right, under the same tolerance (``100 n eps``). The plain checks of a
  result equal to the last one checked are the last ones' (the warm-up's
  and the window's last call give the same bits on the same input: the
  eigenvalues of B are not taken twice). Nothing of it is inside the
  timed call.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

import oplib

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, *parts: str):
    """A sibling file as a module (the harness loads op files by path; the
    directories are on no import path)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_plain = _load("bench_ops_reduction_to_band", "reduction_to_band.py")
reference = _load("bench_reference_cholesky_block_cyclic", os.pardir,
                  "reference", "cholesky_block_cyclic.py")
fresh, call, flops = _plain.fresh, _plain.call, _plain.flops
#: the plain checks' names, as ``ops/reduction_to_band.py`` prints them
_PLAIN = ("|A x - Q(B(Q^H x))|/|A x|", "|Q^H Q x - x|/|x|",
          "max|eigvalsh(B) - eigvalsh(A)|/max|lam|")


def build(config: dict, seed: int, devices) -> dict:
    rows, cols = config["grid"]
    if rows * cols != 4 or len(devices) != 4:
        raise SystemExit(
            f"benchmark: the distributed reduction cell needs a grid of four "
            f"ranks on four devices, got grid {rows}x{cols} and "
            f"{len(devices)} device(s). Nothing was run.")
    n, nb = config["n"], config["nb"]
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2
    mat = oplib.matrix(a, nb, np.dtype(config["dtype"]),
                       oplib.make_grid(config, devices))
    return {"a": a, "ref": mat, "band": config["args"]["band_size"],
            "seed": seed, "nb": nb, "grid": (rows, cols), "last": None}


def host(out):
    """``{"red", "taus": chip (0, 0)'s, "taus_by_rank": {rank: array}}``;
    a device's rank is its position in the grid's mesh, ``red`` the global
    matrix assembled from the devices' shards."""
    import jax

    mat, taus = out
    where = {dev: rank for rank, dev in np.ndenumerate(mat.grid.mesh.devices)}
    storage = np.asarray(jax.device_get(mat.storage), dtype=np.float64)
    shards = {where[s.device]: storage[s.index]
              for s in mat.storage.addressable_shards}
    by_rank = {where[s.device]: np.asarray(s.data, dtype=np.float64)
               for s in taus.addressable_shards}
    d = mat.dist
    red = assemble(shards, d.block_size.row,
                   (d.grid_size.row, d.grid_size.col),
                   (d.size.row, d.size.col),
                   (d.source_rank.row, d.source_rank.col))
    return {"red": red, "taus": by_rank.get((0, 0)), "taus_by_rank": by_rank}


def assemble(shards, nb: int, grid, shape, source=(0, 0)) -> np.ndarray:
    """The ``shape`` matrix whose tile ``(i, j)`` is slot ``local_slot(i,
    j)`` of the shard of rank ``owner(i, j)`` (the inverse of
    ``local_tiles``); NaN where no device answered for a rank."""
    m, n = shape
    out = np.full((m, n), np.nan)
    for i in range(-(-m // nb)):
        for j in range(-(-n // nb)):
            shard = shards.get(reference.owner(i, j, grid, source))
            if shard is None:
                continue
            li, lj = reference.local_slot(i, j, grid)
            rows, cols = min(nb, m - i * nb), min(nb, n - j * nb)
            out[i * nb:i * nb + rows, j * nb:j * nb + cols] = \
                shard[li, lj, :rows, :cols]
    return out


def taus_differences(state, by_rank) -> dict:
    """``{rank: max|taus(rank) - taus(0, 0)| / max|taus(0, 0)|}``; infinite
    for a rank no device answered for."""
    ref = by_rank.get((0, 0))
    out = {}
    for rank in np.ndindex(*state["grid"]):
        got = by_rank.get(rank)
        out[rank] = float("inf") if ref is None or got is None \
            or got.shape != ref.shape \
            else float(np.abs(got - ref).max() / np.abs(ref).max())
    return out


def _worst(values) -> float:
    values = list(values)
    return next((v for v in values if v != v), max(values))  # NaN sticks


def _plain_checks(state, red, taus) -> dict:
    """The plain checks, once for a result equal to the last one checked;
    ``A``'s eigenvalues taken on first use."""
    last = state.get("last")
    if last is not None and np.array_equal(last[0], red) \
            and np.array_equal(last[1], taus):
        return dict(last[2])
    if not np.isfinite(red).all():      # eigvalsh would raise: a NaN fails
        return dict.fromkeys(_PLAIN, float("nan"))
    if "lam" not in state:
        state["lam"] = np.linalg.eigvalsh(state["a"])
    found = _plain.check(state, (red, taus))
    state["last"] = (red, taus, found)
    return dict(found)


def check(state, got) -> dict:
    found = _plain_checks(state, got["red"], got["taus"])
    found["max|taus(chip) - taus(0,0)|/max|taus|"] = \
        _worst(taus_differences(state, got["taus_by_rank"]).values())
    return found
