"""``A = L L^H`` through ``dlaf_tpu.algorithms.cholesky`` on a 2x2 process
grid (``chol-d-n4096-nb256-2x2``: the distributed branch, the unrolled
``_build_dist_cholesky``, one ``shard_map`` program a call on four chips).
Input, fresh copy, call and flop model are ``ops/cholesky.py``'s; what this
file adds:

* ``build`` refuses at once unless the configuration's grid has four ranks
  and the run four devices: on fewer the entry would take its local branch
  and the cell would measure the one-chip cell's program under another name.
* ``host`` brings back, beside the gathered lower triangle the plain check
  reads, EVERY DEVICE'S SHARD of the result as that device holds it
  (``addressable_shards``, keyed by the device's position in the grid's
  mesh): a gathered matrix cannot say where its tiles lay.
* ``check`` adds to the plain residual the worst relative difference, over
  the four chips, of a chip's shard from what
  ``benchmark/reference/cholesky_block_cyclic.py``'s ``local_tiles`` says
  its rank must hold of ``tril(L) + triu(A, 1)``: the host's numpy float64
  factor in the factorized triangle, the input in the triangle that passes
  through, zero in the slots no tile maps to. Same tolerance as the
  residual (``60 n eps``): a float32 factor reads 1e-7 on both and fails
  both (PERF.md); a tile on the wrong chip or in the wrong slot reads about
  1. Nothing of it is inside the timed call.
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


_plain = _load("bench_ops_cholesky", "cholesky.py")
reference = _load("bench_reference_cholesky_block_cyclic", os.pardir,
                  "reference", "cholesky_block_cyclic.py")
fresh, call, flops = _plain.fresh, _plain.call, _plain.flops


def build(config: dict, seed: int, devices) -> dict:
    rows, cols = config["grid"]
    if rows * cols != 4 or len(devices) != 4:
        raise SystemExit(
            f"benchmark: the distributed Cholesky cell needs a grid of four "
            f"ranks on four devices, got grid {rows}x{cols} and "
            f"{len(devices)} device(s). Nothing was run.")
    state = _plain.build(config, seed, devices)
    # the factor the shards are held to: numpy (LAPACK) float64 on the host
    state["low"] = np.linalg.cholesky(state["a"])
    state["nb"], state["grid"] = config["nb"], (rows, cols)
    return state


def host(out):
    """``{"low": the gathered lower triangle, "shards": {rank: array}}``;
    a device's rank is its position in the grid's mesh."""
    where = {dev: rank for rank, dev in np.ndenumerate(out.grid.mesh.devices)}
    shards = {where[s.device]: np.asarray(s.data, dtype=np.float64)
              for s in out.storage.addressable_shards}
    return {"low": _plain.host(out), "shards": shards}


def shard_differences(state, shards) -> dict:
    """``{rank: |shard - local_tiles(want)| / |local_tiles(want)|}`` for
    every rank of the grid; infinite for a rank no device answered for."""
    want = state["low"] + np.triu(state["a"], 1)
    out = {}
    for rank in np.ndindex(*state["grid"]):
        mine = reference.local_tiles(want, state["nb"], state["grid"], rank)
        got = shards.get(rank)
        out[rank] = float("inf") if got is None or got.shape != mine.shape \
            else oplib.frob(got - mine) / oplib.frob(mine)
    return out


def check(state, got) -> dict:
    found = _plain.check(state, got["low"])
    diffs = list(shard_differences(state, got["shards"]).values())
    found["worst chip's |shard - reference local_tiles|/|local_tiles|"] = \
        next((d for d in diffs if d != d), max(diffs))      # NaN sticks
    return found
