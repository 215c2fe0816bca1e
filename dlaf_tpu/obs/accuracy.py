"""In-graph numerical-quality probes (the ``DLAF_ACCURACY`` knob).

The accuracy half of the observability stack (docs/accuracy.md; the perf
half is :mod:`dlaf_tpu.obs.telemetry`): jit-compiled, distributed-aware
estimators of the backward-error quantities the miniapp ``--check-result``
checks used to recompute on the host with O(n^3) numpy gemms —

* Cholesky relative residual ``|A - L L^H|_F / |A|_F`` (and the ``U^H U``
  form),
* triangular-solve residual ``|op(T) X - alpha B|_F / |B|_F``,
* HEGST (gen_to_std) residual ``|L C L^H - A|_F / |A|_F``,
* eigensolver quality: the Frobenius eigenpair residual
  ``|A Z - Z diag(lam)|_F / |A|_F`` (generalized: ``|A Z - B Z
  diag(lam)|_F``), the sampled per-pair maximum
  ``max_i |A z_i - lam_i z_i|_2 / |A|_F``, and the orthogonality defect
  ``|Z^H Z - I|_F``,
* the D&C merge tree's per-level deflation fraction (emitted by
  :mod:`dlaf_tpu.eigensolver.tridiag_solver`).

Estimator modes (the knob; ``Configuration.accuracy``):

* ``"1"`` — stochastic Hutchinson probe: for the residual matrix ``R``,
  ``|R Omega|_F / sqrt(k)`` with ``k`` seeded Rademacher columns is an
  unbiased estimate of ``|R|_F`` (``E |R w|_2^2 = |R|_F^2`` for unit-
  variance iid ``w``; relative std of the squared estimate is
  ``<= sqrt(2/k)``). Cost is O(n^2 k) device matvecs — NO full-matrix
  host fetch, no O(n^3) recompute.
* ``"full"`` — the exact Frobenius residual, computed as the same probe
  with ``Omega = I`` (``|R I|_F == |R|_F`` exactly): O(n^3) device work,
  still no host round trip.
* ``"0"`` — nothing is emitted during timed runs; an explicit check call
  still computes, using the ``"1"`` probe. The knob is a bitwise
  passthrough for the factor outputs either way: every estimator here is
  a separate program over the algorithm outputs, never fused into the
  factorization (pinned by tests/test_accuracy.py).

Distributed matrices are probed distributed: each rank contracts its own
block-cyclic tiles against the (replicated, trace-time-constant) probe
columns and partial products meet in ``comm.collectives.all_reduce`` over
both mesh axes — O(n k) ICI traffic, counted in the collective byte
counters like any other collective. The cross-rank reduction reassociates
the partial sums, so a distributed estimate matches the single-chip value
of the same factor to rounding (~ulps), not bitwise — the one documented
exception to the layer's bitwise contracts (docs/accuracy.md).

:func:`emit` is the one record shape: every estimate lands as an
``accuracy`` JSONL record (site, metric, value, ``bound_ratio =
value / (c * n * eps_eff)`` with the platform-honest
:func:`dlaf_tpu.miniapp.checks.effective_eps`, n, nb, dtype, platform,
knob attrs; rank stamped by the sink) plus a
``dlaf_accuracy_ratio{site,metric}`` gauge —
``python -m dlaf_tpu.obs.validate --require-accuracy`` and
``scripts/accuracy_gate.py`` consume them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import numpy as np

from ..config import register_program_cache

#: Probe columns of the stochastic ("1") mode. k=8 bounds the relative
#: std of the squared-norm estimate by sqrt(2/8) = 50%; with the fixed
#: seed the estimate is deterministic, and tests pin it within a factor
#: of 4 of the exact residual (comfortably inside 4 sigma).
DEFAULT_PROBES = 8
#: Seed of the Rademacher probe columns (and the eigenpair column
#: sample). Fixed: estimates must be reproducible run-to-run so the
#: accuracy gate compares like with like.
PROBE_SEED = 20260804

def _tiny(x):
    """Smallest normal of ``x``'s (real) dtype — the zero-denominator
    guard must be representable in the computation dtype (a fixed
    1e-300 would round to 0.0f on the float32 path and let 0/0 NaN an
    uncorrupted all-zero reference)."""
    import jax.numpy as jnp

    return jnp.finfo(jnp.asarray(x).dtype).tiny


def resolved_mode(mode: Optional[str] = None) -> str:
    """The effective estimator mode: the argument if given, else the
    ``DLAF_ACCURACY`` knob — with ``"0"`` (telemetry off) resolving to
    the ``"1"`` probe for explicit check calls."""
    if mode is None:
        from ..config import get_configuration

        mode = get_configuration().accuracy
    return "1" if mode == "0" else mode


def enabled() -> bool:
    """True when timed runs should compute and emit accuracy records
    (``DLAF_ACCURACY`` != "0")."""
    from ..config import get_configuration

    return get_configuration().accuracy != "0"


def _probe_columns(n: int, mode: str, k: int, seed: int):
    """``(omega, scale)``: the (n, k) float64 Rademacher probe block and
    the ``1/sqrt(k)`` Hutchinson normalization — or ``(None, 1.0)``
    signaling the exact identity probe (mode "full")."""
    if mode == "full":
        return None, 1.0
    k = max(1, min(k, max(n, 1)))
    rng = np.random.default_rng(seed)
    om = (rng.integers(0, 2, size=(n, k)) * 2 - 1).astype(np.float64)
    return om, 1.0 / math.sqrt(k)


def _sample_columns(n: int, mode: str, k: int, seed: int) -> np.ndarray:
    """Seeded eigenpair column sample (mode "1") or every column
    (mode "full")."""
    if mode == "full" or k >= n:
        return np.arange(n)
    return np.sort(np.random.default_rng(seed + 1).choice(
        n, size=k, replace=False))


# ---------------------------------------------------------------------------
# Tile-level building blocks (used inside the shard_map bodies)
# ---------------------------------------------------------------------------

def _tile_coords(dist):
    """Per-rank global tile coordinates inside a shard_map body:
    ``(g_rows, g_cols)`` for this rank's (ltr, ltc, mb, nb) local tile
    view (the block-cyclic map of matrix/tiling.py)."""
    import jax.numpy as jnp

    from ..comm import collectives as cc
    from ..comm.grid import COL_AXIS, ROW_AXIS
    from ..matrix.tiling import storage_tile_grid

    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    sr, sc = dist.source_rank.row, dist.source_rank.col
    _, _, ltr, ltc = storage_tile_grid(dist)
    rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
    rc = (cc.this_rank(COL_AXIS) - sc) % Qc
    return jnp.arange(ltr) * Pr + rr, jnp.arange(ltc) * Qc + rc


def _masked(lt, dist, g_rows, g_cols, mask: str):
    """This rank's local tiles with everything outside ``mask`` zeroed:
    ``"G"`` whole matrix (pad tiles dropped), ``"L"``/``"U"`` the lower/
    upper triangle including the diagonal, ``"SL"``/``"SU"`` the strict
    triangles. Triangular masks require square tiles."""
    import jax.numpy as jnp

    nt = dist.nr_tiles
    mb, nb = dist.block_size.row, dist.block_size.col
    valid = (g_rows[:, None] < nt.row) & (g_cols[None, :] < nt.col)
    if mask == "G":
        m = valid[:, :, None, None]
    else:
        assert mb == nb, "triangular masks require square tiles"
        lower = mask in ("L", "SL")
        strict = mask in ("SL", "SU")
        if lower:
            keep_full = valid & (g_rows[:, None] > g_cols[None, :])
            tri = jnp.tril(jnp.ones((mb, nb), dtype=bool),
                           -1 if strict else 0)
        else:
            keep_full = valid & (g_rows[:, None] < g_cols[None, :])
            tri = jnp.triu(jnp.ones((mb, nb), dtype=bool),
                           1 if strict else 0)
        keep_diag = valid & (g_rows[:, None] == g_cols[None, :])
        m = keep_full[:, :, None, None] | (keep_diag[:, :, None, None] & tri)
    return jnp.where(m, lt, jnp.zeros((), lt.dtype))


def _fit_rows(x, rows: int):
    """Pad (with zero rows) or slice ``x`` to exactly ``rows`` rows."""
    import jax.numpy as jnp

    n = x.shape[0]
    if n == rows:
        return x
    if n > rows:
        return x[:rows]
    return jnp.pad(x, ((0, rows - n), (0, 0)))


def _psum2(x):
    """Sum over both mesh axes (byte-counted, injectable collectives)."""
    from ..comm import collectives as cc
    from ..comm.grid import COL_AXIS, ROW_AXIS

    return cc.all_reduce(cc.all_reduce(x, ROW_AXIS, "sum"), COL_AXIS, "sum")


def _mv(tiles, om, dist, g_rows, g_cols, op: str = "N"):
    """Replicated ``op(T) @ om`` from this rank's (masked) local tiles:
    the rank's partial product is scattered to its global row (col for
    the transposed ops) blocks and all-reduced over both mesh axes, so
    every rank returns the full product. ``om`` is a replicated (rows, k)
    value, padded/sliced to the storage extent internally; the result is
    sliced to the matrix's logical extent. ``op``: "N" (``T @ om``),
    "T" (``T^T @ om``), "C" (``T^H @ om``)."""
    import jax.numpy as jnp

    from ..matrix.tiling import storage_tile_grid

    mb, nb = dist.block_size.row, dist.block_size.col
    _, _, ltr, ltc = storage_tile_grid(dist)
    Gr, Gc = dist.grid_size.row * ltr, dist.grid_size.col * ltc
    om = om.astype(tiles.dtype)
    if op == "N":
        om_t = _fit_rows(om, Gc * nb).reshape(Gc, nb, -1)[g_cols]
        y = jnp.einsum("ijab,jbk->iak", tiles, om_t)
        part = jnp.zeros((Gr, mb, y.shape[-1]), y.dtype).at[g_rows].set(y)
        return _psum2(part.reshape(Gr * mb, -1))[: dist.size.row]
    om_t = _fit_rows(om, Gr * mb).reshape(Gr, mb, -1)[g_rows]
    t = jnp.conj(tiles) if op == "C" else tiles
    w = jnp.einsum("ijab,iak->jbk", t, om_t)
    part = jnp.zeros((Gc, nb, w.shape[-1]), w.dtype).at[g_cols].set(w)
    return _psum2(part.reshape(Gc * nb, -1))[: dist.size.col]


def _mv_herm(lt, om, dist, g_rows, g_cols, uplo: str):
    """Hermitian matvec from one stored triangle: ``A_h @ om`` with
    ``A_h = tri(A) + stri(A)^H`` (the miniapp checks' ``_hermfull``
    convention — the stored diagonal is used as-is)."""
    tri = _masked(lt, dist, g_rows, g_cols, uplo)
    strict = _masked(lt, dist, g_rows, g_cols, "S" + uplo)
    return (_mv(tri, om, dist, g_rows, g_cols, "N")
            + _mv(strict, om, dist, g_rows, g_cols, "C"))


def _sq(x):
    """Frobenius norm squared (real scalar, works for complex)."""
    import jax.numpy as jnp

    return jnp.sum(jnp.real(x * jnp.conj(x)))


def _herm_sq(lt, dist, g_rows, g_cols, uplo: str):
    """|A_h|_F^2 from one stored triangle (strict part counted twice —
    its conjugate mirror has the same magnitudes)."""
    return (_sq(_masked(lt, dist, g_rows, g_cols, uplo))
            + _sq(_masked(lt, dist, g_rows, g_cols, "S" + uplo)))


def _rel(num2, den2, scale: float):
    """``sqrt(num2) * scale / sqrt(den2)`` with an underflow guard."""
    import jax.numpy as jnp

    den = jnp.sqrt(den2)
    return jnp.sqrt(num2) * scale / jnp.maximum(den, _tiny(den))


def _shard_scalar(fn, mesh, n_in: int, extra_specs=()):
    """Wrap a shard_map body returning one replicated (s,)-vector of
    metric values as a jitted program: per-rank (1, 1, s) outputs over
    the mesh (the norm.py idiom); callers read ``[0, 0]``."""
    import jax

    from jax import shard_map
    from ..comm.grid import COL_AXIS, ROW_AXIS
    from jax.sharding import PartitionSpec as P

    def wrapped(*args):
        out = fn(*args)
        return out.reshape(1, 1, -1)

    spec = tuple([P(ROW_AXIS, COL_AXIS)] * n_in) + tuple(extra_specs)
    return jax.jit(shard_map(wrapped, mesh=mesh, in_specs=spec,
                             out_specs=P(ROW_AXIS, COL_AXIS),
                             check_vma=False))


# ---------------------------------------------------------------------------
# Cholesky: |A - L L^H|_F / |A|_F  (uplo U: |A - U^H U|_F / |A|_F)
# ---------------------------------------------------------------------------

@register_program_cache
@functools.lru_cache(maxsize=64)
def _local_cholesky_prog(dist, uplo: str, mode: str, k: int, seed: int):
    import jax
    import jax.numpy as jnp

    from ..matrix.tiling import tiles_to_global

    om_np, scale = _probe_columns(dist.size.row, mode, k, seed)

    def fn(a_st, f_st):
        a = tiles_to_global(a_st, dist)
        f = tiles_to_global(f_st, dist)
        t = jnp.tril(f) if uplo == "L" else jnp.triu(f)
        if om_np is None:
            z = t @ t.conj().T if uplo == "L" else t.conj().T @ t
            r = a - z
        else:
            om = jnp.asarray(om_np).astype(a.dtype)
            z = t @ (t.conj().T @ om) if uplo == "L" \
                else t.conj().T @ (t @ om)
            r = a @ om - z
        return _rel(_sq(r), _sq(a), scale)

    return jax.jit(fn)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_cholesky_prog(dist, mesh, uplo: str, mode: str, k: int, seed: int):
    import jax.numpy as jnp

    n = dist.size.row
    om_np, scale = _probe_columns(n, mode, k, seed)
    if om_np is None:
        om_np = np.eye(n)

    def local(lt_a, lt_f):
        g_rows, g_cols = _tile_coords(dist)
        a_t = _masked(lt_a, dist, g_rows, g_cols, "G")
        f_t = _masked(lt_f, dist, g_rows, g_cols, uplo)
        om = jnp.asarray(om_np).astype(lt_a.dtype)
        ya = _mv(a_t, om, dist, g_rows, g_cols, "N")
        if uplo == "L":
            w = _mv(f_t, om, dist, g_rows, g_cols, "C")
            z = _mv(f_t, w, dist, g_rows, g_cols, "N")
        else:
            w = _mv(f_t, om, dist, g_rows, g_cols, "N")
            z = _mv(f_t, w, dist, g_rows, g_cols, "C")
        den2 = _psum2(_sq(a_t))
        return _rel(_sq(ya - z), den2, scale)[None]

    return _shard_scalar(local, mesh, 2)


def cholesky_residual(uplo: str, a, factor, mode: Optional[str] = None) -> float:
    """Relative Cholesky residual of ``factor`` against the original
    ``a`` (both :class:`~dlaf_tpu.matrix.matrix.Matrix`, local or
    distributed): ``|A - L L^H|_F / |A|_F`` (or the ``U^H U`` form),
    estimated per the mode (module docstring)."""
    mode = resolved_mode(mode)
    if a.size.is_empty():
        return 0.0
    if a.grid is None or a.grid.num_devices == 1:
        prog = _local_cholesky_prog(a.dist, uplo, mode, DEFAULT_PROBES,
                                    PROBE_SEED)
        return float(prog(a.storage, factor.storage))
    prog = _dist_cholesky_prog(a.dist, a.grid.mesh, uplo, mode,
                               DEFAULT_PROBES, PROBE_SEED)
    return float(np.asarray(prog(a.storage, factor.storage))[0, 0, 0])


# ---------------------------------------------------------------------------
# Triangular solve: |op(T) X - alpha B|_F / |B|_F
# ---------------------------------------------------------------------------

@register_program_cache
@functools.lru_cache(maxsize=64)
def _local_trsm_prog(dist_a, dist_b, side, uplo, op, diag, alpha,
                     mode, k, seed):
    import jax
    import jax.numpy as jnp

    from ..matrix.tiling import tiles_to_global

    om_np, scale = _probe_columns(dist_b.size.col, mode, k, seed)

    def tri_op(t):
        t = jnp.tril(t) if uplo == "L" else jnp.triu(t)
        if diag == "U":
            eye = jnp.eye(t.shape[0], dtype=t.dtype)
            t = t - jnp.diag(jnp.diag(t)) + eye
        return {"N": t, "T": t.T, "C": t.conj().T}[op]

    def fn(a_st, b_st, x_st):
        t = tri_op(tiles_to_global(a_st, dist_a))
        b = tiles_to_global(b_st, dist_b)
        x = tiles_to_global(x_st, dist_b)
        if om_np is None:
            r = (t @ x if side == "L" else x @ t) - alpha * b
        else:
            om = jnp.asarray(om_np).astype(b.dtype)
            tx = t @ (x @ om) if side == "L" else x @ (t @ om)
            r = tx - alpha * (b @ om)
        return _rel(_sq(r), _sq(b), scale)

    return jax.jit(fn)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_trsm_prog(dist_a, dist_b, mesh, side, uplo, op, diag, alpha,
                    mode, k, seed):
    import jax.numpy as jnp

    ncols = dist_b.size.col
    om_np, scale = _probe_columns(ncols, mode, k, seed)
    if om_np is None:
        om_np = np.eye(ncols)
    mask = uplo if diag == "N" else ("SL" if uplo == "L" else "SU")

    def local(lt_a, lt_b, lt_x):
        ga_r, ga_c = _tile_coords(dist_a)
        gb_r, gb_c = _tile_coords(dist_b)
        t_t = _masked(lt_a, dist_a, ga_r, ga_c, mask)
        b_t = _masked(lt_b, dist_b, gb_r, gb_c, "G")
        x_t = _masked(lt_x, dist_b, gb_r, gb_c, "G")
        om = jnp.asarray(om_np).astype(lt_b.dtype)
        bo = _mv(b_t, om, dist_b, gb_r, gb_c, "N")
        if side == "L":
            xo = _mv(x_t, om, dist_b, gb_r, gb_c, "N")
            tx = _mv(t_t, xo, dist_a, ga_r, ga_c, op)
            if diag == "U":
                tx = tx + xo
        else:
            to = _mv(t_t, om, dist_a, ga_r, ga_c, op)
            if diag == "U":
                to = to + om[: dist_a.size.row]
            tx = _mv(x_t, to, dist_b, gb_r, gb_c, "N")
        den2 = _psum2(_sq(b_t))
        return _rel(_sq(tx - alpha * bo), den2, scale)[None]

    return _shard_scalar(local, mesh, 3)


def trsm_residual(side, uplo, op, diag, alpha, a, b, x,
                  mode: Optional[str] = None) -> float:
    """Relative triangular-solve residual ``|op(T) X - alpha B|_F /
    |B|_F`` (side "R": ``|X op(T) - alpha B|_F``), estimated per mode."""
    mode = resolved_mode(mode)
    if b.size.is_empty():
        return 0.0
    if b.grid is None or b.grid.num_devices == 1:
        prog = _local_trsm_prog(a.dist, b.dist, side, uplo, op, diag,
                                float(alpha), mode, DEFAULT_PROBES,
                                PROBE_SEED)
        return float(prog(a.storage, b.storage, x.storage))
    prog = _dist_trsm_prog(a.dist, b.dist, b.grid.mesh, side, uplo, op,
                           diag, float(alpha), mode, DEFAULT_PROBES,
                           PROBE_SEED)
    return float(np.asarray(prog(a.storage, b.storage, x.storage))[0, 0, 0])


# ---------------------------------------------------------------------------
# HEGST (gen_to_std): |L C L^H - A|_F / |A|_F  (uplo U: |U^H C U - A|_F)
# ---------------------------------------------------------------------------

@register_program_cache
@functools.lru_cache(maxsize=64)
def _local_hegst_prog(dist, uplo, mode, k, seed):
    import jax
    import jax.numpy as jnp

    from ..matrix.tiling import tiles_to_global

    om_np, scale = _probe_columns(dist.size.row, mode, k, seed)

    def herm(x):
        tri = jnp.tril(x) if uplo == "L" else jnp.triu(x)
        strict = jnp.tril(x, -1) if uplo == "L" else jnp.triu(x, 1)
        return tri + strict.conj().T

    def fn(a_st, f_st, c_st):
        ah = herm(tiles_to_global(a_st, dist))
        f = tiles_to_global(f_st, dist)
        t = jnp.tril(f) if uplo == "L" else jnp.triu(f)
        ch = herm(tiles_to_global(c_st, dist))
        if om_np is None:
            z = t @ ch @ t.conj().T if uplo == "L" \
                else t.conj().T @ ch @ t
            r = z - ah
        else:
            om = jnp.asarray(om_np).astype(ah.dtype)
            if uplo == "L":
                z = t @ (ch @ (t.conj().T @ om))
            else:
                z = t.conj().T @ (ch @ (t @ om))
            r = z - ah @ om
        return _rel(_sq(r), _sq(ah), scale)

    return jax.jit(fn)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_hegst_prog(dist, mesh, uplo, mode, k, seed):
    import jax.numpy as jnp

    n = dist.size.row
    om_np, scale = _probe_columns(n, mode, k, seed)
    if om_np is None:
        om_np = np.eye(n)

    def local(lt_a, lt_f, lt_c):
        g_rows, g_cols = _tile_coords(dist)
        f_t = _masked(lt_f, dist, g_rows, g_cols, uplo)
        om = jnp.asarray(om_np).astype(lt_a.dtype)
        if uplo == "L":
            w1 = _mv(f_t, om, dist, g_rows, g_cols, "C")
            w2 = _mv_herm(lt_c, w1, dist, g_rows, g_cols, uplo)
            z = _mv(f_t, w2, dist, g_rows, g_cols, "N")
        else:
            w1 = _mv(f_t, om, dist, g_rows, g_cols, "N")
            w2 = _mv_herm(lt_c, w1, dist, g_rows, g_cols, uplo)
            z = _mv(f_t, w2, dist, g_rows, g_cols, "C")
        ya = _mv_herm(lt_a, om, dist, g_rows, g_cols, uplo)
        den2 = _psum2(_herm_sq(lt_a, dist, g_rows, g_cols, uplo))
        return _rel(_sq(z - ya), den2, scale)[None]

    return _shard_scalar(local, mesh, 3)


def hegst_residual(uplo: str, a, factor, out,
                   mode: Optional[str] = None) -> float:
    """Relative HEGST residual ``|L C L^H - A|_F / |A|_F`` (uplo "U":
    ``|U^H C U - A|_F``) with ``A``/``C`` hermitian-expanded from their
    stored ``uplo`` triangles, estimated per mode."""
    mode = resolved_mode(mode)
    if a.size.is_empty():
        return 0.0
    if a.grid is None or a.grid.num_devices == 1:
        prog = _local_hegst_prog(a.dist, uplo, mode, DEFAULT_PROBES,
                                 PROBE_SEED)
        return float(prog(a.storage, factor.storage, out.storage))
    prog = _dist_hegst_prog(a.dist, a.grid.mesh, uplo, mode,
                            DEFAULT_PROBES, PROBE_SEED)
    return float(np.asarray(
        prog(a.storage, factor.storage, out.storage))[0, 0, 0])


# ---------------------------------------------------------------------------
# Eigensolver: eigenpair residual + orthogonality
# ---------------------------------------------------------------------------

def _eigen_probe(n: int, mode: str, k: int, seed: int):
    """Combined probe block for the eigensolver estimators: ``k`` random
    Rademacher columns (the Frobenius/orthogonality estimates) followed
    by the sampled one-hot columns (exact per-eigenpair residual
    columns). Mode "full": the identity serves both."""
    om_np, scale = _probe_columns(n, mode, k, seed)
    if om_np is None:
        return np.eye(n), n, 1.0
    sel = _sample_columns(n, mode, k, seed)
    onehot = np.zeros((n, sel.shape[0]))
    onehot[sel, np.arange(sel.shape[0])] = 1.0
    return np.concatenate([om_np, onehot], axis=1), om_np.shape[1], scale


@register_program_cache
@functools.lru_cache(maxsize=64)
def _local_eigen_prog(dist, uplo, generalized, mode, k, seed):
    import jax
    import jax.numpy as jnp

    from ..matrix.tiling import tiles_to_global

    n = dist.size.row
    om_np, k_rand, scale = _eigen_probe(n, mode, k, seed)

    def herm(x):
        tri = jnp.tril(x) if uplo == "L" else jnp.triu(x)
        strict = jnp.tril(x, -1) if uplo == "L" else jnp.triu(x, 1)
        return tri + strict.conj().T

    def fn(a_st, z_st, b_st, lam):
        ah = herm(tiles_to_global(a_st, dist))
        z = tiles_to_global(z_st, dist)
        om = jnp.asarray(om_np).astype(z.dtype)
        lam_om = lam[:, None].astype(z.dtype) * om
        zo = z @ om
        zl = z @ lam_om
        if generalized:
            bh = herm(tiles_to_global(b_st, dist))
            r = ah @ zo - bh @ zl
        else:
            r = ah @ zo - zl
        g = z.conj().T @ zo - om
        den_raw = jnp.sqrt(_sq(ah))
        den = jnp.maximum(den_raw, _tiny(den_raw))
        fro = jnp.sqrt(_sq(r[:, :k_rand])) * scale / den
        # one-hot columns give exact residual columns; mode "full" has
        # no separate one-hot block — the identity makes EVERY column of
        # r an exact |A z_i - lam_i [B] z_i| column
        r_sel = r[:, k_rand:] if om_np.shape[1] > k_rand else r
        colmax = jnp.sqrt(jnp.max(jnp.sum(
            jnp.real(r_sel * jnp.conj(r_sel)), axis=0),
            initial=0.0)) / den
        orth = jnp.sqrt(_sq(g[:, :k_rand])) * scale
        return jnp.stack([fro, colmax, orth])

    return jax.jit(fn)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_eigen_prog(dist, mesh, uplo, generalized, mode, k, seed):
    import jax.numpy as jnp

    from jax.sharding import PartitionSpec as P

    n = dist.size.row
    om_np, k_rand, scale = _eigen_probe(n, mode, k, seed)

    def local(lt_a, lt_z, lt_b, lam):
        g_rows, g_cols = _tile_coords(dist)
        z_t = _masked(lt_z, dist, g_rows, g_cols, "G")
        om = jnp.asarray(om_np).astype(lt_z.dtype)
        lam_om = lam[:, None].astype(lt_z.dtype) * om
        zo = _mv(z_t, om, dist, g_rows, g_cols, "N")
        zl = _mv(z_t, lam_om, dist, g_rows, g_cols, "N")
        azo = _mv_herm(lt_a, zo, dist, g_rows, g_cols, uplo)
        if generalized:
            r = azo - _mv_herm(lt_b, zl, dist, g_rows, g_cols, uplo)
        else:
            r = azo - zl
        g = _mv(z_t, zo, dist, g_rows, g_cols, "C") - om
        den2 = _psum2(_herm_sq(lt_a, dist, g_rows, g_cols, uplo))
        den_raw = jnp.sqrt(den2)
        den = jnp.maximum(den_raw, _tiny(den_raw))
        fro = jnp.sqrt(_sq(r[:, :k_rand])) * scale / den
        # mode "full" has no separate one-hot block: every identity
        # column of r is an exact per-eigenpair residual column
        r_sel = r[:, k_rand:] if om_np.shape[1] > k_rand else r
        colmax = jnp.sqrt(jnp.max(jnp.sum(
            jnp.real(r_sel * jnp.conj(r_sel)), axis=0),
            initial=0.0)) / den
        orth = jnp.sqrt(_sq(g[:, :k_rand])) * scale
        return jnp.stack([fro, colmax, orth])

    return _shard_scalar(local, mesh, 3, extra_specs=(P(),))


def eigen_residuals(uplo: str, a, lam, z, b=None,
                    mode: Optional[str] = None) -> dict:
    """Eigensolver quality estimates for eigenpairs ``(lam, Z)`` of the
    hermitian ``a`` (generalized with ``b``): ``{"eigen_residual":
    |A Z - [B] Z diag(lam)|_F / |A|_F, "eigenpair_max": max over the
    sampled pairs of |A z_i - lam_i [B] z_i|_2 / |A|_F, "orthogonality":
    |Z^H Z - I|_F}``, estimated per mode."""
    mode = resolved_mode(mode)
    if a.size.is_empty():
        return {"eigen_residual": 0.0, "eigenpair_max": 0.0,
                "orthogonality": 0.0}
    lam_arr = np.asarray(lam, dtype=np.float64)
    generalized = b is not None
    b_st = b.storage if generalized else a.storage
    if a.grid is None or a.grid.num_devices == 1:
        prog = _local_eigen_prog(a.dist, uplo, generalized, mode,
                                 DEFAULT_PROBES, PROBE_SEED)
        out = np.asarray(prog(a.storage, z.storage, b_st, lam_arr))
    else:
        prog = _dist_eigen_prog(a.dist, a.grid.mesh, uplo, generalized,
                                mode, DEFAULT_PROBES, PROBE_SEED)
        out = np.asarray(prog(a.storage, z.storage, b_st, lam_arr))[0, 0]
    return {"eigen_residual": float(out[0]), "eigenpair_max": float(out[1]),
            "orthogonality": float(out[2])}


def array_orthogonality(q, mode: Optional[str] = None) -> float:
    """Orthogonality defect ``|Q^H Q - I|_F`` of a plain (device or
    host) square array, estimated per mode — the bench stage arms'
    cheap invariant for tridiag eigenvector blocks."""
    import jax.numpy as jnp

    mode = resolved_mode(mode)
    q = jnp.asarray(q)
    n = q.shape[0]
    if n == 0:
        return 0.0
    om_np, scale = _probe_columns(n, mode, DEFAULT_PROBES, PROBE_SEED)
    if om_np is None:
        g = q.conj().T @ q - jnp.eye(n, dtype=q.dtype)
    else:
        om = jnp.asarray(om_np).astype(q.dtype)
        g = q.conj().T @ (q @ om) - om
    return float(jnp.sqrt(_sq(g)) * scale)


# ---------------------------------------------------------------------------
# Record emission
# ---------------------------------------------------------------------------

def _platform_of(of=None) -> str:
    """Platform label for a record, judged from the device array that
    holds the checked result (``of``) when given, else the default
    backend — never forcing a backend up from a bare call."""
    if of is not None:
        devs = getattr(of, "devices", None)
        if callable(devs):
            try:
                return next(iter(devs())).platform
            except Exception:
                pass
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


@dataclasses.dataclass
class AccuracyResult:
    """One emitted estimate: the value, its analytic budget ``tol =
    c * n * eps_eff`` (None for informational metrics), and the
    normalized ``bound_ratio = value / tol`` the gate consumes."""

    site: str
    metric: str
    value: float
    finite: bool
    tol: Optional[float] = None
    bound_ratio: Optional[float] = None
    eps_eff: Optional[float] = None
    eps_label: str = ""

    @property
    def passed(self) -> bool:
        """Finite and within the analytic budget (informational metrics
        pass on finiteness alone)."""
        return self.finite and (self.tol is None or self.value < self.tol)


def emit(site: str, metric: str, value, *, n: int, nb: int, dtype,
         c: Optional[float] = None, of=None, attrs: Optional[dict] = None,
         mode: Optional[str] = None, record: bool = True) -> AccuracyResult:
    """Emit one ``accuracy`` JSONL record (+ the
    ``dlaf_accuracy_ratio{site,metric}`` gauge) and return the
    :class:`AccuracyResult`.

    ``c`` is the site's analytic tolerance factor (``tol = c * n *
    eps_eff`` with :func:`dlaf_tpu.miniapp.checks.effective_eps` judged
    from ``of`` — the device array holding the checked result — so
    TPU-emulated-f64 budgets stay honest); ``c=None`` marks an
    informational metric (e.g. the D&C deflation fraction) carrying no
    ``bound_ratio``. A non-finite ``value`` lands as ``value: null`` +
    ``nonfinite: true`` — the corruption signal the accuracy gate treats
    as an automatic regression. ``record=False`` computes without
    emitting (the gate's injection drill)."""
    v = float(value)
    finite = math.isfinite(v)
    mode = resolved_mode(mode)
    tol = ratio = eps = None
    label = ""
    if c is not None:
        from ..miniapp.checks import effective_eps

        eps, label = effective_eps(dtype, of=of)
        tol = float(c) * max(int(n), 1) * eps
        if finite and tol > 0:
            ratio = v / tol
    rec = {"site": site, "metric": metric, "platform": _platform_of(of),
           "n": int(n), "nb": int(nb), "dtype": np.dtype(dtype).name,
           "value": v if finite else None,
           "attrs": dict(attrs or {}, mode=mode)}
    if not finite:
        rec["nonfinite"] = True
    if ratio is not None:
        rec["bound_ratio"] = ratio
        rec["c"] = float(c)
        rec["eps_eff"] = eps
    if record:
        from . import counter, emit_event, gauge, metrics_active
        from . import flight as _flight

        emit_event("accuracy", **rec)
        if metrics_active():
            if ratio is not None:
                gauge("dlaf_accuracy_ratio", site=site,
                      metric=metric).set(ratio)
            if not finite:
                counter("dlaf_accuracy_nonfinite_total", site=site,
                        metric=metric).inc()
        if (ratio is not None and ratio > 1.0) or not finite:
            # a blown analytic budget (or a corrupted estimate — worse)
            # IS an incident: capture the flight ring AFTER this record
            # landed in it, so the dump includes the breaching record
            # itself (docs/observability.md trigger catalog)
            _flight.trigger("accuracy_breach", site=site, metric=metric,
                            bound_ratio=(float(ratio)
                                         if ratio is not None else None),
                            nonfinite=not finite)
    return AccuracyResult(site=site, metric=metric, value=v, finite=finite,
                          tol=tol, bound_ratio=ratio, eps_eff=eps,
                          eps_label=label)
