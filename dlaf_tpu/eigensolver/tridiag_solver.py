"""Divide-and-conquer symmetric tridiagonal eigensolver.

TPU-native counterpart of the reference's ``eigensolver/tridiag_solver``
(``api.h:18-26``, ``impl.h``, ``merge.h``): Cuppen's method — split at tile
boundaries (``impl.h:66-80``), ``stedc`` leaf solves (``impl.h:84-90``),
bottom-up merges (``merge.h:790-887``) with rank-one tear, deflation
(zero-component + Givens rotation on near-equal poles, ``merge.h:443-508``),
per-root secular-equation solves (the reference uses LAPACK ``laed4`` on CPU,
``merge.h:590-629``), Gu-Eisenstat z-refinement, and eigenvector assembly by
GEMM (``merge.h`` via ``GeneralSub``).

Division of labor mirrors the reference's host/device split: O(n^2) control
work (deflation, secular roots via vectorized shifted bisection, z
refinement) runs on the host in float64; the O(n^3) eigenvector assembly runs
as device matmuls. Roots are stored as (anchor pole, offset) pairs so the
pole differences ``d_j - lambda_i`` that feed the eigenvector formula never
suffer cancellation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from ..config import register_program_cache
from ..tile_ops import blas as tb
from ..tile_ops.lapack import stedc

_EPS = np.finfo(np.float64).eps

#: Merges below this size run unsharded even when a mesh is given (the
#: collective overhead of a sharded gemm only pays off for big merges).
_SHARD_MERGE_MIN_N = 512

# Above this deflated-problem size the secular solve and the O(k^2)
# z-refinement run on the device (HBM-bound batched math). Below it the host
# path wins — but only when the native C++ Newton solver (secular.cpp,
# O(iters*k) per root, ~50ms at k=2000) actually loaded; with the numpy
# bisection fallback (~4s at k=2000) the device takes over much earlier.
# The configured default lives in config.Configuration.secular_device_min_k.
_DEVICE_SECULAR_MIN_K_NO_NATIVE = 1024


def _device_secular_min_k() -> int:
    from ..config import get_configuration

    cfg = get_configuration()
    s = cfg.secular_device_min_k
    auto = s == 0
    if auto:
        import jax

        # measured 2026-08: the CPU backend's device route
        # loses to the native host solver at every size, so auto disables
        # it there; on TPU the device side is MXU-backed batched math
        s = 4096 if jax.default_backend() == "tpu" else (1 << 62)
    have_native = False
    if cfg.secular_impl == "native":
        try:
            from ..native import bindings

            bindings.get_lib()
            have_native = True
        except Exception:
            pass
    if not have_native:
        # the numpy bisection fallback is ~100x the native Newton solver,
        # so the device takes over much earlier — this overrides the auto
        # host-always resolution on CPU too
        s = min(s, _DEVICE_SECULAR_MIN_K_NO_NATIVE)
    if auto:
        import jax

        backend = jax.default_backend()
        from ..obs import get_logger

        label = "host-always" if s >= (1 << 62) else str(s)
        # once per (backend, threshold) — auto decisions must not be
        # silent (round-2 advisory pattern)
        get_logger("config").warning_once(
            ("secular_device_min_k", backend, s),
            f"secular_device_min_k=0 (auto) resolved to {label} for "
            f"default backend {backend!r}"
            f"{'' if have_native else ' (no native secular solver)'}"
            " — set the knob explicitly to override",
            knob="secular_device_min_k", backend=backend, choice=label)
    return s


def _secular_roots(ds: np.ndarray, zs: np.ndarray, rho: float):
    """All k roots of ``1 + rho * sum z_j^2/(d_j - lam) = 0``.

    ``ds`` ascending, ``zs`` nonzero, ``rho > 0``. Returns (anchor_idx,
    offset): ``lambda_i = ds[anchor_idx[i]] + offset[i]`` with the anchor
    chosen as the nearest pole (LAPACK laed4's shifted representation).
    Vectorized bisection: ~90 iterations of an (k x k) evaluation — monotone,
    unconditionally convergent, and embarrassingly parallel.
    """
    k = ds.shape[0]
    zsq = zs * zs
    # interval ends: (d_i, d_{i+1}), last interval (d_k, d_k + rho*sum z^2)
    upper = np.empty(k)
    upper[:-1] = ds[1:]
    upper[-1] = ds[-1] + rho * zsq.sum()
    gaps = upper - ds

    # choose anchors by the secular value at the midpoint: f(mid) > 0 means
    # the root lies in the left half (anchor at d_i), else right (d_{i+1})
    mid = ds + gaps / 2
    fmid = 1.0 + rho * (zsq[None, :] / (ds[None, :] - mid[:, None])).sum(1)
    anchor = np.where(fmid >= 0, np.arange(k), np.minimum(np.arange(k) + 1, k - 1))
    anchor[-1] = k - 1
    danchor = ds[anchor]
    # bisect offset mu in (lo, hi) relative to the anchor
    lo = np.where(anchor == np.arange(k), 0.0, ds - upper)   # left- vs right-anchored
    hi = np.where(anchor == np.arange(k), gaps, 0.0)
    lo = lo.copy()
    hi = hi.copy()
    # pole differences relative to anchors: delta[i, j] = d_j - d_anchor_i
    delta = ds[None, :] - danchor[:, None]
    for _ in range(90):
        mu = 0.5 * (lo + hi)
        f = 1.0 + rho * (zsq[None, :] / (delta - mu[:, None])).sum(1)
        take_left = f >= 0
        hi = np.where(take_left, mu, hi)
        lo = np.where(take_left, lo, mu)
    mu = 0.5 * (lo + hi)
    return anchor, mu


def _secular_roots_host(ds, zs, rho):
    """Host secular solve: native C++ safeguarded Newton (``native/
    secular.cpp``, the laed4 analog — the reference calls LAPACK laed4 here,
    ``merge.h:590-629``) with transparent fallback to the numpy bisection."""
    from ..config import get_configuration

    if get_configuration().secular_impl == "native":
        # unified degradation policy (health.registry): counted under
        # dlaf_fallback_total{site="secular"}, announced once, raises in
        # strict mode — the ~100x bisection slowdown is never silent
        from ..health.registry import run_with_fallback

        def _native():
            from ..native import bindings

            return bindings.secular_roots(ds, zs, rho)

        return run_with_fallback("secular", _native,
                                 lambda: _secular_roots(ds, zs, rho))
    return _secular_roots(ds, zs, rho)


def _secular_vcols_device(ds, zs, rho, live):
    """Device twin of :func:`_secular_roots` + the Gu-Eisenstat refinement +
    eigenvector-coefficient assembly: returns ``(lam_live, vcols)``. The pole
    differences ``m[i, j] = d_j - lambda_i`` are formed internally in the
    shifted (cancellation-free) representation. All f64; one fused HBM-bound
    program instead of ~90 numpy sweeps.

    ``live`` marks real entries: the caller pads (ds, zs) to a shape bucket
    (padded poles strictly above the root bound, z = 0) so the jit cache is
    keyed by bucket instead of by the data-dependent deflated size k.
    Padded z contribute nothing to the secular function; anchoring a live
    root to a padded pole is still exact (the shifted representation needs
    an ordered reference point, not a pole); only the log-product
    z-refinement must exclude padded ROWS, via ``live``.
    """
    k = ds.shape[0]
    zsq = zs * zs
    upper = jnp.concatenate([ds[1:], (ds[-1] + rho * zsq.sum())[None]])
    gaps = upper - ds
    mid = ds + gaps / 2
    fmid = 1.0 + rho * (zsq[None, :] / (ds[None, :] - mid[:, None])).sum(1)
    idx = jnp.arange(k)
    anchor = jnp.where(fmid >= 0, idx, jnp.minimum(idx + 1, k - 1))
    anchor = anchor.at[-1].set(k - 1)
    danchor = ds[anchor]
    lo = jnp.where(anchor == idx, 0.0, ds - upper)
    hi = jnp.where(anchor == idx, gaps, 0.0)
    delta = ds[None, :] - danchor[:, None]

    def body(_, lohi):
        lo, hi = lohi
        mu = 0.5 * (lo + hi)
        f = 1.0 + rho * (zsq[None, :] / (delta - mu[:, None])).sum(1)
        take_left = f >= 0
        return jnp.where(take_left, lo, mu), jnp.where(take_left, mu, hi)

    # 300 halvings (matching the native solver's iteration cap): roots next
    # to near-deflated poles sit ~1e-28*gap from the anchor and need >90
    # halvings before the offset mu carries any relative accuracy
    lo, hi = lax.fori_loop(0, 300, body, (lo, hi))
    mu = 0.5 * (lo + hi)
    lam_live = danchor + mu
    m = delta - mu[:, None]
    logm = jnp.where(live[:, None], jnp.log(jnp.abs(m)), 0.0)
    dd = ds[None, :] - ds[:, None]
    dd = dd.at[idx, idx].set(1.0)
    logdd = jnp.log(jnp.abs(dd))
    logdd = logdd.at[idx, idx].set(0.0)
    logdd = jnp.where(live[:, None], logdd, 0.0)
    log_zhat2 = logm.sum(0) - logdd.sum(0)
    zhat = jnp.sign(zs) * jnp.exp(0.5 * log_zhat2)
    vcols = zhat[None, :] / m
    vcols = vcols / jnp.linalg.norm(vcols, axis=1, keepdims=True)
    return lam_live, vcols


@functools.lru_cache(maxsize=None)
def _secular_vcols_jit(mesh):
    """Compiled device secular solve. With a mesh, the (kb, kb) bisection
    and refinement run ROW-sharded over all mesh devices (each root's
    bisection is independent; only the log-product column reductions
    cross shards) and the coefficient matrix comes out row-sharded — the
    last (n, n)-class single-device workspace of the sharded merge path."""
    if mesh is None:
        return jax.jit(_secular_vcols_device)
    from jax.sharding import NamedSharding, PartitionSpec

    from ..comm.grid import COL_AXIS, ROW_AXIS

    rows = PartitionSpec((ROW_AXIS, COL_AXIS))
    return jax.jit(_secular_vcols_device,
                   out_shardings=(NamedSharding(mesh, rows),
                                  NamedSharding(mesh, PartitionSpec(
                                      (ROW_AXIS, COL_AXIS), None))))


def _deflation_scan(ds, zs, live, tol):
    """Near-equal-pole deflation scan (reference ``merge.h:443-508``):
    rotate the z weight of pole pairs closer than ``tol`` onto the earlier
    live pole, deflating the later one. Mutates ``zs``/``live`` in place;
    returns the Givens rotations as arrays ``(i, j, c, s)`` in application
    order. Native C++ single pass (``native/deflate.cpp``) with a
    transparent numpy/Python fallback — the scan is sequential (each
    rotation feeds the running anchor's weight into later decisions), so
    the interpreter loop is the fallback, not the product path."""
    from ..config import get_configuration

    if get_configuration().secular_impl == "native":
        try:
            from ..native import bindings

            return bindings.deflate_scan(ds, zs, live, tol)
        except Exception as e:
            from ..health.registry import report_fallback

            report_fallback("deflate", "native_unavailable", exc=e)
    gi, gj, gc, gs = [], [], [], []
    prev = -1
    for j in range(ds.shape[0]):
        if not live[j]:
            continue
        if prev >= 0 and ds[j] - ds[prev] <= tol:
            r = np.hypot(zs[prev], zs[j])
            if r == 0:
                prev = j
                continue
            gi.append(prev)
            gj.append(j)
            gc.append(zs[prev] / r)
            gs.append(zs[j] / r)
            # rotating makes the two poles share d ~ equal; eigenvalue at
            # ds[j] deflates exactly
            zs[prev], zs[j] = r, 0.0
            live[j] = False
        else:
            prev = j
    return (np.asarray(gi, np.int64), np.asarray(gj, np.int64),
            np.asarray(gc, np.float64), np.asarray(gs, np.float64))


def _assemble_qc_impl(vcols, live_b, rows_live, rows_d, cols_d, giv,
                      inv_order, fin, *, n: int):
    """Device-side assembly of the merge's eigenvector-coefficient matrix
    ``qc`` (n, n) from O(n)-sized host control data + the (kb, kb) secular
    output — the TPU analog of the reference's device merge workspaces
    (``merge.h:45-118``, ``kernels.cu``). The host never holds an (n, n)
    array: scatters place the live coefficient columns and the deflated
    unit columns, a ``lax.scan`` undoes the Givens rotations (identity
    padding makes the rotation count a static bucket), and gathers undo the
    pole sort and apply the final eigenvalue ordering.

    Under a column sharding (see :func:`_assemble_qc_jit`) every step here
    is shard-local: the scatters and the Givens row rotations touch each
    column independently, the ``inv_order`` row gather is per-column, and
    only the final ``fin`` column permutation crosses shards."""
    kb = vcols.shape[0]
    w = max(n, kb)
    vm = jnp.where(live_b[:, None] & live_b[None, :], vcols, 0.0)
    u = jnp.zeros((n, w), vcols.dtype)
    # live columns: root i's coefficients scattered to the live poles' rows
    u = u.at[rows_live, :kb].add(vm.T, mode="drop")
    # deflated columns: unit vectors (pad rows point past n -> dropped)
    u = u.at[rows_d, cols_d].add(1.0, mode="drop")

    def rot(uu, p):
        i = p[0].astype(jnp.int32)
        j = p[1].astype(jnp.int32)
        c, s = p[2], p[3]
        ri, rj = uu[i], uu[j]
        uu = uu.at[i].set(c * ri - s * rj)
        uu = uu.at[j].set(s * ri + c * rj)
        return uu, None

    u, _ = lax.scan(rot, u, giv)
    # undo the pole sort (rows), apply the final eigenvalue order (cols) —
    # the reference's permutation-kernel call sites inside the merge
    from ..algorithms.permutations import permute_array

    return permute_array("Col", fin, permute_array("Row", inv_order, u))


def _qc_col_sharding(mesh):
    """THE layout contract of an assembled qc under a mesh: columns sharded
    over all mesh devices, rows replicated — chosen so every internal
    assembly step (scatters, Givens row rotations, row gather) is
    shard-local and only the final column permutation crosses shards."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..comm.grid import COL_AXIS, ROW_AXIS

    return NamedSharding(mesh, PartitionSpec(None, (ROW_AXIS, COL_AXIS)))


def _q_2d_sharding(mesh):
    """Layout of a merge's Q output: 2D block-sharded over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..comm.grid import COL_AXIS, ROW_AXIS

    return NamedSharding(mesh, PartitionSpec(ROW_AXIS, COL_AXIS))


@functools.lru_cache(maxsize=None)
def _assemble_qc_jit(n: int, mesh):
    """Compiled qc assembly; with a mesh, the (n, n) workspace and result
    follow :func:`_qc_col_sharding`, so no device ever materializes the
    full qc."""
    fn = functools.partial(_assemble_qc_impl, n=n)
    if mesh is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=_qc_col_sharding(mesh))


@functools.lru_cache(maxsize=None)
def _eye_perm_jit(n: int, dtype_name: str, mesh):
    """Decoupled-merge qc: a column-permuted identity, laid out per
    :func:`_qc_col_sharding` under a mesh."""
    def fn(fin):
        return jnp.eye(n, dtype=jnp.dtype(dtype_name))[:, fin]

    if mesh is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=_qc_col_sharding(mesh))


@register_program_cache
@functools.lru_cache(maxsize=None)
def _apply_qc_jit(mesh):
    """Compiled merge gemms ``blkdiag(q1, q2) @ qc`` (jit specializes per
    shape; the slice point is q1's static row count). With a mesh, the
    OUTPUT (the next level's Q) is 2D-sharded (:func:`_q_2d_sharding`) and
    XLA inserts the SUMMA-style collectives. Together with the
    column-sharded qc assembly (:func:`_assemble_qc_jit`) this removes the
    one-device HBM ceiling on the (n, n) merge arrays; the remaining
    single-device term is the deflated secular workspace (kb x kb, bounded
    by the deflation count) — the sharded-Q extension the reference,
    local-only here, does not have.

    The gemms ride ``tb.mm`` so ``f64_gemm="mxu"`` reroutes the D&C
    stage's dominant flops onto the int8/bf16 MXU path like every other
    algorithm's trailing products (raw jnp.matmul kept them on the
    ~342 GF/s emulated-f64 tier regardless of the knob)."""
    def fn(q1, q2, qc):
        # FRESH closure per builder call: jax.jit keyed on a module-level
        # function would survive this lru cache's config-change clearing
        # (jit's trace cache keys on the underlying callable), resurrecting
        # a program traced under the previous f64_gemm route
        return _apply_qc_fn(q1, q2, qc)

    if mesh is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=_q_2d_sharding(mesh))


def _apply_qc_fn(q1, q2, qc):
    """The one merge-apply kernel (shared by the per-merge and the
    vmapped level-batched programs, so the two walks can never drift
    apart and break the bitwise contract)."""
    n1 = q1.shape[0]
    top = tb.mm(q1, qc[:n1, :])
    bot = tb.mm(q2, qc[n1:, :])
    return jnp.concatenate([top, bot], axis=0)


@register_program_cache
@functools.lru_cache(maxsize=None)
def _secular_vcols_batched_jit():
    """vmapped device secular solve for one level batch of same-bucket
    merges (``dc_level_batch=1``): every lane is an independent merge's
    deflated problem, padded to the group's max bucket, so a whole tree
    level's secular work lands in ONE device dispatch instead of one per
    merge. Sharded merges never batch (they keep the per-merge
    :func:`_secular_vcols_jit` with its mesh shardings)."""
    vm = jax.vmap(_secular_vcols_device)

    def fn(*args):
        # trace-time retrace counter (DLAF_PROGRAM_TELEMETRY): each
        # re-bucketing of the level batch retraces this program — the
        # documented compile-cost tail of dc_level_batch, now measurable
        obs.telemetry.count_retrace("tridiag.secular_batched")
        return vm(*args)

    return jax.jit(fn)


@register_program_cache
@functools.lru_cache(maxsize=None)
def _assemble_qc_batched_jit(n: int):
    """vmapped qc assembly over a level group of same-(n, kb, gb) merges."""
    return jax.jit(jax.vmap(functools.partial(_assemble_qc_impl, n=n)))


@register_program_cache
@functools.lru_cache(maxsize=None)
def _apply_qc_batched_jit():
    """vmapped merge gemms over a level group: the batched dot_general is
    the MXU-earning form of many small Q·C products (arXiv:2112.09017).
    Same kernel as the per-merge program (:func:`_apply_qc_fn`; the vmap
    wrapper is a fresh callable per builder call, so jit retraces after a
    config-change cache clear)."""
    vm = jax.vmap(_apply_qc_fn)

    def fn(q1, q2, qc):
        obs.telemetry.count_retrace("tridiag.apply_qc_batched")
        return vm(q1, q2, qc)

    return jax.jit(fn)


def _count_merges(mode: str, n: int = 1) -> None:
    """Per-level merge-dispatch accounting (docs/eigensolver_perf.md):
    ``dlaf_dc_merges_total{mode=batched|serialized}`` counts how many
    merges ran through the level-batched vmapped dispatch vs one-at-a-time
    programs."""
    from .. import obs

    if n and obs.metrics_active():
        obs.counter("dlaf_dc_merges_total", mode=mode).inc(n)


#: Per-level deflation accounting (DLAF_ACCURACY, docs/accuracy.md):
#: while a list is installed here, :func:`_merge_ctl_pre` appends one
#: ``(merge size n, deflated count)`` pair per merge — the heavily
#: data-dependent quantity arXiv:2112.09017's D&C throughput hinges on.
#: Scoped per tree level by :func:`_tridiag_dc` (the only writer of this
#: global; the solver is not re-entrant) and emitted as
#: ``accuracy`` records ``site=tridiag_solver,
#: metric=dc_deflation_fraction`` with the level in the attrs.
_DEFLATION_SINK: Optional[list] = None


def _log_deflation(n: int, deflated: int) -> None:
    if _DEFLATION_SINK is not None:
        _DEFLATION_SINK.append((n, deflated))


@dataclasses.dataclass
class _MergeCtl:
    """Host control state of one Cuppen merge, split in two phases so the
    level-batched driver can interleave the host control scans with the
    device dispatches: :func:`_merge_ctl_pre` (sort + deflation + host
    secular solve / device-secular prep), then — once ``lam_live`` exists
    — :func:`_merge_ctl_fin` (final eigenvalue order + the pole-sort
    undo). All fields are O(n) host arrays or scalars; the O(n^2)
    workspaces stay on device."""

    n1: int
    n2: int
    neg: bool
    decoupled: bool = False
    rho_n: float = 0.0
    order: np.ndarray = None
    ds: np.ndarray = None           # sorted (negated) poles, full n
    k: int = 0
    kb: int = 0                     # secular bucket (>= k, power of two)
    idx_live: np.ndarray = None
    idx_defl: np.ndarray = None
    gi: np.ndarray = None           # deflation Givens rotations
    gj: np.ndarray = None
    gc: np.ndarray = None
    gs: np.ndarray = None
    dsk: np.ndarray = None          # live poles/weights (secular inputs)
    zsk: np.ndarray = None
    dev_secular: bool = False       # secular solve deferred to the device
    vcols: np.ndarray = None        # host secular output (k, k)
    lam_live: np.ndarray = None     # host-mode roots (ready after pre)
    lam: np.ndarray = None          # final ascending eigenvalues
    fin: np.ndarray = None
    inv_order: np.ndarray = None

    @property
    def n(self) -> int:
        return self.n1 + self.n2


def _merge_ctl_pre(lam1, lam2, z, rho_signed, use_device: bool,
                   dev_min_k: int) -> _MergeCtl:
    """Phase 1 of a merge's host control work (reference
    ``merge.h:443-629``): rank-one tear normalization, pole sort,
    deflation scan, and either the host secular solve + Gu-Eisenstat
    refinement (small k) or the device-secular prep (large k — the solve
    itself is dispatched by the caller, per merge or level-batched)."""
    n1, n2 = lam1.shape[0], lam2.shape[0]
    d = np.concatenate([lam1, lam2])
    # rho < 0: rho*z z^T is negative semidefinite, so solve the negated
    # problem -T = diag(-d) + |rho| z z^T (same eigenvectors, negated
    # eigenvalues) — the LAPACK dlaed normalization
    neg = rho_signed < 0
    rho = abs(rho_signed)
    if neg:
        d = -d
    ctl = _MergeCtl(n1=n1, n2=n2, neg=neg)
    znorm2 = float(z @ z)
    if rho * znorm2 <= 1e-300:  # fully decoupled
        lam = -d if neg else d
        fin = np.argsort(lam, kind="stable")
        ctl.decoupled = True
        ctl.lam = lam[fin]
        ctl.fin = fin
        _log_deflation(ctl.n, ctl.n)    # every pole is an eigenvalue
        return ctl
    zn = z / np.sqrt(znorm2)
    ctl.rho_n = rho_n = rho * znorm2
    # sort poles
    order = np.argsort(d, kind="stable")
    ds, zs = d[order].copy(), zn[order].copy()
    ctl.order, ctl.ds = order, ds

    # -- deflation (reference merge.h:443-508) ------------------------------
    dmax = np.abs(ds).max(initial=0.0)
    tol = 8 * _EPS * max(dmax, 1.0)
    # dropping z_j perturbs the matrix by ~rho_n*|z_j|; deflate when that
    # is below eps * ||T|| (LAPACK dlaed2 criterion)
    live = rho_n * np.abs(zs) > 8 * _EPS * max(dmax, rho_n)
    ctl.gi, ctl.gj, ctl.gc, ctl.gs = _deflation_scan(ds, zs, live, tol)
    ctl.idx_live = np.nonzero(live)[0]
    ctl.idx_defl = np.nonzero(~live)[0]
    k = ctl.k = ctl.idx_live.shape[0]
    ctl.kb = 1 << max(0, (k - 1).bit_length())
    _log_deflation(ctl.n, ctl.n - k)
    if k == 0:
        return ctl
    ctl.dsk = dsk = ds[ctl.idx_live]
    ctl.zsk = zsk = zs[ctl.idx_live]
    if use_device and k >= dev_min_k and jax.config.jax_enable_x64:
        ctl.dev_secular = True
        return ctl
    anchor, mu = _secular_roots_host(dsk, zsk, rho_n)
    ctl.lam_live = dsk[anchor] + mu
    # accurate pole-root differences: m[i, j] = d_j - lambda_i
    m = (dsk[None, :] - dsk[anchor][:, None]) - mu[:, None]
    # Gu-Eisenstat z refinement (reference laed4/dlaed3 step)
    logm = np.log(np.abs(m))
    dd = dsk[None, :] - dsk[:, None]
    np.fill_diagonal(dd, 1.0)
    logdd = np.log(np.abs(dd))
    np.fill_diagonal(logdd, 0.0)
    log_zhat2 = logm.sum(0) - logdd.sum(0)
    zhat = np.sign(zsk) * np.exp(0.5 * log_zhat2)
    # eigenvector coefficients: v_i[j] = zhat_j / (d_j - lambda_i)
    vcols = (zhat[None, :] / m)
    vcols /= np.linalg.norm(vcols, axis=1, keepdims=True)
    ctl.vcols = vcols
    return ctl


def _secular_bucket(ctl: _MergeCtl, kb: int):
    """Padded ``(ds_b, zs_b, live_kb)`` device-secular inputs at bucket
    ``kb >= ctl.k``: padded poles sit strictly above the root bound with
    z = 0, so they contribute nothing to the secular function (the
    level-batched driver re-buckets to the group's max kb; the padding
    policy is the same one the per-merge path has always used)."""
    dsk, zsk, k = ctl.dsk, ctl.zsk, ctl.k
    if kb > k:
        span = ctl.rho_n * float((zsk * zsk).sum()) + 1.0
        # scale-aware step: at |d| ~ 1e17 an absolute +1.0 would
        # round away, colliding a padded pole with a live one
        step = max(1.0, 16 * np.spacing(abs(dsk[-1]) + span))
        ds_b = np.concatenate(
            [dsk, dsk[-1] + span + step * np.arange(1.0, kb - k + 1)])
        zs_b = np.concatenate([zsk, np.zeros(kb - k)])
    else:
        ds_b, zs_b = dsk, zsk
    live_kb = np.zeros(kb, dtype=bool)
    live_kb[:k] = True
    return ds_b, zs_b, live_kb


def _merge_ctl_fin(ctl: _MergeCtl, lam_live) -> _MergeCtl:
    """Phase 2 of the host control work: final ascending eigenvalue order
    and the pole-sort undo, from the (host- or device-) solved roots."""
    n, k = ctl.n, ctl.k
    lam = np.empty(n)
    if k == 0:
        lam[:] = ctl.ds
    else:
        lam[:k] = lam_live
        lam[k:] = ctl.ds[ctl.idx_defl]
    if ctl.neg:
        lam = -lam
    fin = np.argsort(lam, kind="stable")
    ctl.lam = lam[fin]
    ctl.fin = fin
    inv_order = np.empty(n, dtype=np.int64)
    inv_order[ctl.order] = np.arange(n)
    ctl.inv_order = inv_order
    return ctl


def _givens_padded(ctl: _MergeCtl, gb: int) -> np.ndarray:
    """(gb, 4) Givens-undo array in application (reverse) order, padded
    with identity rotations (exact no-ops) to the bucket ``gb``."""
    g = ctl.gi.shape[0]
    giv = np.zeros((gb, 4))
    giv[:, 2] = 1.0                     # identity-rotation padding
    # reverse order: the undo applies rotations last-to-first
    giv[:g, 0] = ctl.gi[::-1]
    giv[:g, 1] = ctl.gj[::-1]
    giv[:g, 2] = ctl.gc[::-1]
    giv[:g, 3] = ctl.gs[::-1]
    return giv


def _givens_bucket(ctl: _MergeCtl) -> int:
    """Power-of-two bucket of this merge's deflation-rotation count."""
    g = ctl.gi.shape[0]
    return (1 << max(0, (g - 1).bit_length())) if g else 0


def _assembly_arrays(ctl: _MergeCtl, kb: int):
    """O(n)-sized qc-assembly control arrays at secular bucket ``kb``
    (shapes bucketed so the jit cache is keyed by (n, kb, givens bucket),
    not by data-dependent counts). The Givens-undo array is NOT built
    here — callers pad it once at their target bucket
    (:func:`_givens_padded`; the level-batched driver pads to the group
    max, the per-merge path to :func:`_givens_bucket`)."""
    n, k = ctl.n, ctl.k
    live_b = np.zeros(kb, dtype=bool)
    live_b[:k] = True
    rows_live = np.full(kb, n, dtype=np.int64)
    rows_live[:k] = ctl.idx_live
    nd = n - k
    rows_d = np.full(n, n, dtype=np.int64)
    rows_d[:nd] = ctl.idx_defl
    cols_d = np.full(n, n, dtype=np.int64)
    cols_d[:nd] = k + np.arange(nd)
    return live_b, rows_live, rows_d, cols_d


def _vcols_padded(ctl: _MergeCtl, kb: int) -> np.ndarray:
    """Host secular output zero-padded to bucket ``kb``."""
    vpad = np.zeros((kb, kb), dtype=np.float64)
    if ctl.k:
        vpad[:ctl.k, :ctl.k] = ctl.vcols
    return vpad


def _merge_apply(ctl: _MergeCtl, q1, q2, vcols_dev, use_device: bool,
                 mesh=None):
    """Device (or numpy-twin) tail of one merge: qc assembly + the
    blkdiag(q1, q2) @ qc gemms. Device gemms keep Q device-resident
    across the whole merge tree; only O(n) vectors cross to the host.
    Under a mesh the gemms run sharded (SUMMA via GSPMD)."""
    n1, n = ctl.n1, ctl.n
    dtype = q1.dtype

    def apply_qc(lam, qc_dev=None, qc_host=None):
        if use_device:
            return lam, _apply_qc_jit(mesh)(
                jnp.asarray(q1), jnp.asarray(q2), qc_dev)
        return lam, np.vstack([q1 @ qc_host[:n1, :], q2 @ qc_host[n1:, :]])

    if ctl.decoupled:
        if use_device:
            qc = _eye_perm_jit(n, np.dtype(dtype).name, mesh)(
                jnp.asarray(ctl.fin))
            return apply_qc(ctl.lam, qc_dev=qc)
        return apply_qc(ctl.lam, qc_host=np.eye(n, dtype=dtype)[:, ctl.fin])

    if use_device:
        if vcols_dev is None:
            vcols_dev = jnp.asarray(_vcols_padded(ctl, ctl.kb))
        live_b, rows_live, rows_d, cols_d = _assembly_arrays(ctl, ctl.kb)
        giv = _givens_padded(ctl, _givens_bucket(ctl))
        qc = _assemble_qc_jit(n, mesh)(
            vcols_dev, jnp.asarray(live_b), jnp.asarray(rows_live),
            jnp.asarray(rows_d), jnp.asarray(cols_d), jnp.asarray(giv),
            jnp.asarray(ctl.inv_order), jnp.asarray(ctl.fin))
        return apply_qc(ctl.lam, qc_dev=qc)

    # host assembly (use_device=False twin, kept as the numpy reference)
    k = ctl.k
    u_sorted = np.zeros((n, n), dtype=dtype)
    if k == 0:
        u_sorted[:] = np.eye(n, dtype=dtype)
    else:
        u_live = np.zeros((n, k), dtype=dtype)
        u_live[ctl.idx_live, :] = ctl.vcols.T.astype(dtype)
        u_sorted[:, :k] = u_live
        for t, j in enumerate(ctl.idx_defl):
            u_sorted[j, k + t] = 1.0
    # undo the Givens rotations (rows, reverse order)
    for i, j, c, s in zip(ctl.gi[::-1], ctl.gj[::-1], ctl.gc[::-1],
                          ctl.gs[::-1]):
        ri = u_sorted[i].copy()
        rj = u_sorted[j].copy()
        u_sorted[i] = c * ri - s * rj
        u_sorted[j] = s * ri + c * rj
    qc = u_sorted[ctl.inv_order][:, ctl.fin]
    return apply_qc(ctl.lam, qc_host=qc)


def _merge(lam1, q1, lam2, q2, rho_signed, use_device: bool, mesh=None):
    """One Cuppen merge (reference ``merge.h:790-887``), serialized.

    Division of labor (device path): O(n) control work (sort, deflation
    scan, liveness) on host; the secular solve on host (small k) or device
    (large k, bucketed); and ALL O(n^2) workspace assembly on device
    (:func:`_assemble_qc_impl`) — host memory stays O(n + k^2_small) per
    merge, against the round-1 review's O(n^2) host ``u_sorted``/``qc``.
    With ``mesh``, the merge gemms and their Q outputs are 2D-sharded."""
    # rank-one coupling: z from the edge rows of the subproblem eigenvectors
    z = np.concatenate([np.asarray(q1[-1, :]), np.asarray(q2[0, :])])
    ctl = _merge_ctl_pre(lam1, lam2, z, rho_signed, use_device,
                         _device_secular_min_k())
    _count_merges("serialized")
    vcols_dev = None
    if ctl.decoupled:
        return _merge_apply(ctl, q1, q2, None, use_device, mesh)
    if ctl.dev_secular:
        ds_b, zs_b, live_kb = _secular_bucket(ctl, ctl.kb)
        lam_j, vcols_dev = _secular_vcols_jit(mesh)(
            jnp.asarray(ds_b), jnp.asarray(zs_b), jnp.float64(ctl.rho_n),
            jnp.asarray(live_kb))
        # only the O(kb) eigenvalues cross to the host; the (kb, kb)
        # coefficient matrix stays device-resident (row-sharded over
        # the mesh when one is given)
        lam_live = np.asarray(lam_j)[:ctl.k]
    else:
        lam_live = ctl.lam_live
    _merge_ctl_fin(ctl, lam_live)
    return _merge_apply(ctl, q1, q2, vcols_dev, use_device, mesh)


# ---------------------------------------------------------------------------
# Level-batched merge tree (dc_level_batch=1, docs/eigensolver_perf.md)
# ---------------------------------------------------------------------------

class _TreeNode:
    """One node of the D&C split tree (host bookkeeping only)."""

    __slots__ = ("off", "n", "rho", "left", "right", "height")

    def __init__(self, off, n, rho=None, left=None, right=None, height=0):
        self.off, self.n, self.rho = off, n, rho
        self.left, self.right, self.height = left, right, height


def _merge_schedule(d, e, nb: int):
    """Host twin of the recursive splitting (same split rule, same
    pre-order d adjustments — leaf subproblems are bitwise the
    recursion's): returns ``(d_adj, leaves, levels, root)`` with
    ``levels[h]`` = all merge nodes at height ``h`` above the leaves.
    Merges within one level have disjoint index ranges and both children
    at strictly lower heights, so a whole level can run as one batch."""
    d_adj = d.copy()
    leaves: list = []
    levels: dict = {}

    def build(off, n):
        if n <= max(nb, 2):
            node = _TreeNode(off, n)
            leaves.append(node)
            return node
        # split at a tile boundary near the middle (reference impl.h:66-80
        # splits at every tile boundary; binary recursion reaches the same
        # leaves)
        m = (n // 2 // nb) * nb
        if m == 0 or m == n:
            m = n // 2
        rho = e[off + m - 1]
        d_adj[off + m - 1] -= rho
        d_adj[off + m] -= rho
        left = build(off, m)
        right = build(off + m, n - m)
        node = _TreeNode(off, n, rho, left, right,
                         1 + max(left.height, right.height))
        levels.setdefault(node.height, []).append(node)
        return node

    root = build(0, d.shape[0])
    return d_adj, leaves, levels, root


def _run_group(group, res, zmap, dev_min_k: int):
    """One same-(n1, n2) level group: host control scan for every merge
    (the scan overlaps the previously dispatched device programs — jax
    dispatch is async, so the device grinds group g's assembly gemms
    while the host runs group g+1's deflation/secular work), ONE vmapped
    secular dispatch for the device-secular members (padded to the
    group's max bucket), then ONE vmapped assembly + apply dispatch."""
    ctls = [
        _merge_ctl_pre(res[node.left][0], res[node.right][0], zmap[node],
                       node.rho, True, dev_min_k)
        for node in group
    ]
    # batched device secular at the group's shared max bucket
    dev = [(i, c) for i, c in enumerate(ctls)
           if not c.decoupled and c.dev_secular]
    vdev = {}
    if dev:
        kb_g = max(c.kb for _, c in dev)
        buckets = [_secular_bucket(c, kb_g) for _, c in dev]
        lam_j, vcols_j = _secular_vcols_batched_jit()(
            jnp.asarray(np.stack([b[0] for b in buckets])),
            jnp.asarray(np.stack([b[1] for b in buckets])),
            jnp.asarray(np.array([c.rho_n for _, c in dev])),
            jnp.asarray(np.stack([b[2] for b in buckets])))
        lam_h = np.asarray(lam_j)           # one sync for the whole group
        for lane, (i, c) in enumerate(dev):
            c.kb = kb_g                     # re-bucketed to the group max
            vdev[i] = vcols_j[lane]
            _merge_ctl_fin(c, lam_h[lane][:c.k])
    for c in ctls:
        if not c.decoupled and not c.dev_secular:
            _merge_ctl_fin(c, c.lam_live)
    # decoupled merges have no assembly to batch: per-merge dispatch
    asm = [(i, c) for i, c in enumerate(ctls) if not c.decoupled]
    for i, c in enumerate(ctls):
        if c.decoupled:
            node = group[i]
            res[node] = _merge_apply(c, res[node.left][1],
                                     res[node.right][1], None, True, None)
    _count_merges("batched", len(asm))
    _count_merges("serialized", len(ctls) - len(asm))
    if not asm:
        return
    n = group[0].n
    kb_g = max(c.kb for _, c in asm)
    arrs = [_assembly_arrays(c, kb_g) for _, c in asm]
    gb_g = max(_givens_bucket(c) for _, c in asm)
    vcols_stack = jnp.stack(
        [vdev[i] if i in vdev else jnp.asarray(_vcols_padded(c, kb_g))
         for i, c in asm])
    qc = _assemble_qc_batched_jit(n)(
        vcols_stack,
        jnp.asarray(np.stack([a[0] for a in arrs])),
        jnp.asarray(np.stack([a[1] for a in arrs])),
        jnp.asarray(np.stack([a[2] for a in arrs])),
        jnp.asarray(np.stack([a[3] for a in arrs])),
        jnp.asarray(np.stack([_givens_padded(c, gb_g) for _, c in asm])),
        jnp.asarray(np.stack([c.inv_order for _, c in asm])),
        jnp.asarray(np.stack([c.fin for _, c in asm])))
    qout = _apply_qc_batched_jit()(
        jnp.stack([res[group[i].left][1] for i, _ in asm]),
        jnp.stack([res[group[i].right][1] for i, _ in asm]),
        qc)
    for lane, (i, c) in enumerate(asm):
        res[group[i]] = (c.lam, qout[lane])


def _run_level(merges, res, use_device: bool, mesh, level_batch: bool):
    """Execute one tree level. Sharded merges (mesh given, n >=
    _SHARD_MERGE_MIN_N) and sub-2-member groups stay on the serialized
    per-merge path; everything else batches by (n1, n2) shape."""
    serial, groups = [], {}
    for node in merges:
        eff_mesh = mesh if (mesh is not None
                            and node.n >= _SHARD_MERGE_MIN_N) else None
        if not level_batch or not use_device or eff_mesh is not None:
            serial.append((node, eff_mesh))
        else:
            groups.setdefault((node.left.n, node.right.n), []).append(node)
    # singleton groups run serialized: a one-lane vmapped program would
    # only duplicate the per-merge jit cache entries
    for key in [key for key, g in groups.items() if len(g) < 2]:
        serial.extend((node, None) for node in groups.pop(key))
    for node, eff_mesh in serial:
        res[node] = _merge(res[node.left][0], res[node.left][1],
                           res[node.right][0], res[node.right][1],
                           node.rho, use_device, mesh=eff_mesh)
    if groups:
        batch_nodes = [node for g in groups.values() for node in g]
        # ONE host sync pulls every batched merge's rank-one coupling rows
        # (vs two device round trips per merge on the serialized walk)
        edges = jax.device_get(
            [(res[node.left][1][-1, :], res[node.right][1][0, :])
             for node in batch_nodes])
        zmap = {node: np.concatenate([e1, e2])
                for node, (e1, e2) in zip(batch_nodes, edges)}
        dev_min_k = _device_secular_min_k()
        for group in groups.values():
            _run_group(group, res, zmap, dev_min_k)
    # children are dead once the level completes: free their Q storage
    for node in merges:
        del res[node.left], res[node.right]


def _tridiag_dc(d, e, nb: int, use_device: bool, mesh, level_batch: bool):
    """Iterative bottom-up merge-tree driver (level order). With
    ``level_batch`` (and ``use_device``) same-shape merges of one level
    run as single vmapped dispatches; otherwise each merge runs the
    serialized :func:`_merge` — same per-merge math in either walk (the
    merges of a level are independent, so order cannot change results).

    Under ``DLAF_ACCURACY`` != "0" each level additionally emits one
    ``accuracy`` record with its deflation fraction (deflated poles /
    merged poles — the data-dependent work reduction every D&C
    throughput number implicitly depends on; docs/accuracy.md)."""
    global _DEFLATION_SINK
    from ..obs import accuracy

    collect = accuracy.enabled()
    n_total = d.shape[0]
    d_adj, leaves, levels, root = _merge_schedule(d, e, nb)
    res = {}
    for leaf in leaves:
        lam, q = stedc(d_adj[leaf.off: leaf.off + leaf.n],
                       e[leaf.off: leaf.off + leaf.n - 1])
        res[leaf] = (lam, jnp.asarray(q) if use_device else q)
    for h in sorted(levels):
        if collect:
            _DEFLATION_SINK = sink = []
        try:
            _run_level(levels[h], res, use_device, mesh, level_batch)
        finally:
            _DEFLATION_SINK = None
        if collect and sink:
            merged = sum(m for m, _ in sink)
            deflated = sum(k for _, k in sink)
            accuracy.emit(
                "tridiag_solver", "dc_deflation_fraction",
                deflated / merged if merged else 0.0, n=n_total, nb=nb,
                c=None, dtype=np.float64,
                attrs={"level": h, "merges": len(sink),
                       "merged_poles": merged, "deflated_poles": deflated})
    return res[root]


def tridiag_solver(d: np.ndarray, e: np.ndarray, nb: int,
                   use_device: bool = True, mesh=None):
    """Eigendecomposition of the real symmetric tridiagonal (d, e): returns
    ``(eigenvalues, eigenvectors)`` ascending (reference
    ``eigensolver::tridiagSolver``).

    With ``use_device=True`` the eigenvector matrix is a DEVICE-RESIDENT
    (immutable) ``jax.Array`` — Q never round-trips to the host across the
    merge tree; use ``np.asarray`` for a host copy. ``use_device=False``
    returns plain numpy arrays.

    ``mesh`` (the grid's 2D ``jax.sharding.Mesh`` with ('row', 'col')
    axes, i.e. ``grid.mesh``): shard the merge gemms, the qc workspaces,
    and the eigenvector matrix over the mesh — beyond the local-only
    reference, and the scaling path for eigenvector matrices past one
    device's HBM (the returned Q is 2D-sharded; the single-device
    remainder is the deflated secular workspace, bounded by deflation).

    Under ``dc_level_batch=1`` (auto: TPU) all same-shape merges of one
    tree level run as single vmapped device dispatches — the secular
    solves, qc assemblies, and Q·C gemms of a level become one batched
    program each instead of one dispatch per merge, and the host control
    scans overlap the in-flight device work (docs/eigensolver_perf.md).
    Sharded merges (past ``_SHARD_MERGE_MIN_N`` under a mesh) always run
    per merge."""
    if mesh is not None:
        from ..comm.grid import COL_AXIS, ROW_AXIS
        from ..common.asserts import dlaf_assert

        dlaf_assert(use_device,
                    "tridiag_solver: mesh requires use_device=True (the "
                    "numpy twin has no sharded form)")
        dlaf_assert(tuple(mesh.axis_names) == (ROW_AXIS, COL_AXIS),
                    f"tridiag_solver: mesh axes {mesh.axis_names} must be "
                    f"({ROW_AXIS!r}, {COL_AXIS!r}) — pass grid.mesh")
    d = np.asarray(d, dtype=np.float64)
    e = np.asarray(e, dtype=np.float64)
    n = d.shape[0]
    if n == 0:
        return d, (jnp.zeros((0, 0)) if use_device else np.zeros((0, 0)))
    from .. import obs
    from ..config import resolved_dc_level_batch
    from ..types import total_ops

    level_batch = resolved_dc_level_batch()
    # merge-gemm flop model: sum over levels of 2^l * (n/2^l)^3 muls+adds
    # -> (4/3) n^3 (deflation only reduces it; docs/eigensolver_perf.md)
    span = obs.entry_span("tridiag_solver", lambda: dict(
        flops=total_ops(np.dtype(np.float64), 2 * n**3 / 3, 2 * n**3 / 3),
        n=n, nb=nb, dc_level_batch=int(level_batch),
        use_device=int(use_device), sharded=int(mesh is not None)))
    with span:
        return _tridiag_dc(d, e, nb, use_device, mesh, level_batch)
