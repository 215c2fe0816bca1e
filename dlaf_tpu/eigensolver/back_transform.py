"""Back-transformations: tridiag -> band -> full eigenvectors.

TPU-native counterpart of the reference's two back-transformation stages:

* ``bt_band_to_tridiag`` (``impl.h:1-938``): apply the bulge-chasing
  Householder vectors to the eigenvector matrix. The reference re-tiles the
  HH storage into cache-friendly b x b groups; here the uniform
  (n_sweeps, n_steps, b) layout produced by the chase makes one sweep = ONE
  batched segment update, and the whole stage is a ``lax.scan`` over sweeps
  (reverse order) — static shapes, device-resident, no host round trips.

* ``bt_reduction_to_band`` (``impl.h:82-373``): apply the panel reflector
  blocks in reverse order, C <- (I - V T V^H) C per panel — two gemms + one
  small T solve per panel, trace-time unrolled.

Both consume the storage contracts of :mod:`.band_to_tridiag` and
:mod:`.reduction_to_band` directly, and both have local AND distributed
variants matching the reference (``bt_reduction_to_band/api.h:18-23``,
``bt_band_to_tridiag/api.h:21-22``):

* distributed ``bt_reduction_to_band``: per panel (reverse order) the V
  column is gathered along the mesh exactly like the forward reduction,
  T is formed redundantly, W2 = (VT)^H C is a partial einsum psum-reduced
  over the row axis, and C -= V W2 is a local update — the reference's
  trmmPanel/gemmUpdateW2/gemmTrailingMatrix trio as three einsums.
* distributed ``bt_band_to_tridiag``: the chase reflectors mix ROWS only
  and every eigenvector column is independent, so the natural TPU layout
  change is one ``all_to_all`` along the row axis converting the
  block-cyclic row sharding into a column split (each device gets ALL rows
  for 1/P of its column group's columns), the whole sweep scan runs
  locally with zero further communication, and a second ``all_to_all``
  restores the block-cyclic layout. The reference instead pipelines per-
  tile sends of HH groups (``impl.h:1-938``); on ICI the two transposes
  are cheaper than n_sweeps round trips.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import obs
from ..tile_ops import blas as tb
from ..config import register_program_cache
from ..comm import collectives as cc
from ..comm.grid import COL_AXIS, ROW_AXIS
from ..common.asserts import dlaf_assert
from ..matrix.matrix import Matrix
from ..matrix import memory
from ..matrix.panel import (DistContext, gather_sub_panel,
                            gather_sub_panel_dyn, pad_sub_panel_to_tiles,
                            tiles_of_rolled, uniform_slot_start)
from ..matrix.tiling import (_axis_perm_inv, global_to_tiles, storage_tile_grid,
                             tiles_to_global)
from ..tile_ops.lapack import larft
from ..types import ceil_div, telescope_windows
from .band_to_tridiag import TridiagResult
from .reduction_to_band import BandReduction


def chase_reflector_slots(n: int, b: int, n_sweeps: int, n_steps: int,
                          group: int) -> tuple[int, int, int]:
    """``(levels, live, null)`` of one application of the chase reflectors
    in the uniform ``(n_sweeps, n_steps, b)`` layout, from shapes alone:
    ``levels`` the sequential scan steps (``ceil(n_sweeps / group) *
    n_steps`` blocked, ``n_sweeps`` for the sweeps form: ``group=0``),
    ``live`` the ``(s, t)`` slots whose rows ``s + 1 + t b ..`` start inside
    the matrix (``ceil((n - 1 - s) / b)`` a sweep), ``null`` the slots the
    program multiplies all the same: the steps past the matrix end every
    sweep is padded with, and the sweeps that pad the last group."""
    live = sum(min(n_steps, ceil_div(n - 1 - s, b)) for s in range(n_sweeps))
    if group <= 0:
        return n_sweeps, live, n_sweeps * n_steps - live
    nblk = ceil_div(n_sweeps, group)
    return nblk * n_steps, live, nblk * group * n_steps - live


def _staircase(vcols, L: int):
    """The (L, G) staircase of G reflectors ``vcols`` (G, b), L >= b + G - 1:
    column j is reflector j shifted down j rows, zeros elsewhere. A skew:
    each row padded with zeros to L + 1 and the whole read back as (G, L),
    so row j starts at flat index j (L + 1) = j L + j. Pad, reshape, slice
    and transpose only: no loop, no scatter, no gather."""
    G, b = vcols.shape
    flat = jnp.pad(vcols, ((0, 0), (0, L + 1 - b))).reshape(-1)
    return flat[:G * L].reshape(G, L).T


def _count_slots(impl: str, slots: tuple[int, int, int]) -> None:
    """Trace-time accounting of one traced application (a program is
    traced once a process, so the sums are one call's):
    ``dlaf_bt_b2t_levels_total{impl}`` and
    ``dlaf_bt_b2t_reflectors_total{impl, kind=live|null}``
    (:func:`chase_reflector_slots`)."""
    if obs.metrics_active():
        levels, live, null = slots
        obs.counter("dlaf_bt_b2t_levels_total", impl=impl).inc(levels)
        obs.counter("dlaf_bt_b2t_reflectors_total", impl=impl,
                    kind="live").inc(live)
        obs.counter("dlaf_bt_b2t_reflectors_total", impl=impl,
                    kind="null").inc(null)


@register_program_cache
@functools.partial(jax.jit, static_argnames=("b", "n", "group"))
def _bt_b2t_blocked(v_all, tau_all, e, *, b: int, n: int, group: int):
    """E <- Q E via blocked compact-WY groups — the MXU form of the
    reference's cache-friendly b x b HH re-tiling (``bt_band_to_tridiag/
    impl.h``: larft + trmm/gemm per group, vs. our sweep-at-a-time scan's
    rank-1 row updates).

    ``group`` (= G <= b) consecutive sweeps' reflectors at one chase step
    level form a (b+G-1) x G staircase V (column j = sweep s0+j's reflector
    at row offset j; v[0]=1 heads land on the staircase diagonal). Validity
    of the reordering: reflector (s, t) overlaps (s+k, t-1) for k >= 1
    (1..k shared rows) so a lower level containing HIGHER sweeps must be
    applied first — and (s, t-1) is row-disjoint from (s+k, t), so applying
    whole levels ascending preserves the required "sweep s+1 fully before
    sweep s" order. Cross-level pairs separated by >= 2 steps are disjoint
    whenever G <= b+1 (enforced). Each level is then T = larft(V) and two
    tall gemms instead of G separate rank-1 updates: seg - (V T)(V^H seg),
    T folded into V so that its product is (L, G) x (G, G) whatever the
    window's width.
    """
    dlaf_assert(group <= b + 1, "bt_b2t blocked: group must be <= band+1")
    n_sweeps, n_steps, _ = v_all.shape
    m = e.shape[1]
    G = group
    nblk = ceil_div(n_sweeps, G)
    S = nblk * G
    _count_slots("blocked", chase_reflector_slots(n, b, n_sweeps, n_steps, G))
    v_all = jnp.pad(v_all, ((0, S - n_sweeps), (0, 0), (0, 0)))
    tau_all = jnp.pad(tau_all, ((0, S - n_sweeps), (0, 0)))
    L = b + G - 1
    rows = S + n_steps * b + b
    e_pad = jnp.pad(e, ((0, rows - n), (0, 0)))

    # iteration sequence in application order: sweep blocks descending,
    # step levels ascending within a block
    v_seq = v_all.reshape(nblk, G, n_steps, b)[::-1].transpose(0, 2, 1, 3) \
        .reshape(nblk * n_steps, G, b)
    tau_seq = tau_all.reshape(nblk, G, n_steps)[::-1].transpose(0, 2, 1) \
        .reshape(nblk * n_steps, G)
    blk_idx = jnp.repeat(jnp.arange(nblk - 1, -1, -1), n_steps)
    t_idx = jnp.tile(jnp.arange(n_steps), nblk)
    base_seq = blk_idx * G + 1 + t_idx * b

    # phase scopes (`bt_b2t.<phase>`, read by telemetry.phase_table): the
    # staircase's skew (_staircase), the T factor and V T, W = V^H seg with
    # the segment's read, and seg - (V T) W with the write-back
    def body(e_pad, xs):
        vcols, taus, base = xs
        with obs.named_span("bt_b2t.stair"):
            stair = _staircase(vcols, L)
        with obs.named_span("bt_b2t.tfactor"):
            t_mat = larft(stair, jnp.conj(taus))
            vt = stair @ t_mat
        with obs.named_span("bt_b2t.project"):
            seg = lax.dynamic_slice(e_pad, (base, 0), (L, m))
            w = tb.mm(jnp.conj(stair).T, seg)
        with obs.named_span("bt_b2t.apply"):
            seg = seg - tb.mm(vt, w)
            e_pad = lax.dynamic_update_slice(e_pad, seg, (base, 0))
        return e_pad, None

    # one traced body serves every level: scoped_step hands trace-time
    # counters (the slice products' MACs) the trip count
    e_pad, _ = lax.scan(
        obs.scoped_step("bt_b2t.scanstep", body, steps=nblk * n_steps),
        e_pad, (v_seq, tau_seq, base_seq))
    return e_pad[:n]


@register_program_cache
@functools.partial(jax.jit, static_argnames=("b", "n"))
def _bt_b2t_scan(v_all, tau_all, e, *, b: int, n: int):
    """E <- Q E with Q = prod over reflectors H^H in reverse sweep order."""
    n_sweeps, n_steps, _ = v_all.shape
    m = e.shape[1]
    seg_len = n_steps * b
    pad = seg_len + 1
    e_pad = jnp.pad(e, ((0, pad), (0, 0)))
    _count_slots("sweeps", chase_reflector_slots(n, b, n_sweeps, n_steps, 0))

    def body(e_pad, xs):
        s, v_s, tau_s = xs
        start = s + 1
        with obs.named_span("bt_b2t.project"):
            seg = lax.dynamic_slice(e_pad, (start, 0), (seg_len, m))
            seg = seg.reshape(n_steps, b, m)
            w = tb.contract("tb,tbm->tm", jnp.conj(v_s), seg)
        with obs.named_span("bt_b2t.apply"):
            seg = seg - jnp.conj(tau_s)[:, None, None] * v_s[..., None] * w[:, None, :]
            e_pad = lax.dynamic_update_slice(e_pad, seg.reshape(seg_len, m), (start, 0))
        return e_pad, None

    xs = (jnp.arange(n_sweeps - 1, -1, -1),
          v_all[::-1], tau_all[::-1])
    e_pad, _ = lax.scan(
        obs.scoped_step("bt_b2t.scanstep", body, steps=n_sweeps), e_pad, xs)
    return e_pad[:n]


def _bt_b2t_params():
    """(impl, group) from config: how to apply the chase reflectors."""
    from ..config import get_configuration

    cfg = get_configuration()
    dlaf_assert(cfg.bt_b2t_impl in ("blocked", "sweeps"),
                f"bt_b2t_impl must be 'blocked' or 'sweeps', got {cfg.bt_b2t_impl!r}")
    return cfg.bt_b2t_impl, cfg.bt_b2t_group


def _effective_group(b: int, n_sweeps: int, group: int) -> int:
    """Effective compact-WY group size: 0 means auto — band size on MXU
    hardware (big-gemm shaped), min(band, 64) on CPU hosts where the extra
    (band+G)/band flops outweigh gemm width (measured: G=64 fastest at
    band=256 on one core). Values are clamped to [1, min(band+1, n_sweeps)]
    (the disjointness bound of the level reordering; see _bt_b2t_blocked)."""
    if group <= 0:
        from ..tpu_info import default_device
        from ..types import Device

        try:
            on_cpu = default_device() == Device.CPU
        except Exception:
            on_cpu = True
        group = min(b, 64) if on_cpu else b
    return max(1, min(group, b + 1, n_sweeps))


def _chase_program(v_all, *, b: int, n: int, impl: str, group: int):
    """``(jitted program, its static kwargs)`` for ``impl``."""
    if impl == "blocked":
        g = _effective_group(b, int(v_all.shape[0]), group)
        return _bt_b2t_blocked, dict(b=b, n=n, group=g)
    return _bt_b2t_scan, dict(b=b, n=n)


def _apply_chase_reflectors(v_all, tau_all, e, *, b: int, n: int,
                            impl: str, group: int):
    fn, static = _chase_program(v_all, b=b, n=n, impl=impl, group=group)
    return fn(v_all, tau_all, e, **static)


def _build_dist_bt_b2t(dist, mesh, *, b: int, cplx: bool, n_sweeps: int,
                       impl: str = "blocked", group: int = 0):
    """Distributed chase back-transform: two layout transposes around the
    purely local sweep scan (see module docstring)."""
    n = dist.size.row
    nb = dist.block_size.row
    Pr = dist.grid_size.row
    Sr, _, ltr, ltc = storage_tile_grid(dist)
    ntr = dist.nr_tiles.row
    chunk = ceil_div(ltc, Pr) if ltc else 0
    ltc_pad = chunk * Pr

    # static permutations: a2a slot (p*ltr + l) <-> global row tile g
    # (global->slot map shared with tiling's storage order)
    row_order = [0] * Sr
    slots = _axis_perm_inv(ntr, Pr, dist.source_rank.row, ltr)
    for g, slot in enumerate(slots):
        row_order[g] = slot
    used = set(slots)
    pads = [s for s in range(Sr) if s not in used]
    for i, s in enumerate(pads):
        row_order[ntr + i] = s
    inv_order = [0] * Sr
    for pos, slot in enumerate(row_order):
        inv_order[slot] = pos
    row_order = jnp.array(row_order, dtype=jnp.int32)
    inv_order = jnp.array(inv_order, dtype=jnp.int32)

    def run(v_all, tau_all, phase, lt):
        x = jnp.pad(lt, ((0, 0), (0, ltc_pad - ltc), (0, 0), (0, 0)))
        # block-cyclic rows -> full rows x 1/P of my column group's columns
        x = cc.all_to_all(x, ROW_AXIS, split_axis=1, concat_axis=0)
        x = x[row_order]                              # global row-tile order
        e = x.transpose(0, 2, 1, 3).reshape(Sr * nb, chunk * nb)[:n]
        if cplx:
            e = e * phase[:, None]
        if n_sweeps:
            e = _apply_chase_reflectors(v_all, tau_all, e, b=b, n=n,
                                        impl=impl, group=group)
        e = jnp.pad(e, ((0, Sr * nb - n), (0, 0)))
        x = e.reshape(Sr, nb, chunk, nb).transpose(0, 2, 1, 3)
        x = x[inv_order]
        x = cc.all_to_all(x, ROW_AXIS, split_axis=0, concat_axis=1)
        return x[:, :ltc]

    return shard_map(run, mesh=mesh,
                     in_specs=(P(), P(), P(), P(ROW_AXIS, COL_AXIS)),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


@register_program_cache
@functools.lru_cache(maxsize=32)
def _dist_bt_b2t_cached(dist, mesh, b, cplx, n_sweeps, impl, group):
    return jax.jit(_build_dist_bt_b2t(dist, mesh, b=b, cplx=cplx,
                                      n_sweeps=n_sweeps, impl=impl,
                                      group=group))


def _local_phase(name: str, program: bool = True):
    """Host phase of the local branch (``stage.bt_band_to_tridiag.<name>``,
    unfenced: the wall of an async dispatch or of the hand-off's upload);
    one around a dispatched ``program`` counts it as the entry's
    (``dlaf_entry_programs_total``), as ``reduction_to_band._program_phase``
    does."""
    if program and obs.metrics_active():
        obs.counter("dlaf_entry_programs_total",
                    entry="bt_band_to_tridiag").inc()
    return obs.span(f"stage.bt_band_to_tridiag.{name}", fenced=False)


def _bt_b2t_local_array(tri: TridiagResult, e) -> jax.Array:
    n = tri.d.shape[0]
    cplx = np.issubdtype(tri.v.dtype, np.complexfloating)
    # the chase ran on the host: its reflectors are uploaded in every call
    with _local_phase("upload", program=False):
        e = memory.as_device(e)
        v_all, tau_all = memory.as_device(tri.v), memory.as_device(tri.tau)
        phase = memory.as_device(tri.phase) if cplx else None
    if cplx:
        e = e.astype(tri.v.dtype) * phase[:, None]
    if tri.v.shape[0] == 0:
        return e
    impl, group = _bt_b2t_params()
    fn, static = _chase_program(v_all, b=tri.band, n=n, impl=impl,
                                group=group)
    with _local_phase("apply"):
        # program telemetry (DLAF_PROGRAM_TELEMETRY): off = passthrough
        return obs.telemetry.call("bt_band_to_tridiag.local", fn, v_all,
                                  tau_all, e, **static)


def _bt_b2t_entry_span(tri: TridiagResult, m: int, impl: str, group: int,
                       grid: str):
    """Entry span: chase back-transform flop model n^2*m muls + n^2*m
    adds (one rank-1 segment update per live reflector: ~n^2/(2b) of them
    at 4bm real operations each; docs/eigensolver_perf.md)."""
    from ..types import total_ops

    n = tri.d.shape[0]
    dt = np.dtype(tri.v.dtype)
    return obs.entry_span("bt_band_to_tridiag", lambda: dict(
        flops=total_ops(dt, n**2 * m, n**2 * m), n=n, m=m, band=tri.band,
        dtype=dt.name, impl=impl, group=group, grid=grid))


def bt_band_to_tridiag(tri: TridiagResult, evecs):
    """Eigenvectors of the BAND matrix from eigenvectors of the tridiagonal:
    apply the complex phases (see band_to_tridiag), then the chase reflectors
    in reverse sweep order.

    ``evecs`` may be an array (local; returns an array) or a
    :class:`~dlaf_tpu.matrix.matrix.Matrix` (local or distributed; returns a
    Matrix — reference distributed overload ``bt_band_to_tridiag/api.h:21-22``).
    """
    impl_l, group_l = _bt_b2t_params()
    # span attr carries the RESOLVED group (same meaning as the
    # distributed span below, where it keys the compiled-program cache)
    group_l = _effective_group(tri.band, int(tri.v.shape[0]), group_l) \
        if impl_l == "blocked" else 0
    if not isinstance(evecs, Matrix):
        m = evecs.shape[1] if getattr(evecs, "ndim", 2) > 1 else 1
        with _bt_b2t_entry_span(tri, m, impl_l, group_l, "1x1"):
            return _bt_b2t_local_array(tri, evecs)
    if evecs.grid is None or evecs.grid.num_devices == 1:
        with _bt_b2t_entry_span(tri, evecs.size.col, impl_l, group_l, "1x1"):
            with _local_phase("to_global"):
                arr = tiles_to_global(evecs.storage, evecs.dist)
            out = _bt_b2t_local_array(tri, arr)
            with _local_phase("to_tiles"):
                storage = global_to_tiles(out, evecs.dist)
        return Matrix(evecs.dist, storage, evecs.grid)
    dlaf_assert(evecs.size.row == tri.d.shape[0],
                "bt_band_to_tridiag: eigenvector rows != n")
    dlaf_assert(evecs.block_size.row == evecs.block_size.col,
                "bt_band_to_tridiag: square blocks only (distributed)")
    cplx = bool(np.issubdtype(tri.v.dtype, np.complexfloating))
    storage = evecs.storage
    if cplx and not np.issubdtype(storage.dtype, np.complexfloating):
        storage = storage.astype(tri.v.dtype)
    # normalized cache key = the resolved (impl_l, group_l) from entry:
    # group is pre-clamped and irrelevant for "sweeps", so equivalent
    # configurations share one compiled program — and the span attrs
    # above carry exactly the values that key the cache
    fn = _dist_bt_b2t_cached(evecs.dist, evecs.grid.mesh, tri.band, cplx,
                             int(tri.v.shape[0]), impl_l, group_l)
    with _bt_b2t_entry_span(
            tri, evecs.size.col, impl_l, group_l,
            f"{evecs.dist.grid_size.row}x{evecs.dist.grid_size.col}"):
        out = fn(memory.as_device(tri.v), memory.as_device(tri.tau),
                 memory.as_device(tri.phase), storage)
    return Matrix(evecs.dist, out, evecs.grid)


@register_program_cache
@functools.partial(jax.jit, static_argnames=("nb", "la"))
def _bt_r2b_local(a_v, taus, e, *, nb: int, la: bool = False):
    """C <- (I - V T V^H) C per reflector block, reverse order.

    ``la`` (``bt_lookahead=1``, docs/eigensolver_perf.md): the next
    block's tril/larft T-factor chain reads only the CONSTANT (a_v, taus)
    storage — never the updated ``e`` — so it is emitted BEFORE the
    current block's bulk trmm+gemm application, freeing XLA's scheduler
    to hide the latency-bound chain under the MXU bulk (the PR-2
    look-ahead treatment; same ops either way, bitwise identical)."""
    n = a_v.shape[0]
    nt = ceil_div(n, nb) if n else 0
    ks = [k for k in range(nt - 2, -1, -1) if n - (k + 1) * nb > 0]

    def chain(k):
        k1 = (k + 1) * nb
        m_p = n - k1
        vf = a_v[k1:, k * nb: k * nb + nb]
        v = jnp.tril(vf, -1) + jnp.eye(m_p, nb, dtype=a_v.dtype)
        return k1, v, larft(v, taus[k])

    if la:
        pend = chain(ks[0]) if ks else None
        for i in range(len(ks)):
            k1, v, t = pend
            # emit block i+1's T chain ahead of block i's bulk application
            pend = chain(ks[i + 1]) if i + 1 < len(ks) else None
            w = t @ tb.mm(jnp.conj(v).T, e[k1:])
            e = e.at[k1:].add(-tb.mm(v, w))
        return e
    for k in ks:
        k1, v, t = chain(k)
        w = t @ tb.mm(jnp.conj(v).T, e[k1:])
        e = e.at[k1:].add(-tb.mm(v, w))
    return e


def _build_dist_bt_r2b(dist_a, dist_c, mesh, band, la: bool = False):
    """Distributed reflector-block back-transform C <- (I - V T V^H) C,
    panels in reverse order (reference ``bt_reduction_to_band/impl.h:82-373``:
    trmmPanel W=VT, gemmUpdateW2 W2=W^H C, gemmTrailingMatrix C-=V W2).

    ``band`` <= block size (must divide it): panel p is the width-band slice
    of V at element columns [p*band, (p+1)*band), acting on C rows >=
    (p+1)*band — static sub-tile offsets, element-level masks, same scheme
    as the generalized forward reduction (beyond-reference: the reference's
    distributed back-transform exists only for band == block size).

    ``la`` (``bt_lookahead=1``): panel p+1's whole chain — the V
    sub-panel gather (one COL bcast + one ROW all_gather), larft, and the
    C-side masks — reads only the CONSTANT (lt_a, taus), so it is emitted
    BEFORE panel p's bulk W2/update contractions; XLA's async collective
    start/done pairs can then run the ICI transfer and the latency-bound
    T factor while the MXU grinds the bulk (the PR-4 comm look-ahead
    treatment, docs/comm_overlap.md). Hoisted chains count under
    ``dlaf_comm_overlapped_total{algo="bt_r2b_dist"}``. Bitwise identical
    either way — a pure emission reorder."""
    nt = dist_a.nr_tiles.row
    nb = dist_a.block_size.row
    n = dist_a.size.row
    b = band
    npan = ceil_div(n, b) - 1 if n else 0

    def run(lt_a, taus, lt_c):
        ctx_a = DistContext(dist_a)
        ctx_c = DistContext(dist_c)
        arange_nb = jnp.arange(nb)

        def chain(p):
            """Panel p's hoistable prefix (constant-storage reads only);
            None when this step is a no-op on every rank (trace-time)."""
            bdy = (p + 1) * b
            # -- gather the full V sub-panel (element rows >= bdy) -------
            got = gather_sub_panel(ctx_a, lt_a, pb=p * b, b=b, n=n)
            if got is None:
                return None
            vfull, _, tr0, ro, _, _ = got  # A-side masks unused: the
            # C-side masks below are recomputed from ctx_c
            m_p = (nt - tr0) * nb - ro
            v = jnp.tril(vfull, -1) + jnp.eye(m_p, b, dtype=vfull.dtype)
            t = larft(v, taus[p])
            vt = pad_sub_panel_to_tiles(ctx_a, v, tr0=tr0, ro=ro)
            luc = ctx_c.row_start(tr0)
            nrows_c = ctx_c.ltr - luc
            if nrows_c <= 0:
                return None
            g_rows_c = ctx_c.g_rows(luc, nrows_c)
            g_erows_c = g_rows_c[:, None] * nb + arange_nb[None, :]
            rv_c_e = (g_erows_c >= bdy) & (g_erows_c < n)
            sel = jnp.clip(g_rows_c - tr0, 0, nt - tr0 - 1)
            v_my = jnp.where(rv_c_e[:, :, None], vt[sel],
                             jnp.zeros((nrows_c, nb, b),
                                       dtype=vfull.dtype))
            return luc, t, v_my

        def update(ch, lt_c):
            """Panel p's bulk: W2 = T (V^H C) psum'd over 'row', then
            C -= V W2 — the only reads of the updated C."""
            luc, t, v_my = ch
            cpart = lt_c[luc:]
            w2 = tb.contract("rab,rcad->cbd", jnp.conj(v_my), cpart)
            w2 = cc.all_reduce(w2, ROW_AXIS)     # (ltc_c, b, nb_c) = V^H C
            w2 = tb.contract("xb,cbd->cxd", t, w2)
            upd = tb.contract("rab,cbd->rcad", v_my, w2)
            return lt_c.at[luc:].add(-upd)

        # uniform per-step phase scopes (`bt_r2b.step<p>.<phase>`,
        # docs/observability.md critical-path attribution): panel = the
        # reflector gather + larft chain, bulk = the W2/apply update. The
        # reverse sweep keeps the GLOBAL panel index p in the name; under
        # lookahead panel p's chain is emitted (and scoped) ahead of the
        # pending panel's bulk — the overlap the critpath report must see.
        ps = range(npan - 1, -1, -1)
        if la:
            pend = pend_p = None
            for p in ps:
                with obs.named_span(f"bt_r2b.step{p:03d}.panel"):
                    ch = chain(p)  # emitted ahead of pend's bulk update
                if ch is None:
                    continue
                if pend is not None:
                    # this chain's collectives overlap the pending bulk
                    cc.record_overlapped("bt_r2b_dist", ROW_AXIS, 1)
                    cc.record_overlapped("bt_r2b_dist", COL_AXIS, 1)
                    with obs.named_span(f"bt_r2b.step{pend_p:03d}.bulk"):
                        lt_c = update(pend, lt_c)
                pend, pend_p = ch, p
            if pend is not None:
                with obs.named_span(f"bt_r2b.step{pend_p:03d}.bulk"):
                    lt_c = update(pend, lt_c)
            return lt_c
        for p in ps:
            with obs.named_span(f"bt_r2b.step{p:03d}.panel"):
                ch = chain(p)
            if ch is None:
                continue
            with obs.named_span(f"bt_r2b.step{p:03d}.bulk"):
                lt_c = update(ch, lt_c)
        return lt_c

    return shard_map(run, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(), P(ROW_AXIS, COL_AXIS)),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


def _build_dist_bt_r2b_scan(dist_a, dist_c, mesh, band, la: bool = False):
    """``lax.scan`` form of the distributed back-transform
    (``dist_step_mode="scan"``): one compiled reflector-block step looped
    ``ceil(n/b) - 1`` times in reverse — config #5's back-transform has
    the same per-panel unrolled-compile exposure as the forward reduction
    (docs/DESIGN.md). Uses the shared traced-``p`` rolled sub-panel
    gather. TELESCOPED like the forward reduction, mirrored for the
    reverse sweep: panel ``p`` only touches C rows at element >= (p+1)*b,
    so early segments (large ``p``) work on a small bottom window of the
    row-slot axis that grows as the sweep ascends; the W2 psum and the C
    update run over the window's slots under traced element masks.

    The body already emits its panel gather (COL bcast + ROW all_gather)
    and larft AHEAD of the bulk contractions, reading only the constant
    (sub_a, taus) — overlap by construction, like the PR-4 scan bodies;
    ``la`` (``bt_lookahead=1``) labels the structure and books the
    per-body overlap counters (trace-time: once per telescope segment,
    not per executed step — the PR-4 scan caveat)."""
    nt = dist_a.nr_tiles.row
    nb = dist_a.block_size.row
    n = dist_a.size.row
    Pr, Qc = dist_a.grid_size.row, dist_a.grid_size.col
    b = band
    npan = ceil_div(n, b) - 1 if n else 0

    def run(lt_a, taus, lt_c):
        ctx_a = DistContext(dist_a)
        ctx_c = DistContext(dist_c)
        arange_nb = jnp.arange(nb)

        def make_step(lu_off, lc_off, ltr_w):
            base = lu_off * Pr
            sub_a = lt_a[lu_off:, lc_off:]

            def step(sub_c, i):
                p = npan - 1 - i
                if la:
                    # the gather below reads only constant storage and is
                    # emitted ahead of this body's bulk contractions
                    cc.record_overlapped("bt_r2b_dist_scan", ROW_AXIS, 1)
                    cc.record_overlapped("bt_r2b_dist_scan", COL_AXIS, 1)
                pan, bdy, _, _, _, _, _ = gather_sub_panel_dyn(
                    ctx_a, sub_a, p=p, b=b, n=n,
                    row_off=lu_off, col_off=lc_off)
                m_w = (nt - base) * nb
                v = jnp.tril(pan, -1) + jnp.eye(m_w, b, dtype=pan.dtype)
                t = larft(v, taus[p])
                vt = tiles_of_rolled(ctx_a, v, bdy, base * nb)

                g_rows_c = ctx_c.g_rows(lu_off, ltr_w)
                g_erows_c = g_rows_c[:, None] * nb + arange_nb[None, :]
                rv_c_e = (g_erows_c >= bdy) & (g_erows_c < n)
                sel = jnp.clip(g_rows_c - base, 0, nt - base - 1)
                v_my = jnp.where(rv_c_e[:, :, None], vt[sel],
                                 jnp.zeros((ltr_w, nb, b), dtype=pan.dtype))
                w2 = tb.contract("rab,rcad->cbd", jnp.conj(v_my), sub_c)
                w2 = cc.all_reduce(w2, ROW_AXIS)
                w2 = tb.contract("xb,cbd->cxd", t, w2)
                upd = tb.contract("rab,cbd->rcad", v_my, w2)
                return sub_c - upd, None

            return step

        if npan <= 0:
            return lt_c
        # telescoped segments (reverse sweep: segment [i0, i0+len) covers
        # p = npan-1-i0 down to p_lo = npan-i0-len; its window covers
        # every row tile >= (p_lo*b)//nb)
        def window(pos, seg_len):
            p_lo = npan - pos - seg_len
            t_min = (p_lo * b) // nb
            return (uniform_slot_start(t_min, Pr),
                    uniform_slot_start(t_min, Qc))

        for (lu_off, lc_off), i0, seg_len in telescope_windows(npan, window):
            sub_c = lt_c[lu_off:]
            # index-free scope: one traced body per telescope segment —
            # critpath reconstructs per-step timing by occurrence order
            sub_c, _ = jax.lax.scan(
                obs.scoped_step(
                    "bt_r2b.scanstep",
                    make_step(lu_off, lc_off, ctx_c.ltr - lu_off),
                    steps=seg_len), sub_c,
                jnp.arange(i0, i0 + seg_len))
            lt_c = lt_c.at[lu_off:].set(sub_c)
        return lt_c

    return shard_map(run, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(), P(ROW_AXIS, COL_AXIS)),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


@register_program_cache
@functools.lru_cache(maxsize=32)
def _dist_bt_r2b_cached(dist_a, dist_c, mesh, band, scan=False, la=False):
    build = _build_dist_bt_r2b_scan if scan else _build_dist_bt_r2b
    return jax.jit(build(dist_a, dist_c, mesh, band, la=la))


def _bt_r2b_entry_span(red: BandReduction, n: int, m: int, la: bool,
                       grid: str):
    """Entry span (docs/observability.md): block-reflector application
    flop model n^2*m muls + n^2*m adds (docs/eigensolver_perf.md)."""
    from .. import obs
    from ..types import total_ops

    dt = np.dtype(red.matrix.dtype)
    return obs.entry_span("bt_reduction_to_band", lambda: dict(
        flops=total_ops(dt, n**2 * m, n**2 * m), n=n, m=m,
        band=red.band, dtype=dt.name, bt_lookahead=int(la), grid=grid))


def bt_reduction_to_band(red: BandReduction, evecs):
    """Eigenvectors of the ORIGINAL matrix from eigenvectors of the band
    matrix: apply the panel reflector blocks in reverse order.

    Local when ``red.matrix`` is local (``evecs`` array -> array); distributed
    when both ``red.matrix`` and ``evecs`` live on a grid (Matrix -> Matrix,
    reference ``bt_reduction_to_band/api.h:18-23`` distributed overload).

    Under ``bt_lookahead=1`` (auto: TPU) reflector block k+1's T-factor
    chain — and, distributed, its panel gather collectives — is emitted
    ahead of block k's bulk application (docs/eigensolver_perf.md);
    results are bitwise identical either way.
    """
    from ..config import resolved_bt_lookahead

    la = resolved_bt_lookahead()
    a = red.matrix
    if isinstance(evecs, Matrix) and a.grid is not None and a.grid.num_devices > 1:
        dlaf_assert(evecs.grid is not None
                    and evecs.grid.size == a.grid.size,
                    "bt_reduction_to_band: V and C must share the grid")
        dlaf_assert(evecs.block_size.row == a.block_size.row,
                    "bt_reduction_to_band: C row block != V block")
        dlaf_assert(evecs.size.row == a.size.row,
                    "bt_reduction_to_band: C rows != n")
        dlaf_assert(a.block_size.row % red.band == 0,
                    "bt_reduction_to_band: band must divide the block size")
        storage = evecs.storage
        if storage.dtype != a.dtype:
            storage = storage.astype(a.dtype)
        from ..config import resolve_step_mode

        # the builders trace ceil(n/band) - 1 reflector-block steps
        fn = _dist_bt_r2b_cached(a.dist, evecs.dist, a.grid.mesh, red.band,
                                 scan=resolve_step_mode(max(
                                     -(-a.size.row // red.band) - 1, 1))
                                 == "scan", la=la)
        with _bt_r2b_entry_span(
                red, a.size.row, evecs.size.col, la,
                f"{a.dist.grid_size.row}x{a.dist.grid_size.col}"):
            from .. import obs

            # program telemetry (DLAF_PROGRAM_TELEMETRY): off = passthrough
            out = obs.telemetry.call("bt_reduction_to_band.dist", fn,
                                     a.storage, memory.as_device(red.taus),
                                     storage)
        return Matrix(evecs.dist, out, evecs.grid)
    a_v = tiles_to_global(a.storage, a.dist)
    arr = evecs
    ret_matrix = isinstance(evecs, Matrix)
    if ret_matrix:
        arr = tiles_to_global(evecs.storage, evecs.dist)
    e = memory.as_device(arr).astype(a_v.dtype)
    with _bt_r2b_entry_span(red, a.size.row,
                            e.shape[1] if e.ndim > 1 else 1, la, "1x1"):
        from .. import obs

        out = obs.telemetry.call("bt_reduction_to_band.local",
                                 _bt_r2b_local, a_v,
                                 memory.as_device(red.taus), e, nb=red.band,
                                 la=la)
    if ret_matrix:
        return Matrix(evecs.dist, global_to_tiles(out, evecs.dist), evecs.grid)
    return out
