"""tpu_blas — BLAS tile operations on (batched) 2D blocks.

TPU-native counterpart of the reference's ``blas/tile.h:139-517`` (tile-level
``gemm/hemm/her2k/herk/trmm/trsm`` dispatched to blaspp on CPU and cuBLAS on
GPU) plus the ``add`` extension (``blas/tile_extensions.h``). Here every op is
a pure jnp function on arrays whose last two axes are the tile; leading axes
are batch dims, so one call expresses the reference's per-tile task fan-out as
a single batched XLA op that tiles onto the MXU (the idiomatic TPU form of
"many small gemms" is one big batched gemm).

Conventions:
* ``op``: 'N' (none), 'T' (transpose), 'C' (conjugate transpose) — the
  reference's ``blas::Op``.
* ``side``: 'L'/'R'; ``uplo``: 'L'/'U'/'G' (general); ``diag``: 'N'/'U' —
  ``blas::{Side,Uplo,Diag}``.
* Triangular inputs are *stored* triangles: the opposite triangle of the
  argument may hold garbage and is never read (LAPACK storage semantics).
* No in-place: ops return new values; XLA aliases buffers when it can.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax


def _op(a, op: str):
    if op == "N":
        return a
    if op == "T":
        return jnp.swapaxes(a, -1, -2)
    if op == "C":
        return jnp.conj(jnp.swapaxes(a, -1, -2))
    raise ValueError(f"bad op {op!r}")


def _mxu_f64(*arrs, dims) -> bool:
    """Trace-time decision: route this f64/complex128 contraction through
    the error-free int8 MXU path (config knob ``f64_gemm``; see
    tile_ops/ozaki.py)? Programs caching this decision register with
    ``config.register_program_cache`` so knob changes re-trace."""
    from ..config import get_configuration, resolved_f64_gemm

    if resolved_f64_gemm() != "mxu":
        return False
    if any(x.dtype not in (jnp.float64, jnp.complex128) for x in arrs):
        return False
    return min(dims) >= get_configuration().f64_gemm_min_dim


def _oz_slices() -> int:
    """Resolved slice count: the configured value, or — for the 0 "auto"
    default — 7 on f64-emulating backends (TPU: the platform's ~47-48-bit
    double-f32 arithmetic already bounds every surrounding op, so the
    49-bit dot loses nothing and drops 8 of 36 gemms) and 8 (f64-grade
    dots) where f64 is native. Keyed on the PROCESS default backend: a
    trace explicitly placed on a non-default backend (jax.default_device)
    inherits the process tier — set the knob explicitly for that case.
    The auto resolution is announced once per (backend, count) on stderr
    so the tier in effect is never silent. See
    Configuration.f64_gemm_slices."""
    from ..config import get_configuration

    s = int(get_configuration().f64_gemm_slices)
    if s:
        return s
    import jax

    backend = jax.default_backend()
    s = 7 if backend == "tpu" else 8
    from ..obs import get_logger

    # once per (backend, slices): the accuracy tier in effect (56 vs 49
    # mantissa bits) is visible, not silent (round-2 advisory)
    get_logger("config").warning_once(
        ("f64_gemm_slices", backend, s),
        f"f64_gemm_slices=0 (auto) resolved to {s} for default backend "
        f"{backend!r} (~{7 * s} mantissa bits); traces placed on other "
        "backends inherit this — set the knob explicitly to override",
        knob="f64_gemm_slices", backend=backend, choice=s)
    return s


def mm_mxu(a, b):
    """``a @ b`` FORCED onto the int8 MXU path (tile_ops.ozaki), regardless
    of the ``f64_gemm`` knob — the gemm primitive of algorithm paths that
    are themselves MXU-routed by their own knob (the local "ozaki" cholesky
    sweep's panel application). Complex operands promote like :func:`mm`."""
    from . import ozaki

    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        ac = a.astype(jnp.complex128)
        bc = b.astype(jnp.complex128)
        return ozaki.matmul_c128(ac, bc, slices=_oz_slices())
    return ozaki.matmul_f64(a, b, slices=_oz_slices())


def _mm(a, b):
    """Central matmul of the level-3 ops, with the f64_gemm="mxu" reroute."""
    if _mxu_f64(a, b, dims=(a.shape[-2], a.shape[-1], b.shape[-1])):
        return mm_mxu(a, b)
    return a @ b


def mm(a, b):
    """Public matmul with the ``f64_gemm="mxu"`` reroute — for algorithm code
    whose products don't fit a named BLAS op (whole-panel compositions,
    gathered blocks). Native path is exactly ``a @ b``."""
    return _mm(a, b)


def contract(subscripts: str, x, y):
    """Two-operand einsum with the ``f64_gemm="mxu"`` reroute.

    Native path: ``jnp.einsum(subscripts, x, y, preferred_element_type=...)``
    — bit-identical to the raw einsums the distributed algorithms used. On
    the mxu path the contraction is factored into (transpose → flatten →
    ozaki matmul → unflatten → transpose), which is exactly how XLA lowers
    einsum to dot_general, so the int8 path sees one large product.

    Supported: no repeated labels within an operand, every label of each
    operand present in the other operand and/or the output (no implicit
    broadcasting). Labels shared by both operands and the output batch;
    shared labels absent from the output contract.
    """
    lhs, out = subscripts.split("->")
    s1, s2 = lhs.split(",")
    assert len(set(s1)) == len(s1) and len(set(s2)) == len(s2), subscripts
    batch = [c for c in s1 if c in s2 and c in out]
    contracted = [c for c in s1 if c in s2 and c not in out]
    free1 = [c for c in s1 if c not in s2]
    free2 = [c for c in s2 if c not in s1]
    assert all(c in out for c in free1 + free2), subscripts
    assert set(out) == set(batch + free1 + free2), subscripts

    dims1 = dict(zip(s1, x.shape))
    dims2 = dict(zip(s2, y.shape))
    if _mxu_f64(x, y, dims=(max(int(np.prod([dims1[c] for c in free1], dtype=np.int64)), 1),
                            max(int(np.prod([dims1[c] for c in contracted], dtype=np.int64)), 1),
                            max(int(np.prod([dims2[c] for c in free2], dtype=np.int64)), 1))):
        from . import ozaki

        xt = jnp.transpose(x, [s1.index(c) for c in batch + free1 + contracted])
        yt = jnp.transpose(y, [s2.index(c) for c in batch + contracted + free2])
        bshape = tuple(dims1[c] for c in batch)
        f1 = int(np.prod([dims1[c] for c in free1], dtype=np.int64)) if free1 else 1
        f2 = int(np.prod([dims2[c] for c in free2], dtype=np.int64)) if free2 else 1
        kk = int(np.prod([dims1[c] for c in contracted], dtype=np.int64)) if contracted else 1
        mmfn = (ozaki.matmul_c128 if jnp.iscomplexobj(x) or jnp.iscomplexobj(y)
                else ozaki.matmul_f64)
        xf = xt.reshape(bshape + (f1, kk))
        yf = yt.reshape(bshape + (kk, f2))
        if jnp.iscomplexobj(xf) != jnp.iscomplexobj(yf):
            xf = xf.astype(jnp.complex128)
            yf = yf.astype(jnp.complex128)
        full = mmfn(xf, yf, slices=_oz_slices())
        full = full.reshape(bshape + tuple(dims1[c] for c in free1)
                            + tuple(dims2[c] for c in free2))
        order = batch + free1 + free2
        return jnp.transpose(full, [order.index(c) for c in out])
    return jnp.einsum(subscripts, x, y,
                      preferred_element_type=jnp.result_type(x, y))


def tri_mask(a, uplo: str, *, k: int = 0):
    """Keep the stored triangle of the last-two-dims block."""
    if uplo == "G":
        return a
    if uplo == "L":
        return jnp.tril(a, k=k)
    if uplo == "U":
        return jnp.triu(a, k=-k)
    raise ValueError(f"bad uplo {uplo!r}")


def hermitian_from(a, uplo: str):
    """Full (conjugate-)symmetric block from its stored triangle, e.g. for
    ``hemm``/``hegst`` inputs. Diagonal imaginary parts are dropped for
    complex dtypes (Hermitian diagonal is real by definition)."""
    if uplo == "G":
        return a
    tri = tri_mask(a, uplo, k=-1)
    diag = jnp.real(_diag_of(a)) if jnp.iscomplexobj(a) else _diag_of(a)
    d = _embed_diag(diag, a.shape, a.dtype)
    return tri + jnp.conj(jnp.swapaxes(tri, -1, -2)) + d


def _diag_of(a):
    return jnp.diagonal(a, axis1=-2, axis2=-1)


def _embed_diag(d, shape, dtype):
    n = shape[-1]
    eye = jnp.eye(n, dtype=dtype)
    return d[..., None] * eye


def _tri(a, uplo: str, diag: str):
    """Triangle of ``a`` with optional implicit unit diagonal."""
    t = tri_mask(a, uplo)
    if diag == "U":
        n = a.shape[-1]
        t = t - _embed_diag(_diag_of(t), a.shape, a.dtype) + jnp.eye(n, dtype=a.dtype)
    return t


# ---------------------------------------------------------------------------
# Level-3 ops (reference blas/tile.h:139-517)
# ---------------------------------------------------------------------------

def gemm(a, b, c=None, *, alpha=1.0, beta=0.0, op_a: str = "N", op_b: str = "N"):
    """``c = alpha op_a(a) op_b(b) + beta c`` (reference ``tile::gemm``)."""
    prod = _mm(_op(a, op_a), _op(b, op_b))
    out = alpha * prod
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out.astype(a.dtype)


def hemm(side: str, uplo: str, a, b, c=None, *, alpha=1.0, beta=0.0):
    """``c = alpha A b + beta c`` (side='L') with Hermitian ``A`` stored in
    ``uplo`` (reference ``tile::hemm``)."""
    af = hermitian_from(a, uplo)
    prod = _mm(af, b) if side == "L" else _mm(b, af)
    out = alpha * prod
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out.astype(b.dtype)


def herk(uplo: str, op_a: str, a, c, *, alpha=1.0, beta=1.0):
    """``c = alpha op_a(a) op_a(a)^H + beta c`` on the ``uplo`` triangle
    (reference ``tile::herk``; alpha/beta real).

    The full Hermitian product is formed (one MXU gemm); only the requested
    triangle of ``c`` is updated, the other triangle passes through — matching
    LAPACK update semantics so garbage triangles stay untouched.
    """
    oa = _op(a, op_a)
    if _mxu_f64(oa, dims=(oa.shape[-2], oa.shape[-1])):
        from . import ozaki

        prod = (ozaki.herk_c128(oa, slices=_oz_slices())
                if jnp.iscomplexobj(oa)
                else ozaki.syrk_f64(oa, slices=_oz_slices()))
    else:
        prod = oa @ jnp.conj(jnp.swapaxes(oa, -1, -2))
    upd = alpha * prod + beta * c
    if jnp.iscomplexobj(c):  # herk guarantees a real diagonal
        d = _embed_diag(jnp.real(_diag_of(upd)) - _diag_of(upd), upd.shape, upd.dtype)
        upd = upd + d
    return _merge_triangle(upd, c, uplo)


def her2k(uplo: str, op: str, a, b, c, *, alpha=1.0, beta=1.0):
    """``c = alpha op(a) op(b)^H + conj(alpha) op(b) op(a)^H + beta c`` on the
    ``uplo`` triangle (reference ``tile::her2k``; beta real)."""
    oa, ob = _op(a, op), _op(b, op)
    prod = alpha * _mm(oa, jnp.conj(jnp.swapaxes(ob, -1, -2)))
    prod = prod + jnp.conj(jnp.swapaxes(prod, -1, -2))
    upd = prod + beta * c
    return _merge_triangle(upd, c, uplo)


def _merge_triangle(update, orig, uplo: str):
    if uplo == "G":
        return update
    return tri_mask(update, uplo) + tri_mask(orig, "U" if uplo == "L" else "L", k=-1)


def trmm(side: str, uplo: str, op_a: str, diag: str, a, b, *, alpha=1.0):
    """``b = alpha op_a(A) b`` (side='L') with triangular ``A``
    (reference ``tile::trmm``)."""
    t = _op(_tri(a, uplo, diag), op_a)
    prod = _mm(t, b) if side == "L" else _mm(b, t)
    return (alpha * prod).astype(b.dtype)


#: Triangle sizes above this split recursively instead of lowering to one
#: XLA TriangularSolve. Two reasons (both measured on one v5e chip,
#: 2026-07-31): (1) memory — XLA's blocked substitution under the
#: f64→f32-pair X64 rewrite keeps O(n/128) prefix-shaped update temps
#: alive simultaneously (observed: f64 n=8192 against an 8192-wide rhs
#: wants ~13 GB of HLO temps and OOMs a 16 GB chip); (2) perf — the
#: recursion turns the bulk of the flops into large gemms, which ride
#: ``_mm``'s f64_gemm="mxu" reroute onto the int8 MXU path, while the
#: native solve is always software-emulated f64.
TRSM_RECURSE_MIN = 2048


def _trsm_native(side, uplo, op_a, diag, a, b):
    return lax.linalg.triangular_solve(
        a, b,
        left_side=(side == "L"),
        lower=(uplo == "L"),
        transpose_a=(op_a in ("T", "C")),
        conjugate_a=(op_a == "C"),
        unit_diagonal=(diag == "U"))


def _trsm_rec(side, uplo, op_a, diag, a, b):
    """Recursive blocked solve: split A 2x2, solve the halves, connect with
    one gemm (the standard blocked substitution the reference hand-tiles at
    ``nb`` granularity — here at halving granularity so the connecting gemm
    is as large as possible for the MXU)."""
    n = a.shape[-1]
    if n <= TRSM_RECURSE_MIN:
        return _trsm_native(side, uplo, op_a, diag, a, b)
    h = max(TRSM_RECURSE_MIN // 2, (n // 2) // 256 * 256)  # MXU-aligned split
    a11, a22 = a[:h, :h], a[h:, h:]
    # off-diagonal block of op(A): for op='N' the stored block on the
    # ``eff_lower`` side; otherwise the transpose of the other one
    eff_lower = (uplo == "L") == (op_a == "N")
    if eff_lower:
        s = a[h:, :h] if op_a == "N" else _op(a[:h, h:], op_a)
    else:
        s = a[:h, h:] if op_a == "N" else _op(a[h:, :h], op_a)
    if side == "L":
        if eff_lower:       # forward: op(A) = [[T11, 0], [S, T22]]
            x1 = _trsm_rec(side, uplo, op_a, diag, a11, b[:h])
            x2 = _trsm_rec(side, uplo, op_a, diag, a22,
                           b[h:] - _mm(s, x1))
        else:               # backward: op(A) = [[T11, S], [0, T22]]
            x2 = _trsm_rec(side, uplo, op_a, diag, a22, b[h:])
            x1 = _trsm_rec(side, uplo, op_a, diag, a11,
                           b[:h] - _mm(s, x2))
        return jnp.concatenate([x1, x2], axis=0)
    if eff_lower:           # X [[T11, 0], [S, T22]] = [B1, B2]
        x2 = _trsm_rec(side, uplo, op_a, diag, a22, b[..., h:])
        x1 = _trsm_rec(side, uplo, op_a, diag, a11,
                       b[..., :h] - _mm(x2, s))
    else:                   # X [[T11, S], [0, T22]] = [B1, B2]
        x1 = _trsm_rec(side, uplo, op_a, diag, a11, b[..., :h])
        x2 = _trsm_rec(side, uplo, op_a, diag, a22,
                       b[..., h:] - _mm(x1, s))
    return jnp.concatenate([x1, x2], axis=-1)


def trsm(side: str, uplo: str, op_a: str, diag: str, a, b, *, alpha=1.0):
    """Solve ``op_a(A) x = alpha b`` (side='L') / ``x op_a(A) = alpha b``
    (side='R') with triangular ``A`` (reference ``tile::trsm``).

    Small/batched triangles lower to XLA ``TriangularSolve`` (blocked
    forward substitution on TPU); 2D triangles above ``TRSM_RECURSE_MIN``
    use the recursive blocked form (see there for why).
    """
    out_dtype = b.dtype
    b = alpha * b
    if a.ndim == 2 and b.ndim == 2 and a.shape[-1] > TRSM_RECURSE_MIN:
        return _trsm_rec(side, uplo, op_a, diag, a, b).astype(out_dtype)
    return _trsm_native(side, uplo, op_a, diag, a, b).astype(out_dtype)


def f64_gemm_uses_mxu(dtype, dim: int) -> bool:
    """Does the ``f64_gemm="mxu"`` knob route this dtype at this block size
    onto the int8/bf16 MXU path? Single owner of the algorithm-level route
    decision (the tile-level ``_mm`` gate checks per-operand shapes
    itself)."""
    from ..config import get_configuration, resolved_f64_gemm

    import numpy as _np

    routed = (resolved_f64_gemm() == "mxu"
              and _np.dtype(dtype) in (_np.dtype(_np.float64),
                                       _np.dtype(_np.complex128))
              and dim >= get_configuration().f64_gemm_min_dim)
    if routed:
        # fault injection can force the ozaki -> plain-dot degradation;
        # the min-dim gate above is route policy and stays uncounted
        from ..health.registry import route_available

        return route_available("ozaki", "ozaki_gemm")
    return routed


def resolve_chunk_width(knob: str, dtype, gate_dim: int, chunk_axis: int,
                        *auto_dims: int) -> int:
    """Shared trace-time resolution for the workspace-bounding chunk knobs
    (``trsm_rhs_chunk``, ``red2band_trail_chunk``), which agree on
    everything but their dims. Returns the chunk width, or 0 for
    unchunked — including whenever the resolved width would not be
    shorter than ``chunk_axis``. Knob semantics: 0 = off; explicit widths
    are clamped to ``f64_gemm_min_dim`` when the mxu route is active at
    ``gate_dim`` (the per-gemm route gate takes min over ALL gemm dims —
    a narrower chunk would flip routes and change numerics); -1 = auto,
    which chunks at ``max(4096, f64_gemm_min_dim)`` only where the
    measured OOMs live — TPU, mxu route, every dim of ``auto_dims``
    >= 8192."""
    from ..config import get_configuration

    cfg = get_configuration()
    cfg_width = getattr(cfg, knob)
    mxu = f64_gemm_uses_mxu(dtype, gate_dim)
    if cfg_width > 0:
        cw = max(cfg_width, cfg.f64_gemm_min_dim) if mxu else cfg_width
    elif cfg_width == 0:
        return 0
    else:
        import jax

        if jax.default_backend() != "tpu" or not mxu \
                or any(d < 8192 for d in auto_dims):
            return 0
        cw = max(4096, cfg.f64_gemm_min_dim)
    return cw if cw < chunk_axis else 0


def trsm_panel_uses_mixed(dtype) -> bool:
    """Will :func:`trsm_panel` route this dtype through the refined-inverse
    mixed path under the current config? For callers that precompute
    ``inv_a`` once and reuse it across several panel solves."""
    from ..config import resolved_f64_trsm

    import numpy as _np

    return (resolved_f64_trsm() == "mixed"
            and _np.dtype(dtype) in (_np.dtype(_np.float64),
                                     _np.dtype(_np.complex128)))


def trsm_panel(side: str, uplo: str, op_a: str, diag: str, a, b, *,
               alpha=1.0, inv_a=None):
    """``trsm`` with ONE (2D) triangular block ``a`` against a possibly
    batched rhs ``b`` — the per-tile panel-solve pattern of the distributed
    algorithms. Under config ``f64_trsm="mixed"`` (f64 / complex128) the solve
    becomes refined-explicit-inverse (tile_ops.mixed, computed once, not per
    batch entry) times matmul (which follows ``f64_gemm``, so "mxu" puts the
    application on the int8 path); otherwise ``a`` broadcasts into the
    native solve. Whole-matrix local solves should call :func:`trsm` — the
    explicit-inverse route is for block-sized panels.

    ``inv_a``: optional precomputed refined inverse of ``a``'s triangle
    (from ``mixed.potrf_inv_refined`` — the fused factor+inverse step),
    consumed only on the mixed path; saves re-deriving the f32 seed solve."""
    from ..config import resolved_f64_trsm

    if (resolved_f64_trsm() == "mixed" and a.ndim == 2
            and a.dtype in (jnp.float64, jnp.complex128)
            and b.dtype == a.dtype):
        from . import mixed as mx

        inv = inv_a if inv_a is not None else \
            mx.tri_inv_refined(_tri(a, uplo, diag), lower=(uplo == "L"))
        ti = _op(inv, op_a)
        prod = _mm(ti, b) if side == "L" else _mm(b, ti)
        return (alpha * prod).astype(b.dtype)
    if b.ndim > a.ndim:
        a = jnp.broadcast_to(a, b.shape[:b.ndim - 2] + a.shape)
    return trsm(side, uplo, op_a, diag, a, b, alpha=alpha)


# ---------------------------------------------------------------------------
# Extensions / small helpers used by algorithms
# ---------------------------------------------------------------------------

def add(a, b, *, alpha=1.0):
    """``b = b + alpha a`` (reference ``tile_extensions.h`` ``tile::add``)."""
    return b + alpha * a


def scal(a, *, alpha):
    return alpha * a


def axpy(x, y, *, alpha=1.0):
    """``y = y + alpha x`` elementwise (reference GPU-internal ``tile::axpy``,
    ``blas/tile.h``; used by reduction-to-band micro-kernels there — here the
    algorithms fuse it into einsums, the op exists for tile-level use)."""
    return y + alpha * x


def gemv(a, x, y=None, *, alpha=1.0, beta=1.0, op_a: str = "N"):
    """``y = alpha op(A) x + beta y`` (reference GPU-internal ``tile::gemv``).
    ``x``/``y`` are vectors on the last axis; leading axes batch."""
    ax = jnp.einsum("...ij,...j->...i", _op(a, op_a), x)
    if y is None:
        return alpha * ax
    return alpha * ax + beta * y


def trmv(uplo: str, op_a: str, diag: str, a, x):
    """``x = op(T) x`` with triangular ``T`` (reference GPU-internal
    ``tile::trmv``; the T-factor accumulation uses it)."""
    t = _tri(a, uplo, diag)
    return jnp.einsum("...ij,...j->...i", _op(t, op_a), x)
