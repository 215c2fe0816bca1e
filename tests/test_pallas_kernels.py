"""Pallas kernel tests (interpret mode on the CPU mesh)."""

import numpy as np
import pytest

import jax.numpy as jnp

from dlaf_tpu.tile_ops.pallas_kernels import masked_trailing_update, supports_pallas_update


@pytest.mark.parametrize("R,C,nb", [(3, 2, 16), (2, 2, 8), (1, 1, 8)])
def test_masked_trailing_update(R, C, nb):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((R, C, nb, nb)).astype(np.float32)
    vr = rng.standard_normal((R, nb, nb)).astype(np.float32)
    vc = rng.standard_normal((C, nb, nb)).astype(np.float32)
    mode = rng.integers(0, 4, size=(R, C)).astype(np.int32)
    out = np.asarray(masked_trailing_update(
        jnp.asarray(a), jnp.asarray(vr), jnp.asarray(vc), jnp.asarray(mode),
        interpret=True))
    tril = np.tril(np.ones((nb, nb), dtype=bool))
    triu = np.triu(np.ones((nb, nb), dtype=bool))
    for r in range(R):
        for c in range(C):
            full = a[r, c] - vr[r] @ vc[c].T
            if mode[r, c] == 0:
                expect = a[r, c]
            elif mode[r, c] == 1:
                expect = full
            elif mode[r, c] == 2:
                expect = np.where(tril, full, a[r, c])
            else:
                expect = np.where(triu, full, a[r, c])
            np.testing.assert_allclose(out[r, c], expect, rtol=2e-5, atol=2e-5)


def test_gate(monkeypatch):
    monkeypatch.delenv("DLAF_FORCE_PALLAS_UPDATE", raising=False)
    assert supports_pallas_update(jnp.float32, "tpu")
    assert supports_pallas_update(jnp.bfloat16, "tpu")
    assert not supports_pallas_update(jnp.float64, "tpu")
    assert not supports_pallas_update(jnp.float32, "cpu")
    assert not supports_pallas_update(jnp.complex64, "tpu")


@pytest.mark.parametrize("dtype,rtol", [(np.float32, 2e-5), (jnp.bfloat16, 8e-2)])
@pytest.mark.parametrize("R,C,nb", [(3, 2, 16), (2, 2, 8)])
def test_masked_trailing_update_dtypes(R, C, nb, dtype, rtol):
    """bf16 exercises the f32-accumulate/cast-back round-trip, including
    untouched (mode 0 / masked upper-triangle) elements passing through."""
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((R, C, nb, nb)), dtype=dtype)
    vr = jnp.asarray(rng.standard_normal((R, nb, nb)), dtype=dtype)
    vc = jnp.asarray(rng.standard_normal((C, nb, nb)), dtype=dtype)
    mode = jnp.asarray(rng.integers(0, 3, size=(R, C)), dtype=jnp.int32)
    out = masked_trailing_update(a, vr, vc, mode, interpret=True)
    assert out.dtype == a.dtype
    af, vrf, vcf = (np.asarray(x, dtype=np.float32) for x in (a, vr, vc))
    tri = np.tril(np.ones((nb, nb), dtype=bool))
    m = np.asarray(mode)
    outf = np.asarray(out, dtype=np.float32)
    for r in range(R):
        for c in range(C):
            full = af[r, c] - vrf[r] @ vcf[c].T
            if m[r, c] == 0:
                expect = af[r, c]
            elif m[r, c] == 1:
                expect = full
            else:
                expect = np.where(tri, full, af[r, c])
            np.testing.assert_allclose(outf[r, c], expect, rtol=rtol, atol=rtol)
            if m[r, c] == 0:
                # pass-through must be bit-exact, not a cast round-trip error
                np.testing.assert_array_equal(np.asarray(out[r, c]),
                                              np.asarray(a[r, c]))


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_distributed_cholesky_pallas_branch(monkeypatch, devices8, uplo):
    """Force the Pallas integration branch of the distributed trailing
    update (mode construction + .set() wiring) off-TPU via
    DLAF_FORCE_PALLAS_UPDATE; kernel runs in interpret mode on CPU."""
    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    monkeypatch.setenv("DLAF_FORCE_PALLAS_UPDATE", "1")
    n, nb = 24, 4
    grid = Grid(2, 4)
    rng = np.random.default_rng(7)
    x = rng.standard_normal((n, n))
    a = (x @ x.T + n * np.eye(n)).astype(np.float32)
    mat = Matrix.from_global(a, TileElementSize(nb, nb), grid=grid)
    out = cholesky(uplo, mat).to_numpy()
    eps = np.finfo(np.float32).eps
    if uplo == "L":
        f = np.tril(out)
        resid = np.linalg.norm(f @ f.T - a) / np.linalg.norm(a)
        np.testing.assert_array_equal(np.triu(out, 1), np.triu(a, 1))
    else:
        f = np.triu(out)
        resid = np.linalg.norm(f.T @ f - a) / np.linalg.norm(a)
        np.testing.assert_array_equal(np.tril(out, -1), np.tril(a, -1))
    assert resid < 60 * n * eps

