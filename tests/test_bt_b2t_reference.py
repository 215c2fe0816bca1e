"""``bt_band_to_tridiag`` against the benchmark's plain reference (ISSUE 39).

The cell ``bt_b2t_d_n4096_1x1`` holds the library's application of the bulge
chase's reflectors to ``benchmark/reference/chase_reflectors.py`` (numpy
float64, one rank-1 update a reflector in the published order; no jax, no
code of ``dlaf_tpu``) on 64 sampled columns. Here the same comparison runs
on the CPU on ALL columns of real chase output, for both forms of the
application (``bt_b2t_impl``: the blocked compact-WY levels and the
sweep-at-a-time scan), both kinds of input (an array, a local ``Matrix``)
and, for the blocked form, a window no wider than its staircase is tall (T
is folded into V, ``seg - (V T)(V^H seg)``, at every width),
at the cell's own limit ``100 n eps`` with the native epsilon; and the
reference is tied to the model once: with its ``Q``, ``Q^T B Q`` is the
tridiagonal ``(d, e)`` the chase returned.
"""

import importlib.util
import os

import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver import bt_band_to_tridiag
from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag
from dlaf_tpu.matrix.matrix import Matrix

EPS = float(np.finfo(np.float64).eps)
C_TOL = 100.0           # the configuration's guarantee (the miniapp's c)
NB = 32                 # tile of the Matrix input


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "chase_reflectors.py")
    spec = importlib.util.spec_from_file_location("chase_reflectors", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


@pytest.fixture(autouse=True)
def config_reset():
    yield
    C.finalize()
    C.initialize()


def _band(n, b, seed):
    """The cell's input: lower band storage ``(b + 1, n)`` of ``(G + G^T)/2``
    with ``G`` standard normal from the seed (benchmark/ops)."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    return ref.lower_band((g + g.T) / 2, b)


@pytest.fixture(scope="module")
def chased():
    """``{(n, b): (band, TridiagResult)}``: each chase runs once."""
    kept = {}

    def get(n, b):
        if (n, b) not in kept:
            band = _band(n, b, seed=n + b)
            kept[n, b] = band, band_to_tridiag(band, b)
        return kept[n, b]
    return get


def test_reference_imports_nothing_of_the_library():
    src = open(ref.__file__).read()
    assert "import jax" not in src and "from jax" not in src
    assert "import dlaf_tpu" not in src and "from dlaf_tpu" not in src
    assert ref.apply_q(np.zeros((0, 1, 4)), np.zeros((0, 1)),
                       np.ones((3, 2)), 4).dtype == np.float64


SIZES = [(96, 8), (130, 16), (257, 32)]
#: ``(n, b, m, impl)``: all ``n`` columns in both forms; and, in the blocked
#: form, ``m = L`` columns, as many as its staircase has rows (``L = b + G -
#: 1``, ``G = b`` on the CPU for ``b <= 64``), where the folded ``V T`` is
#: wider than the window
COLUMNS = [pytest.param(n, b, n, impl, id=f"{n}-{b}-{impl}")
           for n, b in SIZES for impl in ("blocked", "sweeps")] + [
    pytest.param(n, b, 2 * b - 1, "blocked", id=f"{n}-{b}-narrow-blocked")
    for n, b in SIZES]


@pytest.mark.parametrize("kind", ["array", "Matrix"])
@pytest.mark.parametrize("n, b, m, impl", COLUMNS)
def test_all_columns_against_the_plain_reference(n, b, m, impl, kind, chased):
    _band_storage, tri = chased(n, b)
    C.initialize(C.Configuration(bt_b2t_impl=impl))
    e_all = np.random.default_rng(7 * n + b).standard_normal((n, n))
    e = e_all[:, :m]
    want = ref.apply_q(tri.v, tri.tau, e, b)
    if kind == "Matrix":
        out = bt_band_to_tridiag(
            tri, Matrix.from_global(e, TileElementSize(NB, NB)))
        assert isinstance(out, Matrix)
        got = np.asarray(out.to_numpy())
    else:
        got = np.asarray(bt_band_to_tridiag(tri, e))
    assert got.shape == want.shape and got.dtype == np.float64
    tol = C_TOL * n * EPS
    assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)
    # column by column too: a wrong column hides in a Frobenius norm of n
    assert (np.linalg.norm(got - want, axis=0)
            <= tol * np.linalg.norm(want, axis=0)).all()
    if m < n:
        # the same columns inside all n agree with these
        wide = np.asarray(bt_band_to_tridiag(tri, e_all))[:, :m]
        assert np.linalg.norm(got - wide) <= tol * np.linalg.norm(want)


@pytest.mark.parametrize("n, b", [(96, 8), (130, 16)])
def test_reference_q_takes_the_band_to_the_chases_tridiagonal(n, b, chased):
    """The reference applies the ``Q`` of ``T = Q^T B Q``: the published
    semantics, not only the library's habit."""
    band, tri = chased(n, b)
    q = ref.apply_q(tri.v, tri.tau, np.eye(n), b)
    assert np.abs(q.T @ q - np.eye(n)).max() <= C_TOL * n * EPS
    b_mat = ref.dense_band(band)
    t_mat = ref.tridiagonal(tri.d, tri.e)
    assert np.linalg.norm(q.T @ b_mat @ q - t_mat) \
        <= C_TOL * n * EPS * np.linalg.norm(b_mat)


def test_a_float32_application_fails_the_limit(chased):
    """The limit tells a float64 application from a float32 one."""
    n, b = 130, 16
    _band_storage, tri = chased(n, b)
    e = np.random.default_rng(1).standard_normal((n, n))
    want = ref.apply_q(tri.v, tri.tau, e, b)
    low = ref.apply_q(tri.v, tri.tau, e, b, dtype=np.float32)
    assert low.dtype == np.float32
    assert np.linalg.norm(low - want) > C_TOL * n * 2.0 ** -47 \
        * np.linalg.norm(want)
