"""Tests for tile storage transforms and the Matrix container.

Mirrors the reference's ``test/unit/matrix/test_matrix.cpp`` /
``test_layout_info.cpp`` scope: round-trips, edge tiles, non-trivial grids
with source-rank offsets, and sharded placement over the 8-device mesh.
"""

import numpy as np
import pytest

import jax

from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import (GlobalElementSize, GlobalTileIndex, GridSize2D,
                                     LocalElementSize, LocalTileIndex, RankIndex2D,
                                     TileElementSize)
from dlaf_tpu.matrix import layout_info as li
from dlaf_tpu.matrix import tiling
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import Matrix

CASES = [
    # (m, n, mb, nb, P, Q, src_r, src_c)
    (10, 10, 4, 4, 1, 1, 0, 0),
    (12, 12, 4, 4, 2, 2, 0, 0),
    (13, 26, 5, 5, 2, 3, 1, 2),   # edge tiles + source-rank offset
    (7, 7, 8, 8, 2, 2, 1, 1),     # single (short) tile, offset source
    (26, 13, 4, 8, 4, 2, 3, 0),
    (0, 0, 4, 4, 2, 2, 0, 0),
]


def _dist(m, n, mb, nb, P, Q, sr, sc):
    return Distribution(GlobalElementSize(m, n), TileElementSize(mb, nb),
                        GridSize2D(P, Q), RankIndex2D(0, 0), RankIndex2D(sr, sc))


@pytest.mark.parametrize("m,n,mb,nb,P,Q,sr,sc", CASES)
def test_tiling_roundtrip(m, n, mb, nb, P, Q, sr, sc):
    d = _dist(m, n, mb, nb, P, Q, sr, sc)
    rng = np.random.default_rng(42)
    a = rng.standard_normal((m, n))
    t = tiling.global_to_tiles(a, d)
    Sr, Sc, ltr, ltc = tiling.storage_tile_grid(d)
    assert t.shape == (Sr, Sc, mb, nb)
    back = tiling.tiles_to_global(t, d)
    np.testing.assert_array_equal(np.asarray(back), a)


def test_tiling_places_tiles_correctly():
    d = _dist(13, 26, 5, 5, 2, 3, 1, 2)
    a = np.arange(13 * 26, dtype=np.float64).reshape(13, 26)
    t = np.asarray(tiling.global_to_tiles(a, d))
    nt = d.nr_tiles
    for tr in range(nt.row):
        for tc in range(nt.col):
            r, c = tiling.global_tile_to_storage_index(d, tr, tc)
            ts = d.tile_size_of(GlobalTileIndex(tr, tc))
            expect = a[tr * 5: tr * 5 + ts.row, tc * 5: tc * 5 + ts.col]
            np.testing.assert_array_equal(t[r, c, : ts.row, : ts.col], expect)
            # padding region is zero
            assert np.all(t[r, c, ts.row:, :] == 0)
            assert np.all(t[r, c, :, ts.col:] == 0)


def _storage_reference(a, d):
    """Tile storage by the module docstring's formula, in numpy alone:
    ``storage[p*ltr + l_r, q*ltc + l_c]`` is global tile ``(l_r*P + (p -
    src_r) % P, l_c*Q + (q - src_c) % Q)``, zero-padded to a whole tile,
    all zeros where no such tile exists."""
    mb, nb = d.block_size.row, d.block_size.col
    P, Q = d.grid_size.row, d.grid_size.col
    nt = d.nr_tiles
    ltr, ltc = -(-nt.row // P), -(-nt.col // Q)
    out = np.zeros((P * ltr, Q * ltc, mb, nb), dtype=a.dtype)
    for p in range(P):
        for q in range(Q):
            for lr in range(ltr):
                for lc in range(ltc):
                    tr = lr * P + (p - d.source_rank.row) % P
                    tc = lc * Q + (q - d.source_rank.col) % Q
                    if tr < nt.row and tc < nt.col:
                        blk = a[tr * mb:(tr + 1) * mb, tc * nb:(tc + 1) * nb]
                        out[p * ltr + lr, q * ltc + lc,
                            :blk.shape[0], :blk.shape[1]] = blk
    return out


#: name -> (m, n, mb, nb, P, Q, src_r, src_c, axes in storage order)
LAYOUT_CASES = {
    "1x1_divisible": (16, 24, 4, 8, 1, 1, 0, 0, (True, True)),
    "1x1_ragged": (15, 22, 4, 8, 1, 1, 0, 0, (True, True)),
    "2x2": (16, 24, 4, 4, 2, 2, 0, 0, (False, False)),
    "2x2_ragged_src": (13, 26, 5, 5, 2, 2, 1, 1, (False, False)),
    "2x1_src": (19, 8, 4, 4, 2, 1, 1, 0, (False, True)),
    # one tile a rank: storage order is tile order on a 2x2 grid too ...
    "2x2_one_tile_each": (8, 8, 4, 4, 2, 2, 0, 0, (True, True)),
    # ... but not where a rank's slot is padding (one tile, two ranks)
    "2x2_one_tile": (3, 3, 4, 4, 2, 2, 0, 0, (False, False)),
}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_layout_transforms_equal_the_numpy_reference_bitwise(case):
    *shape, in_order = LAYOUT_CASES[case]
    d = _dist(*shape)
    assert tiling._axes_in_order(d) == in_order
    a = np.random.default_rng(30).standard_normal(shape[:2])
    want = _storage_reference(a, d)
    t = tiling.global_to_tiles(a, d)
    np.testing.assert_array_equal(np.asarray(t), want)
    np.testing.assert_array_equal(
        np.asarray(tiling.tiles_to_global(jax.numpy.asarray(want), d)), a)
    # and under jit, as the entry points' programs trace them
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda x: tiling.global_to_tiles(x, d))(
            jax.numpy.asarray(a))), want)


def _primitives(fn, *args):
    """Names of the primitives ``fn`` traces, nested ``jit``s flattened."""
    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            subs = [getattr(v, "jaxpr", None) for v in eqn.params.values()]
            subs = [s for s in subs if s is not None]
            for sub in subs:
                walk(sub, out)
            if not subs:
                out.append(eqn.primitive.name)
        return out

    return walk(jax.make_jaxpr(fn)(*args).jaxpr, [])


#: what ``jnp.take`` with constant indices traces, and the transforms of
#: the parent commit (0cdaec9) around it, recorded from its jaxpr
_TAKE = ["lt", "add", "select_n", "broadcast_in_dim", "gather"]
_PAD = ["convert_element_type", "pad"]
PARENT_TO_TILES = _PAD + ["reshape", "transpose"] + _PAD + 2 * _TAKE
PARENT_TO_GLOBAL = 2 * _TAKE + ["transpose", "reshape", "slice"]


def test_one_device_whole_tiles_is_a_plain_transpose():
    """No gather, no pad, no slice: what the compiler gets on a 1x1 grid
    with whole tiles is ``reshape`` + ``transpose`` (ISSUE 30: the identity
    permutation is decided at trace time, XLA does not fold the gather)."""
    d = _dist(*LAYOUT_CASES["1x1_divisible"][:8])
    a = jax.numpy.zeros((16, 24))
    t = tiling.global_to_tiles(a, d)
    assert _primitives(lambda x: tiling.global_to_tiles(x, d), a) == [
        "reshape", "transpose"]
    assert _primitives(lambda x: tiling.tiles_to_global(x, d), t) == [
        "transpose", "reshape"]


def test_one_device_ragged_pads_and_slices_elements_only():
    d = _dist(*LAYOUT_CASES["1x1_ragged"][:8])
    a = jax.numpy.zeros((15, 22))
    t = tiling.global_to_tiles(a, d)
    assert _primitives(lambda x: tiling.global_to_tiles(x, d), a) == (
        _PAD + ["reshape", "transpose"])
    assert _primitives(lambda x: tiling.tiles_to_global(x, d), t) == [
        "transpose", "reshape", "slice"]


@pytest.mark.parametrize("case,drop_to_tiles,drop_to_global", [
    ("2x2_ragged_src", [], []),
    # whole tiles: the parent's zero-width element pad and its no-op slice
    # are not emitted (the slice JAX dropped itself), the rest is its trace
    ("2x2", _PAD, ["slice"]),
])
def test_permuted_grid_traces_the_parents_operations(case, drop_to_tiles,
                                                     drop_to_global):
    shape = LAYOUT_CASES[case][:8]
    d = _dist(*shape)
    a = jax.numpy.zeros(shape[:2])
    t = tiling.global_to_tiles(a, d)
    assert _primitives(lambda x: tiling.global_to_tiles(x, d), a) == (
        PARENT_TO_TILES[len(drop_to_tiles):])
    want = [p for p in PARENT_TO_GLOBAL if p not in drop_to_global]
    assert _primitives(lambda x: tiling.tiles_to_global(x, d), t) == want


def test_one_axis_in_order_gathers_the_other_only():
    shape = LAYOUT_CASES["2x1_src"][:8]
    d = _dist(*shape)
    a = jax.numpy.zeros(shape[:2])
    prims = _primitives(lambda x: tiling.global_to_tiles(x, d), a)
    assert prims.count("gather") == 1 and prims.count("pad") == 2
    t = tiling.global_to_tiles(a, d)
    assert _primitives(lambda x: tiling.tiles_to_global(x, d),
                       t).count("gather") == 1


@pytest.mark.parametrize("case", ["1x1_ragged", "2x2_ragged_src"])
def test_on_global_is_the_composition_and_passes_extras_through(case):
    shape = LAYOUT_CASES[case][:8]
    d = _dist(*shape)
    a = np.random.default_rng(31).standard_normal(shape[:2])
    st = tiling.global_to_tiles(a, d)
    prog = tiling.on_global(lambda g: 2.0 * g, d)
    assert prog.__name__ == "<lambda>_on_tiles"
    np.testing.assert_array_equal(
        np.asarray(jax.jit(prog)(st)),
        np.asarray(tiling.global_to_tiles(2.0 * a, d)))

    def with_info(g):
        return g + 1.0, g.shape[0], jax.numpy.int32(7)

    out, rows, info = jax.jit(tiling.on_global(with_info, d))(st)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(tiling.global_to_tiles(a + 1.0, d)))
    assert (int(rows), int(info)) == (shape[0], 7)


@pytest.mark.parametrize("m,n,mb,nb,P,Q,sr,sc", CASES)
def test_matrix_roundtrip_local(m, n, mb, nb, P, Q, sr, sc):
    rng = np.random.default_rng(7)
    a = rng.standard_normal((m, n))
    # without a grid the distribution is 1x1 (source rank must be (0,0) then)
    mat = Matrix.from_global(a, TileElementSize(mb, nb), grid=None)
    np.testing.assert_array_equal(mat.to_numpy(), a)


def test_matrix_sharded_over_mesh(devices8):
    grid = Grid(2, 4)
    rng = np.random.default_rng(3)
    a = rng.standard_normal((24, 24))
    mat = Matrix.from_global(a, TileElementSize(4, 4), grid=grid,
                             source_rank=RankIndex2D(1, 2))
    assert len(mat.storage.sharding.device_set) == 8
    np.testing.assert_array_equal(mat.to_numpy(), a)
    # per-tile reads see the right data
    t = mat.tile(GlobalTileIndex(2, 3))
    np.testing.assert_array_equal(t, a[8:12, 12:16])


def test_matrix_from_global_device_array_retiles_sharded(devices8):
    """A device-resident (sharded) global array re-tiles inside one
    compiled program with the tile sharding on the output — the handoff
    path from mesh-sharded D&C eigenvectors; result must match the numpy
    construction bit for bit."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    grid = Grid(2, 4)
    rng = np.random.default_rng(5)
    a = rng.standard_normal((24, 24))
    a_dev = jax.device_put(
        a, NamedSharding(grid.mesh, PartitionSpec(None, ("row", "col"))))
    mat = Matrix.from_global(a_dev, TileElementSize(4, 4), grid=grid,
                             source_rank=RankIndex2D(1, 2))
    ref = Matrix.from_global(a, TileElementSize(4, 4), grid=grid,
                             source_rank=RankIndex2D(1, 2))
    assert mat.storage.sharding == grid.tile_sharding()
    np.testing.assert_array_equal(np.asarray(mat.storage),
                                  np.asarray(ref.storage))
    # an array committed to a single device (outside the grid layout) must
    # take the eager fallback, not crash the compiled fast path
    a_one = jax.device_put(a, jax.devices()[0])
    mat1 = Matrix.from_global(a_one, TileElementSize(4, 4), grid=grid,
                              source_rank=RankIndex2D(1, 2))
    np.testing.assert_array_equal(np.asarray(mat1.storage),
                                  np.asarray(ref.storage))


def test_matrix_from_element_fn():
    fn = lambda i, j: 1.0 / (1 + i + j)  # noqa: E731
    mat = Matrix.from_element_fn(fn, GlobalElementSize(9, 9), TileElementSize(4, 4))
    a = mat.to_numpy()
    i, j = np.meshgrid(np.arange(9), np.arange(9), indexing="ij")
    np.testing.assert_allclose(a, 1.0 / (1 + i + j))


def test_matrix_complex_dtype():
    a = (np.arange(36).reshape(6, 6) + 1j * np.ones((6, 6))).astype(np.complex128)
    mat = Matrix.from_global(a, TileElementSize(4, 4))
    assert mat.dtype == np.complex128
    np.testing.assert_array_equal(mat.to_numpy(), a)


# -- layout info (reference test_layout_info.cpp) ---------------------------

def test_col_major_layout():
    sz = LocalElementSize(10, 7)
    bl = TileElementSize(4, 3)
    lay = li.col_major_layout(sz, bl, ld=12)
    assert lay.tile_offset(LocalTileIndex(0, 0)) == 0
    assert lay.tile_offset(LocalTileIndex(1, 0)) == 4
    assert lay.tile_offset(LocalTileIndex(0, 1)) == 3 * 12
    assert lay.tile_offset(LocalTileIndex(2, 2)) == 8 + 6 * 12
    # min mem: last tile (2,2) has size (2,1); offset + (1-1)*ld + 2
    assert lay.min_mem_size() == (8 + 6 * 12) + 2


def test_tile_layout():
    sz = LocalElementSize(10, 7)
    bl = TileElementSize(4, 4)
    lay = li.tile_layout(sz, bl)
    # 3x2 tiles, tile area 16, column stride 16*3
    assert lay.tile_offset(LocalTileIndex(1, 0)) == 16
    assert lay.tile_offset(LocalTileIndex(0, 1)) == 48
    last = lay.tile_offset(LocalTileIndex(2, 1))
    assert lay.min_mem_size() == last + (3 - 1) * 4 + 2


def test_layout_empty():
    lay = li.tile_layout(LocalElementSize(0, 0), TileElementSize(4, 4))
    assert lay.min_mem_size() == 0


def test_sharding_matches_distribution_ownership(devices8):
    """The design's central invariant (DESIGN.md par.1): NamedSharding over the
    cyclic-permuted 4D storage places on device (p, q) EXACTLY the tiles the
    block-cyclic Distribution assigns to rank (p, q) — every algorithm's
    shard_map masks assume it. Verified shard-by-shard against the
    Distribution's own ownership math, with a source-rank offset."""
    from dlaf_tpu.matrix.util_distribution import rank_global_tile

    grid = Grid(2, 4)
    P, Q = 2, 4
    src = RankIndex2D(1, 2)
    rng = np.random.default_rng(8)
    n, nb = 28, 4                      # 7x7 tiles: uneven per-rank counts
    a = rng.standard_normal((n, n))
    mat = Matrix.from_global(a, TileElementSize(nb, nb), grid=grid,
                             source_rank=src)
    nt = (n + nb - 1) // nb
    mesh_devs = mat.grid.mesh.devices  # (P, Q) device array
    dev_rank = {d: (p, q) for p in range(P) for q in range(Q)
                for d in [mesh_devs[p, q]]}
    for shard in mat.storage.addressable_shards:
        p, q = dev_rank[shard.device]
        owned = np.asarray(shard.data)   # (ltr, ltc, nb, nb) local tiles
        # collect this rank's global tiles in cyclic (slot) order
        g_rows = [g for g in range(nt) if rank_global_tile(g, P, src.row) == p]
        g_cols = [g for g in range(nt) if rank_global_tile(g, Q, src.col) == q]
        for li_r, g_r in enumerate(g_rows):
            for li_c, g_c in enumerate(g_cols):
                r0, c0 = g_r * nb, g_c * nb
                expect = np.zeros((nb, nb))
                blk = a[r0:min(r0 + nb, n), c0:min(c0 + nb, n)]
                expect[:blk.shape[0], :blk.shape[1]] = blk
                np.testing.assert_array_equal(owned[li_r, li_c], expect,
                                              err_msg=f"tile ({g_r},{g_c}) on rank ({p},{q})")


def test_complex_pair_transfer_mode(monkeypatch):
    """memory.place/fetch pair fallback (PJRT paths that reject complex128
    transfers, docs in matrix/memory.py): with the mode forced on, c128
    Matrix construction and gather round-trip bit-identically through
    paired f64 transfers."""
    from dlaf_tpu.matrix import memory

    rng = np.random.default_rng(11)
    a = rng.standard_normal((24, 24)) + 1j * rng.standard_normal((24, 24))
    ref = Matrix.from_global(a, TileElementSize(8, 8)).to_numpy()

    monkeypatch.setattr(memory, "_complex_pair_mode", True)
    m = Matrix.from_global(a, TileElementSize(8, 8))
    got = m.to_numpy()
    assert got.dtype == np.complex128
    assert got.tobytes() == np.asarray(ref).tobytes()
    t = m.tile(GlobalTileIndex(1, 2))
    assert t.tobytes() == np.asarray(ref[8:16, 16:24]).tobytes()

    # distributed construction reshards device-resident complex storage
    # (Matrix._shard) — must stay on device in pair mode, no direct
    # complex transfer
    from dlaf_tpu.comm.grid import Grid

    md = Matrix.from_global(a, TileElementSize(8, 8), grid=Grid(2, 4))
    assert np.asarray(md.to_numpy()).tobytes() == np.asarray(ref).tobytes()


def test_complex_pair_fallback_detection(monkeypatch):
    """The try/except detection path: a direct complex device_put failing
    (while the probe also fails) falls back to the pair route, latches the
    mode with a warning, and still round-trips bit-exactly. Non-complex
    failures re-raise untouched."""
    import warnings as _warnings

    import jax as _jax

    from dlaf_tpu.matrix import memory

    real_put = _jax.device_put

    def flaky_put(x, sharding=None):
        if np.iscomplexobj(x):
            # the PJRT error type place() recognizes as a transfer
            # rejection (a bare RuntimeError must NOT trigger the retry)
            from jax.errors import JaxRuntimeError

            raise JaxRuntimeError("synthetic: backend rejects complex128")
        return real_put(x, sharding)

    monkeypatch.setattr(memory, "_complex_pair_mode", None)
    monkeypatch.setattr(_jax, "device_put", flaky_put)
    a = (np.arange(12.0) + 1j * np.arange(12.0)[::-1]).reshape(3, 4)
    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        out = memory.place(a)
    assert memory._complex_pair_mode is True
    assert any("pair mode" in str(x.message) for x in w)
    assert np.asarray(out).tobytes() == a.tobytes()
    # real arrays that fail must re-raise, not loop into the pair path
    monkeypatch.setattr(memory, "_complex_pair_mode", None)
    monkeypatch.setattr(
        _jax, "device_put",
        lambda x, sharding=None: (_ for _ in ()).throw(RuntimeError("down")))
    with pytest.raises(RuntimeError, match="down"):
        memory.place(np.ones((2, 2)))


def test_complex_pair_fallback_ignores_non_transfer_errors(monkeypatch):
    """Round-2 advisory: only recognized transfer-error types trigger the
    pair retry. A bare RuntimeError (interpreter teardown, unrelated bug)
    and a RESOURCE_EXHAUSTED device OOM both re-raise directly — the pair
    path transiently needs MORE memory, and an unrelated failure would
    just fail a second time."""
    import jax as _jax
    from jax.errors import JaxRuntimeError

    from dlaf_tpu.matrix import memory

    a = (np.arange(4.0) + 1j * np.arange(4.0)).reshape(2, 2)

    def put_raising(exc):
        return lambda x, sharding=None: (_ for _ in ()).throw(exc)

    monkeypatch.setattr(memory, "_complex_pair_mode", None)
    monkeypatch.setattr(_jax, "device_put",
                        put_raising(RuntimeError("not a transfer error")))
    with pytest.raises(RuntimeError, match="not a transfer"):
        memory.place(a)
    assert memory._complex_pair_mode is None

    monkeypatch.setattr(
        _jax, "device_put",
        put_raising(JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")))
    with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        memory.place(a)
    assert memory._complex_pair_mode is None
    # fetch symmetric: device OOM on readback re-raises too
    monkeypatch.setattr(
        _jax, "device_get",
        put_raising(JaxRuntimeError("RESOURCE_EXHAUSTED: host")))
    with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        memory.fetch(jnp_complex_probe())


def jnp_complex_probe():
    import jax.numpy as jnp

    return jnp.asarray(np.ones((2, 2), np.complex128))
