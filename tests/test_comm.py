"""Tests for the grid and collective verbs on the 8-device CPU mesh.

Mirrors the reference's ``test/unit/communication/`` suite (bcast / reduce /
all_reduce / p2p at several grid shapes and both rank orderings,
``grids_6_ranks.h``) using shard_map over virtual devices.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dlaf_tpu.comm import collectives as cc
from dlaf_tpu.comm.grid import Grid


def _shmap(grid, f, in_specs, out_specs):
    return shard_map(f, mesh=grid.mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


@pytest.mark.parametrize("rows,cols", [(2, 4), (4, 2), (2, 2), (1, 8), (8, 1)])
def test_grid_shapes(rows, cols, devices8):
    g = Grid(rows, cols)
    assert (g.size.row, g.size.col) == (rows, cols)
    assert g.num_devices == rows * cols


def test_grid_orderings(devices8):
    g_rm = Grid(2, 4, ordering="row-major")
    g_cm = Grid(2, 4, ordering="col-major")
    devs = jax.devices()
    assert g_rm.mesh.devices[0, 1] == devs[1]
    assert g_cm.mesh.devices[0, 1] == devs[2]
    assert g_cm.mesh.devices[1, 0] == devs[1]


@pytest.mark.parametrize("axis,src", [("row", 0), ("row", 1), ("col", 2)])
def test_bcast(axis, src, devices8):
    g = Grid(2, 4)
    x = jnp.arange(8, dtype=jnp.float64).reshape(2, 4) + 1.0

    def f(x):
        blk = x.reshape(())  # local (1,1) block -> scalar
        return cc.bcast(blk, axis, src).reshape(1, 1)

    out = _shmap(g, f, P("row", "col"), P("row", "col"))(x)
    out = np.asarray(out)
    if axis == "row":
        expect = np.tile(np.asarray(x)[src: src + 1, :], (2, 1))
    else:
        expect = np.tile(np.asarray(x)[:, src: src + 1], (1, 4))
    np.testing.assert_array_equal(out, expect)


def test_bcast_complex(devices8):
    g = Grid(2, 4)
    x = (jnp.arange(8) + 1j * jnp.arange(8)).reshape(2, 4).astype(jnp.complex128)

    def f(x):
        return cc.bcast(x.reshape(()), "col", 1).reshape(1, 1)

    out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    expect = np.tile(np.asarray(x)[:, 1:2], (1, 4))
    np.testing.assert_array_equal(out, expect)


@pytest.mark.parametrize("op,red", [("sum", np.sum), ("max", np.max), ("min", np.min)])
def test_all_reduce(op, red, devices8):
    g = Grid(2, 4)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 4)))

    def f(x):
        return cc.all_reduce(x.reshape(()), "col", op).reshape(1, 1)

    out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    expect = np.tile(red(np.asarray(x), axis=1, keepdims=True), (1, 4))
    np.testing.assert_allclose(out, expect, rtol=1e-14)


def test_reduce_matches_allreduce_on_root(devices8):
    g = Grid(2, 4)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 4)))

    def f(x):
        return cc.reduce(x.reshape(()), "row", root=1).reshape(1, 1)

    out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    np.testing.assert_allclose(out[1], np.asarray(x).sum(axis=0), rtol=1e-14)


def test_send_recv(devices8):
    g = Grid(2, 4)
    x = jnp.arange(8, dtype=jnp.float64).reshape(2, 4)

    def f(x):
        return cc.send_recv(x.reshape(()), "col", src=0, dst=3).reshape(1, 1)

    out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    # dst column 3 received column 0's values; others zero
    np.testing.assert_array_equal(out[:, 3], np.asarray(x)[:, 0])
    assert np.all(out[:, :3] == 0)


def test_all_gather_panel(devices8):
    g = Grid(2, 4)
    x = jnp.arange(32, dtype=jnp.float64).reshape(8, 4)

    def f(x):  # local (4, 1) column chunk; gather along 'col' -> full row block
        return cc.all_gather(x, "col", tiled=True, concat_axis=1)

    out = _shmap(g, f, P("row", "col"), P("row", None))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_this_rank_axis_size(devices8):
    g = Grid(2, 4)

    def f():
        r = cc.this_rank("row") * 10 + cc.this_rank("col")
        n = cc.axis_size("row") * 100 + cc.axis_size("col")
        return (r + n).reshape(1, 1)

    out = np.asarray(_shmap(g, f, (), P("row", "col"))())
    expect = np.array([[204, 205, 206, 207], [214, 215, 216, 217]])
    np.testing.assert_array_equal(out, expect)


# -- multihost glue (single-process testable surface) ------------------------

def test_multihost_grid_shapes_and_axes(devices8):
    from dlaf_tpu.comm.multihost import multihost_grid, process_info, slice_groups
    import jax

    g = multihost_grid()
    assert g.num_devices == 8
    assert g.size.row * g.size.col == 8
    assert set(g.mesh.axis_names) == {"row", "col"}
    g2 = multihost_grid(2, 4)
    assert (g2.size.row, g2.size.col) == (2, 4)
    pi, pc = process_info()
    assert pi == 0 and pc == 1
    # all virtual CPU devices sit in one ICI island
    assert len(slice_groups(jax.devices())) == 1


def test_multihost_grid_runs_algorithms(devices8):
    import numpy as np
    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.comm.multihost import multihost_grid
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    rng = np.random.default_rng(3)
    x = rng.standard_normal((24, 24))
    a = x @ x.T + 24 * np.eye(24)
    mat = Matrix.from_global(a, TileElementSize(4, 4), grid=multihost_grid())
    out = cholesky("L", mat)
    f = np.tril(out.to_numpy())
    assert np.linalg.norm(f @ f.T - a) / np.linalg.norm(a) < 1e-13


def test_initialize_multihost_single_process_noop():
    from dlaf_tpu.comm.multihost import initialize_multihost

    initialize_multihost()  # must not raise or disturb the backend


# -- blocking sync tier (reference communication/sync/*.h) --------------------


def test_sync_gather_matches_to_numpy(devices8):
    from dlaf_tpu.comm import sync as cs
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    rng = np.random.default_rng(7)
    a = rng.standard_normal((20, 12))
    mat = Matrix.from_global(a, TileElementSize(4, 4), grid=Grid(2, 4))
    np.testing.assert_array_equal(cs.gather(mat), a)
    # to_numpy IS the sync tier (the reference's tests go through sync:: too)
    np.testing.assert_array_equal(mat.to_numpy(), a)


def test_sync_gather_shards_covers_every_device(devices8):
    from dlaf_tpu.comm import sync as cs

    g = Grid(2, 4)
    x = jax.device_put(np.arange(16.0).reshape(2, 4, 2),
                       g.tile_sharding())
    shards = cs.gather_shards(x)
    assert len(shards) == 8
    assert sum(s.size for s in shards) == x.size
    assert cs.gather_shards(np.ones(3))[0].shape == (3,)


def test_sync_reduce_ops(devices8):
    from dlaf_tpu.comm import sync as cs

    parts = [np.array([1.0, -2.0]), np.array([3.0, 5.0])]
    np.testing.assert_array_equal(cs.all_reduce(parts, "sum"), [4.0, 3.0])
    np.testing.assert_array_equal(cs.all_reduce(parts, "max"), [3.0, 5.0])
    np.testing.assert_array_equal(cs.all_reduce(parts, "min"), [1.0, -2.0])
    # root is a parity argument: the host plays every rank
    np.testing.assert_array_equal(cs.reduce(parts, root=1, op="sum"), [4.0, 3.0])
    with pytest.raises(ValueError):
        cs.all_reduce(parts, "xor")


def test_sync_barrier_is_hard_fence():
    from dlaf_tpu.comm import sync as cs
    from dlaf_tpu.common.sync import hard_fence

    assert cs.barrier is hard_fence


def test_hard_fence_reads_back_from_one_shard(devices8):
    """The readback indexes the first addressable shard, a one-device
    array, never the sharded array itself (on a 2x2 v5e result that is a
    gather over four devices: 4.8 ms of host time a fence against 2.0,
    PERF.md PR 27); a one-device array takes the same path."""
    from dlaf_tpu.common.sync import hard_fence

    class Sharded:
        """Stands in for a jax Array whose global index must not be used."""
        size, ndim, ready = 16, 2, 0

        def __init__(self, shards):
            self.addressable_shards = shards

        def block_until_ready(self):
            self.ready += 1

        def __getitem__(self, idx):
            raise AssertionError("indexed the sharded array")

    class Shard:
        def __init__(self, data):
            self.data = data

    class Recorder(np.ndarray):
        seen = []

        def __getitem__(self, idx):
            Recorder.seen.append(idx)
            return np.asarray(self).__getitem__(idx)

    x = Sharded([Shard(np.ones((2, 4)).view(Recorder)), Shard(None)])
    assert hard_fence(x) is x
    assert x.ready == 1 and Recorder.seen == [(0, 0)]

    g = Grid(2, 2)
    arr = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                         jax.sharding.NamedSharding(g.mesh, P("row", "col")))
    assert len(arr.addressable_shards) == 4
    assert hard_fence(arr, None, jnp.ones(3))[0] is arr


@pytest.mark.parametrize("rows,cols,axis,src", [
    (2, 4, "col", 0), (2, 4, "col", 2), (1, 8, "col", 3), (8, 1, "row", 5),
    (2, 3, "col", 1),  # non-power-of-2 axis (last doubling round truncated)
])
def test_bcast_tree_matches_psum(rows, cols, axis, src, devices8, monkeypatch):
    """bcast_impl="tree" (binomial ppermute doubling) is value-identical to
    the psum form on every axis size/source — the knob exists so the first
    multi-chip ICI access can A/B hop latency vs ring bandwidth."""
    import dlaf_tpu.config as config

    if rows * cols > 8:
        pytest.skip("needs more virtual devices")
    g = Grid(rows, cols)
    n = rows * cols
    x = jnp.arange(n, dtype=jnp.float64).reshape(rows, cols) + 1.0

    def f(x):
        return cc.bcast(x.reshape(()), axis, src).reshape(1, 1)

    ref = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    monkeypatch.setenv("DLAF_BCAST_IMPL", "tree")
    config.initialize()
    try:
        out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    finally:
        monkeypatch.delenv("DLAF_BCAST_IMPL")
        config.initialize()
    np.testing.assert_array_equal(out, ref)


def test_bcast_tree_full_algorithm(devices8, monkeypatch):
    """A full distributed factorization under bcast_impl="tree" matches the
    psum-broadcast result bit-for-bit (same reductions, different bcast)."""
    import dlaf_tpu.config as config
    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    n, nb = 16, 4
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n))
    a = x @ x.T + n * np.eye(n)
    g = Grid(2, 4)
    ref = cholesky("L", Matrix.from_global(a, TileElementSize(nb, nb),
                                           grid=g)).to_numpy()
    monkeypatch.setenv("DLAF_BCAST_IMPL", "tree")
    config.initialize()
    try:
        out = cholesky("L", Matrix.from_global(a, TileElementSize(nb, nb),
                                               grid=g)).to_numpy()
    finally:
        monkeypatch.delenv("DLAF_BCAST_IMPL")
        config.initialize()
    np.testing.assert_allclose(np.tril(out), np.tril(ref), rtol=0, atol=0)


@pytest.mark.parametrize("rows,cols", [(2, 4), (4, 2), (2, 2), (1, 8)])
@pytest.mark.parametrize("owner_r,owner_c", [(0, 0), (1, 1)])
def test_bcast2d_matches_two_hop(rows, cols, owner_r, owner_c, devices8):
    """The fused 2D diagonal broadcast (one psum over BOTH mesh axes,
    docs/comm_overlap.md) is BITWISE identical to the two-hop
    bcast(bcast(...)) it replaces — including the signed-zero flattening
    any multi-participant psum performs."""
    g = Grid(rows, cols)
    orr, occ = owner_r % rows, owner_c % cols
    vals = np.arange(rows * cols, dtype=np.float64).reshape(rows, cols) + 1.0
    vals[0, 0] = -0.0   # the masked-add edge the contract documents
    x = jnp.asarray(vals)

    def fused(x):
        return cc.bcast2d(x.reshape(()), orr, occ).reshape(1, 1)

    def two_hop(x):
        blk = x.reshape(())
        return cc.bcast(cc.bcast(blk, "row", orr), "col", occ).reshape(1, 1)

    out_f = np.asarray(_shmap(g, fused, P("row", "col"), P("row", "col"))(x))
    out_2 = np.asarray(_shmap(g, two_hop, P("row", "col"),
                              P("row", "col"))(x))
    np.testing.assert_array_equal(out_f, out_2)
    np.testing.assert_array_equal(out_f, np.full((rows, cols),
                                                 vals[orr, occ]))


def test_bcast2d_tree_impl(devices8, monkeypatch):
    """bcast_impl="tree" has no 2-axis fusion: bcast2d falls back to the
    two-hop binomial trees with identical values."""
    import dlaf_tpu.config as config

    g = Grid(2, 4)
    x = jnp.arange(8, dtype=jnp.float64).reshape(2, 4) + 1.0

    def f(x):
        return cc.bcast2d(x.reshape(()), 1, 2).reshape(1, 1)

    ref = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    monkeypatch.setenv("DLAF_BCAST_IMPL", "tree")
    config.initialize()
    try:
        out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    finally:
        monkeypatch.delenv("DLAF_BCAST_IMPL")
        config.initialize()
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(out, np.full((2, 4), np.asarray(x)[1, 2]))


def test_bcast2d_records_per_axis_bytes(devices8, monkeypatch, tmp_path):
    """Accounting parity with the two-hop form: one bcast2d charges the
    payload once per mesh axis under kind="bcast2d" (the per-axis byte
    counters the ICI roofline reads — scripts/mfu_table.py)."""
    import dlaf_tpu.config as config
    from dlaf_tpu import obs

    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "m.jsonl"))
    config.initialize()
    try:
        g = Grid(2, 4)
        x = jnp.arange(8, dtype=jnp.float64).reshape(2, 4) + 1.0

        def f(x):
            return cc.bcast2d(x.reshape(()), 0, 0).reshape(1, 1)

        _shmap(g, f, P("row", "col"), P("row", "col"))(x)
        snap = obs.registry().snapshot()
        got = {m["labels"]["axis"]: m["value"] for m in snap
               if m["name"] == "dlaf_comm_collective_bytes_total"
               and m["labels"].get("kind") == "bcast2d"}
        assert got.get("row", 0) == 8 and got.get("col", 0) == 8, snap
    finally:
        monkeypatch.delenv("DLAF_METRICS_PATH")
        config.initialize()
        obs._reset_for_tests()


def test_bcast2d_injection_parity(devices8):
    """corrupt_collective("bcast") must still reach the diagonal-tile
    broadcast now that it is the fused bcast2d — the drill targets "a
    broadcast on the step critical path", not a specific lowering."""
    from dlaf_tpu.health import inject

    g = Grid(2, 2)
    x = jnp.ones((2, 2), dtype=jnp.float64)

    def f(x):
        return cc.bcast2d(x.reshape(()), 0, 0).reshape(1, 1)

    with inject.corrupt_collective("bcast", nth=0, seed=1):
        out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    assert np.isnan(out).all(), out
    clean = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    np.testing.assert_array_equal(clean, np.ones((2, 2)))


def test_reduce_root_semantics(devices8):
    """reduce() defines the result ONLY on root (zeros elsewhere) — the
    reference's contract (kernels/reduce.h: only the root's output tile is
    defined); accidental non-root reads must surface, not silently work."""
    g = Grid(2, 4)
    x = jnp.arange(8, dtype=jnp.float64).reshape(2, 4) + 1.0

    def f(x):
        return cc.reduce(x.reshape(()), "col", root=2).reshape(1, 1)

    out = np.asarray(_shmap(g, f, P("row", "col"), P("row", "col"))(x))
    rowsums = np.asarray(x).sum(axis=1)
    expect = np.zeros((2, 4))
    expect[:, 2] = rowsums
    np.testing.assert_array_equal(out, expect)


def test_multihost_layout_slice_aware():
    """The ICI/DCN layout decision (pod-only in production) is a pure
    function: fake devices with slice_index exercise the multi-slice
    branches — the col axis must stay inside one slice when the slice
    size factors over it, and slice-major ordering must hold otherwise."""
    import dataclasses

    from dlaf_tpu.comm.multihost import layout_2d, slice_groups

    @dataclasses.dataclass(frozen=True)
    class FakeDev:
        id: int
        slice_index: int

    # 2 slices x 4 devices, grid 4x2: per-slice (4) % cols (2) == 0 -> the
    # hybrid helper rejects fakes, so the slice-major heuristic must place
    # each row's 2 cols inside ONE slice
    devs = [FakeDev(i, i // 4) for i in range(8)]
    assert set(map(len, slice_groups(devs).values())) == {4}
    out = layout_2d(devs, 4, 2)
    assert out.shape == (4, 2)
    for r in range(4):
        assert len({d.slice_index for d in out[r]}) == 1, \
            f"row {r} spans slices: {[d.slice_index for d in out[r]]}"

    # grid 2x4: cols (4) == per-slice -> each row IS one slice
    out2 = layout_2d(devs, 2, 4)
    for r in range(2):
        assert len({d.slice_index for d in out2[r]}) == 1

    # single-slice world: plain reshape preserves device order
    flat = [FakeDev(i, 0) for i in range(8)]
    out3 = layout_2d(flat, 2, 4)
    assert [d.id for d in out3.ravel()] == list(range(8))

    # non-factoring shape (per=4, cols=3 x rows... use 12 devices, 3 slices
    # of 4, grid 4x3: per % cols != 0 and cols % per != 0 -> device-order
    # reshape fallback, still total
    devs12 = [FakeDev(i, i // 4) for i in range(12)]
    out4 = layout_2d(devs12, 4, 3)
    assert sorted(d.id for d in out4.ravel()) == list(range(12))
