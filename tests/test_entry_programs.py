"""One program per local Cholesky call (ISSUE 30).

The local branch of ``cholesky`` used to dispatch three programs (tiles ->
global, the factorization, global -> tiles; five eager operations more when
the input was not donated), and the matrix crossed a program boundary in
f64 between each. Now it is one, built from ``matrix/tiling.py:on_global``.
What these tests hold on to: the number of programs XLA actually runs per
call (read from a profiler session, not from the library's own counter),
that the counters ``dlaf_entry_programs_total`` / ``dlaf_entry_calls_total``
say the same, that the factor is bit for bit what ``_cholesky_local``
computes from the global array, and what donation does to the input.
"""

import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import (_cholesky_local,
                                          _cholesky_local_scan, cholesky)
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix

#: XLA's CPU client leaves one such event on the host plane of a profiler
#: session per program it executes, jitted or eager (jax 0.9.0)
EXECUTE = "PjRtCpuExecutable::Execute"


@pytest.fixture(autouse=True)
def obs_reset():
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


@contextlib.contextmanager
def programs_run(trace_dir):
    """Yields a list that holds, after the block, how many programs ran
    inside it."""
    from jax.profiler import ProfileData

    ran = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield ran
    finally:
        jax.profiler.stop_trace()
        newest = max(glob.glob(os.path.join(str(trace_dir), "**",
                                            "*.xplane.pb"), recursive=True),
                     key=os.path.getmtime)
        ran.append(sum(e.name == EXECUTE
                       for plane in ProfileData.from_file(newest).planes
                       for line in plane.lines for e in line.events))


def _counter(name, entry="cholesky"):
    return sum(m["value"] for m in obs.registry().snapshot()
               if m["name"] == name and m["labels"].get("entry") == entry)


def _hpd(n, seed):
    g = np.random.default_rng(seed).standard_normal((n, n))
    return (g + g.T) / 2 + n * np.eye(n)


def _matrix(a, nb):
    m = Matrix.from_global(a, TileElementSize(nb, nb))
    jax.block_until_ready(m.storage)
    return m


def test_the_profiler_counts_every_program_eager_ones_too(tmp_path):
    x = jnp.arange(12.0).reshape(3, 4)
    twice = jax.jit(lambda v: 2 * v)
    jax.block_until_ready((twice(x), x + 1))        # compile outside
    with programs_run(tmp_path / "one") as one:
        jax.block_until_ready(twice(x))
    with programs_run(tmp_path / "three") as three:
        jax.block_until_ready(twice(twice(x)) + 1)
    assert (one, three) == ([1], [3])


def _one_call(tmp_path, n, nb, donate, with_info, reference):
    """A warm call of the public entry under a profiler session: one
    program ran, both counters rose by one, the factor is ``reference``'s
    of the global array, the input is consumed exactly when donated."""
    a = _hpd(n, seed=n + nb)
    cholesky("L", _matrix(a, nb), donate=donate, with_info=with_info)
    mat = _matrix(a, nb)
    programs, calls = (_counter("dlaf_entry_programs_total"),
                       _counter("dlaf_entry_calls_total"))
    with programs_run(tmp_path / "trace") as ran:
        out = cholesky("L", mat, donate=donate, with_info=with_info)
        jax.block_until_ready(out[0].storage if with_info else out.storage)
    assert ran == [1]
    assert _counter("dlaf_entry_programs_total") == programs + 1
    assert _counter("dlaf_entry_calls_total") == calls + 1
    if with_info:
        out, info = out
        assert info.dtype == jnp.int32 and int(info) == 0
    want = reference(jnp.asarray(a))
    np.testing.assert_array_equal(out.to_numpy(), np.asarray(want))
    assert mat.storage.is_deleted() == donate
    if not donate:
        np.testing.assert_array_equal(mat.to_numpy(), a)


@pytest.mark.parametrize("trailing", ["loop", "scan"])
@pytest.mark.parametrize("with_info", [False, True], ids=["plain", "info"])
@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
def test_local_cholesky_is_one_program_a_call(tmp_path, donate, with_info,
                                              trailing):
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl"),
                                 cholesky_trailing=trailing))
    n, nb = 72, 16          # ragged: 4.5 tiles a side

    def reference(g):
        if trailing == "scan":
            return _cholesky_local_scan(g, uplo="L", nb=nb)
        return _cholesky_local(g, uplo="L", nb=nb, trailing="loop")

    _one_call(tmp_path, n, nb, donate, with_info, reference)


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
def test_one_program_on_the_route_the_chip_runs(tmp_path, donate, as_on_tpu):
    """Under a TPU's knob resolution (tests/test_tpu_route.py): ozaki
    trailing with the look-ahead, the route of ``chol_d_n4096_1x1``."""
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl")))
    n, nb = 128, 64

    def reference(g):
        return _cholesky_local(g, uplo="L", nb=nb, trailing="ozaki",
                               lookahead=True)

    _one_call(tmp_path, n, nb, donate, False, reference)


def test_counters_are_silent_without_the_metrics_sink():
    C.initialize()
    assert not obs.metrics_active()
    cholesky("L", _matrix(_hpd(32, seed=3), 16))
    assert _counter("dlaf_entry_programs_total") == 0
    assert _counter("dlaf_entry_calls_total") == 0


def test_every_entry_counts_its_calls_and_the_distributed_cholesky_a_program(
        tmp_path, devices8):
    """``dlaf_entry_calls_total`` comes from ``obs.entry_span``, so every
    entry has it; ``dlaf_entry_programs_total`` is counted where an entry
    dispatches a program it says so of: the local branches and, since
    ISSUE 37, the distributed Cholesky's one program a call."""
    from dlaf_tpu.algorithms.triangular import triangular_solve
    from dlaf_tpu.comm.grid import Grid

    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl")))
    a = _hpd(32, seed=5)
    size = TileElementSize(8, 8)
    cholesky("L", Matrix.from_global(a, size, grid=Grid(2, 2)))
    assert _counter("dlaf_entry_calls_total") == 1
    assert _counter("dlaf_entry_programs_total") == 1
    triangular_solve("L", "L", "N", "N", 1.0,
                     Matrix.from_global(np.tril(a), size),
                     Matrix.from_global(a, size))
    assert _counter("dlaf_entry_calls_total", "triangular_solve") == 1
    assert _counter("dlaf_entry_programs_total", "triangular_solve") == 0


def test_the_program_is_built_once_per_static_key(tmp_path):
    from dlaf_tpu.algorithms.cholesky import _local_cholesky_cached

    C.initialize()
    _local_cholesky_cached.cache_clear()
    a = _hpd(32, seed=7)
    for _ in range(2):
        cholesky("L", _matrix(a, 16))
        cholesky("L", _matrix(a, 16), donate=True)
    info = _local_cholesky_cached.cache_info()
    assert (info.misses, info.hits) == (2, 2)
    # a configuration change drops it with the other program caches
    C.initialize(C.Configuration(cholesky_trailing="scan"))
    assert _local_cholesky_cached.cache_info().currsize == 0
