"""``larft``'s T^-1 inverse as emitted (ISSUE 40): masked doubling steps of
two small products each, no ``triangular_solve`` and no loop, and the
counter ``dlaf_larft_doublings_total{k}`` the chase's back-transformation
and the reduction's scan builder count per EXECUTED level and panel. The
counter cases trace the builders only (``lower``): the counts are made at
trace time, the results are other tests'."""

import importlib
import re

import jax
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.tile_ops import lapack as tl

bt = importlib.import_module("dlaf_tpu.eigensolver.back_transform")
r2b = importlib.import_module("dlaf_tpu.eigensolver.reduction_to_band")

F64 = np.float64


@pytest.fixture
def metrics(tmp_path):
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl")))
    C._clear_program_caches()
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()
    C._clear_program_caches()


def _doublings(k=None):
    return sum(m["value"] for m in obs.registry().snapshot()
               if m["name"] == "dlaf_larft_doublings_total"
               and (k is None or m["labels"].get("k") == str(k)))


def _spec(*shape):
    return jax.ShapeDtypeStruct(shape, F64)


def test_chase_shape_has_no_triangular_solve_and_no_loop():
    """At the chase's (255, 128): the StableHLO a TPU is handed (the
    parent's held ``stablehlo.triangular_solve``, which the TPU compiler
    expands into a 127-trip loop) and the module the CPU compiles hold no
    triangular solve, no custom call and no ``while``."""
    args = (_spec(255, 128), _spec(128))
    tpu = jax.jit(tl.larft).trace(*args).lower(lowering_platforms=("tpu",))
    ops = set(re.findall(r"stablehlo\.(\w+)", tpu.as_text()))
    assert "dot_general" in ops
    assert not ops & {"triangular_solve", "custom_call", "while"}, ops
    cpu = jax.jit(tl.larft).lower(*args).compile().as_text()
    ops = set(re.findall(r"= \S+ ([\w-]+)\(", cpu))
    assert "dot" in ops
    assert not ops & {"triangular-solve", "custom-call", "while"}, ops


@pytest.mark.parametrize("b, group", [(16, 16), (16, 5)])
def test_counter_reads_doublings_per_chase_level(metrics, b, group):
    """``ceil(log2 G)`` steps a level, times the levels of one traced
    ``_bt_b2t_blocked``: ``ceil(n_sweeps / G) * n_steps``."""
    n, m = 96, 8
    n_sweeps, n_steps = n - 1, -(-(n - 1) // b)
    bt._bt_b2t_blocked.lower(_spec(n_sweeps, n_steps, b),
                             _spec(n_sweeps, n_steps), _spec(n, m),
                             b=b, n=n, group=group)
    levels = -(-n_sweeps // group) * n_steps
    steps = int(np.ceil(np.log2(group)))
    assert _doublings(group) == steps * levels == _doublings()


@pytest.mark.parametrize("n", [3 * 128, 5 * 128 - 7])
def test_counter_reads_doublings_per_reduction_panel(metrics, n):
    """Seven steps a panel at band 128, times the panels of one traced
    ``_red2band_local_scan``."""
    r2b._red2band_local_scan.lower(_spec(n, n), nb=128)
    panels = -(-n // 128) - 1
    assert _doublings(128) == 7 * panels == _doublings()
