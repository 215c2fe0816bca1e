"""The chase back-transformation's staircase as emitted: a skew of the
level's reflectors (pad, reshape, slice, transpose), bit for bit the vmapped
``dynamic_update_slice`` kept here as the specification, which a TPU is
handed as a ``scatter`` and runs as a loop of G trips."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

bt = importlib.import_module("dlaf_tpu.eigensolver.back_transform")


def spec_staircase(vcols, L):
    """The specification: column j is reflector j written at row j of a
    zero column of length L."""
    G = vcols.shape[0]
    return jax.vmap(lambda vj, j: lax.dynamic_update_slice(
        jnp.zeros((L,), vcols.dtype), vj, (j,)))(vcols, jnp.arange(G)).T


def _bits(x):
    x = np.asarray(x)
    return x.shape, x.dtype, x.view(np.uint8).tobytes()


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("b, group", [(128, 128), (128, 129), (16, 5),
                                      (16, 1), (7, 8)])
def test_staircase_is_the_spec_bit_for_bit(b, group, dtype):
    """Every (b, G <= b + 1) the blocked program accepts, padded sweep
    groups' zero reflectors included."""
    rng = np.random.default_rng(b * 1000 + group)
    v = rng.standard_normal((group, b))
    if dtype is np.complex128:
        v = v + 1j * rng.standard_normal((group, b))
    v[-1] = 0.0                     # a padded sweep's reflector
    v = jnp.asarray(v.astype(dtype))
    L = b + group - 1
    got = jax.jit(bt._staircase, static_argnums=1)(v, L)
    assert _bits(got) == _bits(spec_staircase(v, L))


def _spec(*shape):
    return jax.ShapeDtypeStruct(shape, np.float64)


def test_blocked_program_hands_a_tpu_no_scatter(as_on_tpu):
    """The whole blocked program at n = 96, b = G = 16, lowered for a TPU
    under its knob resolution: no ``stablehlo.scatter``."""
    n, b, m = 96, 16, 8
    n_sweeps, n_steps = n - 1, -(-(n - 1) // b)
    text = bt._bt_b2t_blocked.trace(
        _spec(n_sweeps, n_steps, b), _spec(n_sweeps, n_steps), _spec(n, m),
        b=b, n=n, group=b).lower(lowering_platforms=("tpu",)).as_text()
    ops = set(re.findall(r"stablehlo\.(\w+)", text))
    assert "dot_general" in ops
    assert "scatter" not in ops, ops


def test_staircase_compiles_without_loop_scatter_or_gather():
    """At the chase's (G, b) = (128, 128): the module the CPU compiles."""
    text = jax.jit(bt._staircase, static_argnums=1).lower(
        _spec(128, 128), 255).compile().as_text()
    ops = set(re.findall(r"= \S+ ([\w-]+)\(", text))
    assert "pad" in ops
    assert not ops & {"while", "scatter", "gather"}, ops
