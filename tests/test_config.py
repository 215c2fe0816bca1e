"""Tests for layered configuration (reference: src/init.cpp:117-177 behavior)."""

import dlaf_tpu.config as C
from dlaf_tpu.obs.logging import forget_once, once_seen_keys


def test_defaults():
    cfg = C.update_configuration()
    assert cfg.grid_ordering == "row-major"
    # 0 = auto: 4096 on TPU, device-disabled on CPU (round-4 sweep)
    assert cfg.secular_device_min_k == 0


def test_user_struct_layer():
    cfg = C.update_configuration(C.Configuration(secular_device_min_k=3))
    assert cfg.secular_device_min_k == 3


def test_env_overrides_user(monkeypatch):
    monkeypatch.setenv("DLAF_SECULAR_DEVICE_MIN_K", "4")
    cfg = C.update_configuration(C.Configuration(secular_device_min_k=3))
    assert cfg.secular_device_min_k == 4


def test_cli_overrides_env(monkeypatch):
    monkeypatch.setenv("DLAF_SECULAR_DEVICE_MIN_K", "4")
    cfg = C.update_configuration(C.Configuration(secular_device_min_k=3),
                                 argv=["--dlaf:secular-device-min-k=5", "ignored", "--other"])
    assert cfg.secular_device_min_k == 5


def test_cli_bool_and_dashes(monkeypatch):
    cfg = C.update_configuration(argv=["--dlaf:print-config"])
    assert cfg.print_config is True
    cfg = C.update_configuration(argv=["--dlaf:grid-ordering=col-major"])
    assert cfg.grid_ordering == "col-major"


def test_initialize_get_finalize():
    cfg = C.initialize(C.Configuration(enable_x64=True))
    assert C.get_configuration() is cfg
    C.finalize()
    assert C.get_configuration() is not cfg  # re-initialized with defaults


def test_slices_auto_default(monkeypatch):
    """f64_gemm_slices=0 (the default) resolves per platform: 7 where f64
    is the double-f32 emulation (TPU), 8 where it is native. Explicit
    values are honored verbatim (config.py / blas._oz_slices)."""
    from dlaf_tpu.tile_ops import blas

    C.initialize()
    assert C.get_configuration().f64_gemm_slices == 0
    assert blas._oz_slices() == 8  # this suite runs on the CPU backend

    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert blas._oz_slices() == 7

    monkeypatch.setenv("DLAF_F64_GEMM_SLICES", "8")
    C.initialize()
    assert blas._oz_slices() == 8  # explicit wins on any platform

    monkeypatch.setenv("DLAF_F64_GEMM_SLICES", "10")
    import pytest
    with pytest.raises(ValueError):
        C.initialize()
    monkeypatch.delenv("DLAF_F64_GEMM_SLICES")
    C.initialize()


def test_resolve_step_mode(monkeypatch):
    # auto (the default) picks per (step count, platform) from the
    # measured compile constants; explicit modes pass through untouched
    import dlaf_tpu.config as config

    config.initialize()
    try:
        assert config.get_configuration().dist_step_mode == "auto"
        assert config.resolve_step_mode(8, "cpu") == "unrolled"
        assert config.resolve_step_mode(200, "cpu") == "scan"
        assert config.resolve_step_mode(31, "tpu") == "unrolled"
        assert config.resolve_step_mode(32, "tpu") == "scan"
        monkeypatch.setenv("DLAF_DIST_STEP_MODE", "scan")
        config.initialize()
        assert config.resolve_step_mode(2, "tpu") == "scan"
        monkeypatch.setenv("DLAF_DIST_STEP_MODE", "unrolled")
        config.initialize()
        assert config.resolve_step_mode(10_000, "tpu") == "unrolled"
    finally:
        monkeypatch.delenv("DLAF_DIST_STEP_MODE", raising=False)
        config.initialize()


def test_resolve_platform_auto(monkeypatch, capsys):
    """The shared platform-auto resolver (config.resolve_platform_auto):
    non-auto values pass through silently; "auto" picks per the process
    default backend and announces once per (knob, backend, choice)."""
    import jax

    # explicit value: passthrough, no announcement
    out = C.resolve_platform_auto(
        "native", knob="t_knob", tpu_choice="mxu", other_choice="native",
        detail="d")
    assert out == "native" and capsys.readouterr().err == ""

    for backend, expect in (("cpu", "native"), ("tpu", "mxu")):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        forget_once("config", ("t_knob", backend, expect))
        try:
            got = C.resolve_platform_auto(
                "auto", knob="t_knob", tpu_choice="mxu",
                other_choice="native", detail="why-detail")
            assert got == expect
            msg = capsys.readouterr().err
            assert f"t_knob=auto resolved to {expect!r}" in msg
            assert "why-detail" in msg
            # second resolution: same answer, announced only once
            assert C.resolve_platform_auto(
                "auto", knob="t_knob", tpu_choice="mxu",
                other_choice="native", detail="why-detail") == expect
            assert capsys.readouterr().err == ""
        finally:
            forget_once("config", ("t_knob", backend, expect))


def test_resolved_route_accessors(monkeypatch):
    """resolved_f64_gemm/resolved_f64_trsm: the bare defaults give the
    native routes off-TPU and the mxu/mixed routes on TPU; explicit knobs
    outrank auto on any backend. The announce keys these resolutions add
    are removed on exit so later announcement-capturing tests stay
    order-independent."""
    import jax

    keys = [(k, b, c) for k, b, c in
            (("f64_gemm", "cpu", "native"), ("f64_trsm", "cpu", "native"),
             ("f64_gemm", "tpu", "mxu"), ("f64_trsm", "tpu", "mixed"))]
    pre = {k for k in keys if k in once_seen_keys("config")}
    C.initialize()  # bare defaults (f64_gemm/f64_trsm = "auto")
    try:
        assert C.resolved_f64_gemm() == "native"  # suite runs on CPU
        assert C.resolved_f64_trsm() == "native"

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert C.resolved_f64_gemm() == "mxu"
        assert C.resolved_f64_trsm() == "mixed"

        # explicit knob outranks auto even on TPU
        C.initialize(C.Configuration(f64_gemm="native",
                                     f64_trsm="native"))
        assert C.resolved_f64_gemm() == "native"
        assert C.resolved_f64_trsm() == "native"
    finally:
        for k in keys:
            if k not in pre:
                forget_once("config", k)
        C.initialize()


def test_cholesky_trailing_auto_still_validates(monkeypatch):
    """cholesky_trailing="auto" resolves before the VALID_TRAILING gate,
    so bogus explicit values still fail fast at the driver."""
    import jax.numpy as jnp
    import numpy as np
    import pytest

    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    m = Matrix.from_global(jnp.asarray(np.eye(8)), TileElementSize(4, 4))
    out = cholesky("L", m)  # auto default resolves (loop on CPU) and runs
    np.testing.assert_allclose(np.tril(np.asarray(out.to_numpy())),
                               np.eye(8), atol=1e-12)
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "bogus")
    C.initialize()
    try:
        with pytest.raises(Exception, match="cholesky_trailing"):
            cholesky("L", m)
    finally:
        monkeypatch.delenv("DLAF_CHOLESKY_TRAILING")
        C.initialize()


def test_cholesky_lookahead_knob(monkeypatch):
    """cholesky_lookahead: validated enum ("0"/"1"/"auto"), env-layered,
    auto resolves per backend (1 on TPU, 0 elsewhere)."""
    import jax
    import pytest

    from dlaf_tpu.obs.logging import forget_once

    assert C.Configuration().cholesky_lookahead == "auto"
    with pytest.raises(ValueError, match="cholesky_lookahead"):
        C.initialize(C.Configuration(cholesky_lookahead="yes"))
    C.initialize(C.Configuration(cholesky_lookahead="1"))
    try:
        assert C.resolved_cholesky_lookahead() is True
        monkeypatch.setenv("DLAF_CHOLESKY_LOOKAHEAD", "0")
        C.initialize()
        assert C.resolved_cholesky_lookahead() is False
        monkeypatch.delenv("DLAF_CHOLESKY_LOOKAHEAD")
        C.initialize()
        for backend, expect in (("cpu", False), ("tpu", True)):
            monkeypatch.setattr(jax, "default_backend",
                                lambda b=backend: b)
            key = ("cholesky_lookahead", backend, "1" if expect else "0")
            forget_once("config", key)
            try:
                assert C.resolved_cholesky_lookahead() is expect
            finally:
                forget_once("config", key)
    finally:
        C.initialize(C.Configuration())


def test_lower_layers_import_nothing_above():
    """``config.py``, ``tile_ops/``, ``matrix/``, ``comm/`` and ``common/``
    sit under the algorithms: none imports from ``algorithms``,
    ``eigensolver``, ``serve``, ``fleet`` or ``miniapp`` (they still
    report to ``obs`` and ``health``). One import is known and named as
    debt (ROADMAP D15): ``tile_ops/lapack.py`` takes the host secular
    solver from ``eigensolver/tridiag_solver.py``."""
    import glob
    import os
    import re

    root = os.path.dirname(os.path.abspath(C.__file__))
    files = [os.path.join(root, "config.py")]
    for layer in ("tile_ops", "matrix", "comm", "common"):
        files += sorted(glob.glob(os.path.join(root, layer, "*.py")))
    above = re.compile(r"^\s*(?:from|import)\s+(?:\.\.?|dlaf_tpu\.)"
                       r"(algorithms|eigensolver|serve|fleet|miniapp)\b")
    found = []
    for path in files:
        with open(path) as f:
            for line in f:
                if above.match(line):
                    found.append((os.path.relpath(path, root),
                                  line.strip()))
    assert found == [(os.path.join("tile_ops", "lapack.py"),
                      "from ..eigensolver.tridiag_solver import "
                      "_secular_roots_host")], found
