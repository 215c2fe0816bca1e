"""Test harness configuration.

Mirrors the reference's "6 oversubscribed MPI ranks" strategy
(``test/include/dlaf_test/comm_grids/grids_6_ranks.h``) by forcing an
8-device virtual CPU platform so distributed code paths (2D meshes, ICI
collective verbs, shard_map algorithms) run on any host. Must run before the
first ``import jax`` anywhere in the test session.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Run the full assertion ladder in tests (reference CI enables heavy asserts).
os.environ.setdefault("DLAF_ASSERT_HEAVY_ENABLE", "1")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# The suite is XLA-compile-dominated (the 30 slowest tests are 5-30 s of
# compile each); persist compiled programs across test sessions like the
# product path does (config.initialize() places the same directory).
# Cache key includes platform + device count, so chip entries never
# collide with these.
#
# Threshold 5 s (not 0.5): on this container's jaxlib, cache-LOADED small
# custom-call-dense programs (the local red2band family) intermittently
# compute garbage when many deserialized executables run in one session —
# reproduced as random test_reduction_to_band scan-vs-unrolled mismatches
# that vanish with the cache off and never occur on cold (writing) runs.
# Keeping sub-5s compiles out of the cache sidesteps the corruption where
# it was observed while retaining the big-program compile savings.
# An explicit JAX_COMPILATION_CACHE_DIR wins over the repo-local default:
# CI's slow job restores a cross-run cache there (.github/workflows/ci.yml)
# and an unconditional override would silently leave that cache empty.
_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
jax.config.update("jax_compilation_cache_dir", _cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)

import pytest  # noqa: E402

#: The `quick` smoke tier (``pytest -m quick``): ONE representative config
#: per algorithm family / core layer, for quick sanity checks where the
#: full suite's wall is unaffordable. The FIRST collected parametrization of each named test gets the
#: marker, so the tier tracks parametrize changes without hand-pinned ids.
_QUICK_TESTS = {
    ("test_cholesky.py", "test_cholesky_local"),
    ("test_cholesky.py", "test_cholesky_distributed"),
    ("test_cholesky.py", "test_cholesky_local_trailing_variants"),
    ("test_cholesky.py", "test_cholesky_scan_native_dtypes"),
    ("test_triangular.py", "test_solve_local_all_combos"),
    ("test_triangular.py", "test_solve_distributed"),
    ("test_qr.py", "test_t_factor_local_matrix"),
    ("test_qr.py", "test_t_factor_distributed"),
    ("test_gen_to_std.py", "test_gen_to_std_local"),
    ("test_gen_to_std.py", "test_gen_to_std_distributed"),
    ("test_gen_to_std.py", "test_general_sub_multiply"),
    ("test_reduction_to_band.py", "test_red2band_local"),
    ("test_reduction_to_band.py", "test_red2band_distributed_band_size"),
    ("test_band_to_tridiag.py", "test_band_to_tridiag"),
    ("test_band_to_tridiag.py", "test_native_matches_numpy"),
    ("test_tridiag_solver.py", "test_random"),
    ("test_eigensolver.py", "test_eigensolver"),
    ("test_eigensolver.py", "test_eigensolver_distributed"),
    ("test_eigensolver.py", "test_gen_eigensolver"),
    ("test_eigensolver.py", "test_bt_reduction_to_band"),
    ("test_eigensolver.py", "test_bt_band_to_tridiag"),
    ("test_eigensolver.py", "test_permutations"),
    ("test_ozaki.py", "test_accuracy_f64_grade"),
    ("test_ozaki.py", "test_syrk_matches_matmul"),
    ("test_pallas_kernels.py", "test_masked_trailing_update"),
    ("test_pallas_panel.py", "test_fused_potrf_parity"),
    ("test_pallas_panel.py", "test_fused_step_emits_one_kernel_per_panel_op"),
    ("test_tile_ops.py", "test_gemm"),
    ("test_tile_ops.py", "test_lange"),
    ("test_matrix.py", "test_matrix_roundtrip_local"),
    ("test_matrix.py", "test_matrix_sharded_over_mesh"),
    ("test_comm.py", "test_bcast"),
    ("test_comm.py", "test_grid_shapes"),
    ("test_config.py", "test_defaults"),
    ("test_config.py", "test_cli_overrides_env"),
    ("test_distribution.py", "test_distribution_2d"),
    ("test_index2d.py", "test_basic_coords"),
    ("test_types.py", "test_flop_weights"),
    ("test_aux_components.py", "test_max_norm_local_and_distributed"),
    ("test_aux_components.py", "test_bench_headline_fallback_replays_history"),
    ("test_serve.py", "test_cholesky_batched_bitwise_vs_singles"),
    ("test_serve.py", "test_warmed_queue_artifact_passes_require_serve"),
    ("test_resilience.py", "test_queue_dispatch_retries_transient_fault"),
    ("test_resilience.py", "test_eigensolver_preempt_resume_bitwise"),
    ("test_obs.py", "test_noop_fast_path_when_disabled"),
    ("test_obs.py", "test_jsonl_schema_roundtrip"),
    ("test_obs.py", "test_miniapp_cholesky_metrics_integration"),
    ("test_telemetry.py", "test_telemetry_call_records_compile_and_retrace"),
    ("test_telemetry.py", "test_bench_gate_committed_history_replays_clean"),
    ("test_accuracy.py", "test_probe_within_variance_bound"),
    ("test_accuracy.py", "test_gate_legs"),
    ("test_analysis.py", "test_drills_trip_their_rules"),
    ("test_analysis.py", "test_lint_repo_is_clean"),
    ("test_live_telemetry.py", "test_serve_trace_join_end_to_end"),
    ("test_live_telemetry.py",
     "test_metrics_scrape_monotone_across_two_scrapes"),
}


#: Tier-1 wall-clock budget control. Fixing the `jax.shard_map` imports
#: (PR 1 satellite) grew the collected ``not slow`` selection from ~400
#: to ~1340 tests, and the suite is compile-dominated with sub-5s
#: compiles deliberately kept out of the persistent cache (see above) —
#: running every distributed parametrization per push no longer fits the
#: ~15 min tier budget. For the heavy algorithm files, keep every
#: STRIDE-th parametrization of each test function in the default tier
#: and move the rest to the ``slow`` deep tier (``ci/run.sh full`` still
#: runs everything). Selection is deterministic (sorted by nodeid, so
#: independent of collection order), tracks parametrize changes, and
#: never demotes a ``quick``-marked item.
_TIER1_STRIDE = {
    "test_cholesky.py": 8,
    # PR-6 rebalance: the quick tier had crept to 761 s of the 870 s
    # budget; the eigensolver files carry the compile-heaviest
    # parametrizations (full-pipeline + distributed grids), so their
    # strides widen and the new batched-vs-serial D&C pins are strided
    # from day one (every parametrization still runs in ci/run.sh full).
    # Post-rebalance tier-1: 742 passed in ~545-615 s warm-cache.
    "test_eigensolver.py": 8,
    "test_reduction_to_band.py": 6,
    "test_gen_to_std.py": 4,
    "test_triangular.py": 4,
    "test_ozaki.py": 2,
    "test_tridiag_solver.py": 2,
}


def pytest_collection_modifyitems(config, items):
    seen = set()
    thinned = {}
    for item in items:
        key = (item.path.name, getattr(item, "originalname", item.name))
        if key in _QUICK_TESTS and key not in seen:
            seen.add(key)
            item.add_marker(pytest.mark.quick)
        if item.path.name in _TIER1_STRIDE:
            # group by class too: same-named methods in different classes
            # (e.g. test_ozaki.py's per-route Test* classes) must stride
            # independently, or one class's parametrize edits shift which
            # of another's parametrizations stay in the default tier
            cls = getattr(item, "cls", None)
            gkey = (item.path.name, cls.__name__ if cls else None,
                    getattr(item, "originalname", item.name))
            thinned.setdefault(gkey, []).append(item)
    for key, group in thinned.items():
        stride = _TIER1_STRIDE[key[0]]
        for i, item in enumerate(sorted(group, key=lambda it: it.nodeid)):
            if i % stride and \
                    not any(m.name == "quick" for m in item.own_markers):
                item.add_marker(pytest.mark.slow)


_exit_status = None


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session, exitstatus):
    global _exit_status
    _exit_status = int(exitstatus)


def pytest_unconfigure(config):
    # Interpreter teardown of a full-tier session — hundreds of live XLA
    # executables plus the 8-device virtual CPU client — costs 1-2 min of
    # pure destructor time AFTER the summary prints, real wall the tier
    # budget cannot spare. Everything durable (persistent compile cache,
    # obs JSONL artifacts, junit files) has been written synchronously by
    # now (trylast: the terminal reporter's summary is already out), so
    # skip the teardown. Embedders that call pytest.main() in-process and
    # need control back (IDE runners, meta-runners) opt out via
    # DLAF_PYTEST_TEARDOWN=1; coverage saves its data via atexit, which
    # os._exit would bypass, so a live coverage module also opts out.
    import sys

    if _exit_status is not None and \
            not os.environ.get("DLAF_PYTEST_TEARDOWN") and \
            "coverage" not in sys.modules:

        try:
            # what the obs layer's atexit hook would have done (os._exit
            # skips atexit): land the profiler trace + final snapshot of
            # a session run with DLAF_TRACE_DIR/DLAF_METRICS_PATH set
            from dlaf_tpu import obs

            obs._shutdown()
        except Exception:
            pass
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(_exit_status)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Resolve every platform-keyed knob as a TPU process does, on this
    CPU: ``jax.default_backend()`` answers "tpu" for the test. The knobs
    are read at trace time and are not cache keys, so the registered
    program caches are dropped on the way in and on the way out (a trace
    made under one resolution must not serve the other)."""
    import dlaf_tpu.config as C

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    C.initialize()
    C._clear_program_caches()
    yield
    monkeypatch.undo()
    C.initialize()
    C._clear_program_caches()
