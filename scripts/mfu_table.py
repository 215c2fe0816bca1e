#!/usr/bin/env python
"""Print the MFU / roofline table (markdown, on stdout).

Every perf PR so far reported bare GF/s; this script supplies the
*denominator*: a route-specific achievable ceiling per BASELINE config, so
results read as "% of route ceiling" (MFU) instead of unanchored numbers.

Ceilings are per chip and route-specific, not the marketing peak:

* **ozaki f64-equivalent** — the error-free int8-slice route spends
  ``s*(s+1)/2`` slice-pair dots per f64 product (s=7 on TPU: 28 — see
  ``config.f64_gemm_slices``), so the compute ceiling is
  ``dot-route peak / 28`` (bf16 path on TPU since the dot_ab session;
  bit-identical to the s8 dot).  A syrk-shaped trailing halves the
  mirrored pairs, so blocked factorizations can exceed ~½ of this model's
  denominator-pessimism — the ceiling is the honest matmul-pair model.
* **HBM roofline** — the jnp slice path is memory-bound well below the
  MXU ceiling at small N (the r4 sessions measured ~100x below raw dot
  peak); the traffic model below counts, per factorization step with
  trailing extent ``m``: 2 int8 slice operand sets (``2*s*m*nb`` bytes),
  one live int32 partial plane read+written and the f64 accumulator
  read+written under the scan accumulation schedule
  (``(4+4+8+8)*m**2``).  ``ceiling_hbm = flops / bytes * BW``.  This is
  an estimate of the *route's* traffic, stated so future PRs can refine
  it — not a measured counter.
* The **effective ceiling** per config is ``min(compute, HBM)``; the
  table's ``bound`` column names which side binds.

Measured values come from the append-only ``.bench_history.jsonl``
(post-peel-fix TPU f64 entries only — the pre-fix decomposition was
numerically corrupted; see ``PEEL_FIX_TS`` below).  Multi-chip
BASELINE configs whose grids this environment has never exposed report
their single-chip rehearsal number with a note, or "pending".

* **ICI roofline** (multi-chip configs) — comm-bound ceiling derived from
  the per-axis ``dlaf_comm_collective_bytes_total`` counters: the
  distributed program is TRACED (no compile, no execution) on a virtual
  CPU mesh of the config's grid in a subprocess — the UNROLLED builders,
  whose per-``k`` emission makes the trace-time counters exact per-run
  traffic (the scan builders count per executed step too, but of their
  telescoped windows, padded to each segment's widest step) — the
  trace-time byte counters give the per-rank ICI payload per axis, and
  the ceiling is
  ``flops_model / sum_axis(2(p-1)/p * bytes_axis / link_bw)`` — the ring
  all-reduce traffic factor applied per mesh axis (conservative for the
  all_gathers, whose factor is (p-1)/p).  This is the bound the
  ``comm_lookahead`` overlap (docs/comm_overlap.md) must stay under even
  with perfect compute/comm overlap, so the "pending" multi-chip rows
  carry a number before live silicon does.  Link bandwidth is the public
  per-chip ICI aggregate / 4 links.

* **measured MFU (device)** — the ISSUE-14 measured path: entry-span
  flop models joined to the phase's attributed device-busy wall from a
  profiler trace (``dlaf_tpu.obs.devtrace``), replayed hermetically from
  the committed fixture under ``tests/fixtures/devtrace/`` (a distilled
  ``DLAF_TRACE_DIR`` Chrome trace + its merged JSONL). The denominator
  is measured device time, not host wall and not a model — but the
  committed fixture ran in the CPU CI container, so its numbers are
  labeled with their platform/shape and are NOT comparable to the TPU
  roofline ceilings; a TPU-captured fixture drops in with no code
  change.

Usage:
    python scripts/mfu_table.py            # print the markdown table
    python scripts/mfu_table.py --no-ici   # skip the traced ICI column
                                           # (fast; prints em-dashes)
    python scripts/mfu_table.py --measured # fill the measured(dev) and
                                           # measured-bound columns from
                                           # the committed devtrace and
                                           # critpath fixtures
    python scripts/mfu_table.py --fixture DIR  # override the devtrace
                                           # fixture dir
    python scripts/mfu_table.py --critpath-fixture DIR  # override the
                                           # critpath fixture dir
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
HISTORY = os.path.join(REPO, ".bench_history.jsonl")
BEGIN, END = "<!-- mfu-table:begin -->", "<!-- mfu-table:end -->"

#: Entries before the ozaki peel fix measured a corrupted
#: decomposition and must not feed the MFU table
PEEL_FIX_TS = "2026-08-02T04:00"

#: Published per-chip peaks, the one table of them in this repo (Google
#: Cloud documentation, "TPU v5e" and "TPU v5p" system architecture:
#: FLOP/s in bf16, OP/s in int8, HBM bytes/s). The measured platform is
#: v5e; v5p is the north-star target part.
CHIPS = {
    "v5e": dict(bf16=197e12, int8=394e12, hbm=819e9),
    "v5p": dict(bf16=459e12, int8=918e12, hbm=2765e9),
}

#: The key the chip itself reports (``jax.devices()[0].device_kind``) ->
#: part name in :data:`CHIPS`. Only kinds a run of this repo has seen.
DEVICE_KINDS = {"TPU v5 lite": "v5e"}


def peaks_for(device_kind: str) -> dict:
    """Published peaks of the chip reporting ``device_kind``. An unknown
    kind is an error, not a default: a utilization against the wrong
    denominator is worse than none."""
    try:
        return CHIPS[DEVICE_KINDS[device_kind]]
    except KeyError:
        raise KeyError(
            f"mfu_table: no published peaks for device_kind "
            f"{device_kind!r}; add it to DEVICE_KINDS/CHIPS with its "
            f"source") from None

#: int8/bf16 slice-pair dots per f64 product at the TPU default
#: f64_gemm_slices=0 -> s=7 (config.py): s*(s+1)/2.
OZ_SLICES = 7
OZ_PAIRS = OZ_SLICES * (OZ_SLICES + 1) // 2

#: Per-link, per-direction ICI bytes/s: public per-chip aggregate (v5e
#: 1600 Gbps, v5p 4800 Gbps) spread over the 4 torus links.
ICI_LINK_BW = {"v5e": 50e9, "v5p": 150e9}

#: Reference real-flop models per family (the entry spans' total_ops
#: basis at real dtypes — add + mul summed — so the ICI ceiling divides
#: like the measured numbers do; config #3's complex weighting is noted
#: in its row, not folded in here).
#:
#: The three eigensolver-pipeline stage models (new in PR 6 — config #5
#: stops being a red2band proxy; docs/eigensolver_perf.md):
#:
#: * tridiag — D&C merge gemms: level l runs 2^l merges of size n/2^l,
#:   each blkdiag(q1, q2) @ qc ~ (n/2^l)^3 muls+adds -> sum = (4/3) n^3
#:   (deflation only reduces it, so this is the model ceiling).
#: * bt_b2t — chase back-transform: ~n^2/b reflectors of length b, each
#:   a rank-1 segment update of 2*b*m muls+adds over m = n columns
#:   -> 2 n^3.
#: * bt_r2b — reflector-block application C <- (I - V T V^H) C:
#:   W2 = V^H C and C -= V W2 at 2*b*m_p*n muls+adds each, summed over
#:   panels (sum m_p ~ n^2 / 2b) -> 2 n^3.
_FLOPS_MODEL = {
    "cholesky": lambda n: n ** 3 / 3,
    "trsm": lambda n: n ** 3,            # square B (free axis = n)
    "hegst": lambda n: n ** 3,
    "red2band": lambda n: 4 * n ** 3 / 3,
    "tridiag": lambda n: 4 * n ** 3 / 3,
    "bt_b2t": lambda n: 2 * n ** 3,
    "bt_r2b": lambda n: 2 * n ** 3,
    # full standard-EVP pipeline (the eigensolver entry span's canonical
    # 5n^3/3 muls + 5n^3/3 adds; #5's extra gen stages noted in its row)
    "eigensolver": lambda n: 10 * n ** 3 / 3,
}


def oz_compute_ceiling(chip: str, dot: str = "bf16") -> float:
    """f64-equivalent GF/s ceiling of the ozaki route on ``chip``."""
    return CHIPS[chip][dot] / OZ_PAIRS / 1e9


#: Modeled per-step panel-chain latency (seconds) of the CURRENT product
#: route: the 2026-08-01 v5e panel-chain probes measured the mixed
#: (f32-seed + Newton) potrf+trsm chain at ~+0.6 ms/step over pure gemm
#: at nb=256 (config.py ``f64_trsm`` docstring) — a latency- not
#: flops-bound figure, so it is held flat across the nb=256..512 configs
#: (a model, stated so future PRs can refine it with measured numbers).
#: The fused Pallas panel route (``panel_impl``, docs/pallas_panel.md)
#: replaces the chain with TWO kernel dispatches per step — modeled
#: ~0.1 ms/step pending silicon — which is the ~6x panel-ceiling lift
#: the ``fpanel`` / ``fpanel+fp1`` bench arms exist to measure.
PANEL_STEP_S = 0.6e-3

#: Modeled per-step latency of the FUSED STEP route (``step_impl``,
#: docs/pallas_panel.md): ONE pallas_call per blocked step — the panel
#: potrf, the strip solve, and the adjacent trailing slab never leave
#: VMEM between them, so the per-step floor collapses to a single kernel
#: dispatch + the strip's HBM streaming. Modeled ~0.05 ms/step pending
#: silicon (half the fused-panel chain's two dispatches) — the ``fstep``
#: bench arm and the committed critpath fixture pair
#: (tests/fixtures/critpath{,_prestep}/) are the measured instruments
#: that replace this model.
FUSED_STEP_S = 0.05e-3

#: Families whose per-step panel chain serializes across steps (step
#: k+1's panel consumes step k's strip): the chain is a WALL-CLOCK FLOOR
#: of nt * PANEL_STEP_S even under perfect lookahead/comm overlap, so
#: ``flops / floor`` is a hard ceiling like the rooflines.
_PANEL_CHAIN_FAMILIES = ("cholesky", "trsm", "hegst")


def panel_ceiling(family: str, n: int, nb: int,
                  step_s: float = PANEL_STEP_S):
    """Panel-critical-path ceiling in GF/s (steps x modeled panel
    latency), or None for families without a serialized per-step panel
    chain."""
    if family not in _PANEL_CHAIN_FAMILIES:
        return None
    nt = -(-n // nb)
    return _FLOPS_MODEL[family](n) / (nt * step_s) / 1e9


def chol_hbm_ceiling(chip: str, n: int, nb: int) -> float:
    """HBM-roofline GF/s for the blocked Cholesky's ozaki trailing path
    (traffic model in the module docstring; real-arithmetic flops n^3/3)."""
    flops = bytes_ = 0.0
    nt = -(-n // nb)
    for k in range(nt):
        m = n - (k + 1) * nb
        if m <= 0:
            continue
        flops += 2.0 * m * m * nb          # trailing herk/gemm adds+muls
        bytes_ += 2.0 * OZ_SLICES * m * nb + 24.0 * m * m
    if bytes_ == 0:
        return float("inf")
    return flops / bytes_ * CHIPS[chip]["hbm"] / 1e9


def trsm_hbm_ceiling(chip: str, n: int, nb: int) -> float:
    """Same traffic shape for the blocked substitution (free axis = n)."""
    return chol_hbm_ceiling(chip, n, nb)


def _trace_ici_child(spec: dict) -> None:
    """Child-process body (``--trace-ici``): trace the family's
    distributed builder on a virtual CPU mesh of the config's grid —
    abstract eval only, no compile/exec — and print the per-axis
    ``dlaf_comm_collective_bytes_total`` totals as JSON. Runs under
    ``tpu_info.cpu_subprocess_env`` so the device count can be forced."""
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from dlaf_tpu import obs
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import (GlobalElementSize, GridSize2D,
                                         TileElementSize)
    from dlaf_tpu.matrix.distribution import Distribution
    from dlaf_tpu.matrix.tiling import storage_tile_grid

    family = spec["family"]
    n, nb = spec["n"], spec["nb"]
    rows, cols = spec["rows"], spec["cols"]
    dtype = jnp.dtype(spec["dtype"])
    grid = Grid(rows, cols)
    dist = Distribution(GlobalElementSize(n, n), TileElementSize(nb, nb),
                        grid_size=GridSize2D(rows, cols))
    str_, stc, _, _ = storage_tile_grid(dist)
    sds = jax.ShapeDtypeStruct((str_, stc, nb, nb), dtype)

    def trace_red2band():
        from dlaf_tpu.eigensolver.reduction_to_band import \
            _build_dist_red2band

        fn = _build_dist_red2band(dist, grid.mesh, dtype.name,
                                  spec.get("band", nb))
        jax.eval_shape(fn, sds)

    def trace_bt_r2b():
        from dlaf_tpu.eigensolver.back_transform import _build_dist_bt_r2b

        band = spec.get("band", nb)
        npan = max(-(-n // band) - 1, 0)
        taus = jax.ShapeDtypeStruct((npan, band), dtype)
        fn = _build_dist_bt_r2b(dist, dist, grid.mesh, band, la=True)
        jax.eval_shape(fn, sds, taus, sds)

    def trace_bt_b2t():
        from dlaf_tpu.eigensolver.back_transform import _build_dist_bt_b2t

        band = spec.get("band", nb)
        n_sweeps = max(n - 2, 0)
        n_steps = -(-max(n - 1, 1) // band)
        fn = jax.jit(_build_dist_bt_b2t(dist, grid.mesh, b=band,
                                        cplx=False, n_sweeps=n_sweeps))
        jax.eval_shape(fn,
                       jax.ShapeDtypeStruct((n_sweeps, n_steps, band),
                                            dtype),
                       jax.ShapeDtypeStruct((n_sweeps, n_steps), dtype),
                       jax.ShapeDtypeStruct((n,), dtype), sds)

    # UNROLLED builders: their per-k emission makes the trace-time byte
    # counters the exact minimal per-run traffic; the scan builders count
    # per executed step as well, but of padded telescope windows.
    # (bt_b2t's layout all_to_alls sit OUTSIDE its sweep scan — exactly
    # two collectives per run — so its trace is exact too.)
    if family in ("cholesky",):
        from dlaf_tpu.algorithms.cholesky import _build_dist_cholesky

        fn = _build_dist_cholesky(dist, grid.mesh, "L", False, True)
        jax.eval_shape(fn, sds)
    elif family in ("trsm", "hegst"):
        from dlaf_tpu.algorithms.triangular import _build_dist_solve

        alpha = jax.ShapeDtypeStruct((), dtype)
        combos = ([("L", "L", "N")] if family == "trsm"
                  # twosolve HEGST = two whole-matrix solves
                  else [("L", "L", "N"), ("R", "L", "C")])
        for side, uplo, op in combos:
            fn = _build_dist_solve(dist, dist, grid.mesh, side, uplo,
                                   op, "N", dtype.name)
            jax.eval_shape(fn, sds, sds, alpha)
    elif family == "bt_r2b":
        trace_bt_r2b()
    elif family == "bt_b2t":
        trace_bt_b2t()
    elif family == "eigensolver":
        # the full pipeline's traced ICI traffic = red2band + both
        # back-transform stages (the counters accumulate across the three
        # traces); the host tridiag control stages move no ICI payload
        # and the sharded merge gemms communicate through GSPMD, which
        # the cc-layer counters do not see — noted in the #5 row
        trace_red2band()
        trace_bt_r2b()
        trace_bt_b2t()
    else:   # red2band
        trace_red2band()

    per_axis = {"row": 0.0, "col": 0.0}
    for m in obs.registry().snapshot():
        if m["name"] == "dlaf_comm_collective_bytes_total":
            axis = m["labels"].get("axis")
            if axis in per_axis:
                per_axis[axis] += m["value"]
    print(json.dumps(per_axis))


def ici_ceiling(family: str, n: int, nb: int, grid: str, chip: str):
    """Traced comm-bound ceiling in GF/s for a multi-chip config, or None
    (1x1 grids, the tridiag stage — its sharded merge gemms communicate
    through GSPMD collectives the cc-layer trace counters do not see —
    or the trace child failed)."""
    rows, cols = (int(x) for x in grid.split("x"))
    if rows * cols <= 1 or family == "tridiag":
        return None
    sys.path.insert(0, REPO)
    from dlaf_tpu.tpu_info import cpu_subprocess_env

    env = cpu_subprocess_env(n_virtual_devices=rows * cols)
    env["DLAF_METRICS_PATH"] = os.devnull   # arm the trace-time counters
    env.pop("DLAF_LOG", None)
    spec = dict(family=family, n=n, nb=nb, rows=rows, cols=cols,
                dtype="complex128" if family == "hegst" else "float64")
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--trace-ici",
             json.dumps(spec)],
            env=env, capture_output=True, text=True, timeout=2400,
            cwd=REPO, check=True)
        per_axis = json.loads(out.stdout.strip().splitlines()[-1])
    except (subprocess.SubprocessError, ValueError, OSError) as e:
        print(f"ici trace failed for {family} {n}/{nb} {grid}: {e}",
              file=sys.stderr)
        return None
    bw = ICI_LINK_BW[chip]
    t = 0.0
    for axis, p in (("row", rows), ("col", cols)):
        if p > 1 and per_axis.get(axis):
            t += 2.0 * (p - 1) / p * per_axis[axis] / bw
    if t == 0.0:
        return None
    return _FLOPS_MODEL[family](n) / t / 1e9


#: devtrace fixture for the measured-MFU column (``--measured``): a
#: distilled Chrome trace + merged JSONL, committed so the replay needs
#: no hardware and no live run (docs/observability.md device-time
#: attribution).
FIXTURE_DIR = os.path.join(REPO, "tests", "fixtures", "devtrace")

#: critpath fixture for the measured-bound column (``--measured``): the
#: ISSUE-16 per-step schedule join, committed with its schedule-bearing
#: merged artifact (docs/observability.md critical-path attribution).
CRITPATH_FIXTURE_DIR = os.path.join(REPO, "tests", "fixtures", "critpath")

#: critpath program (step-scope algo tag) -> table family.
ALGO_FAMILIES = {
    "cholesky": "cholesky", "trsm": "trsm", "hegst": "hegst",
    "red2band": "red2band", "bt_r2b": "bt_r2b",
}

#: entry-span phase name -> table family (the devtrace phase join keys
#: measured device GF/s by span name; the table rows key by family).
ENTRY_PHASE_FAMILIES = {
    "cholesky": "cholesky", "triangular_solve": "trsm",
    "gen_to_std": "hegst", "reduction_to_band": "red2band",
    "tridiag_solver": "tridiag", "bt_band_to_tridiag": "bt_b2t",
    "bt_reduction_to_band": "bt_r2b", "eigensolver": "eigensolver",
    "gen_eigensolver": "eigensolver",
}


def measured_device(fixture_dir: str = FIXTURE_DIR):
    """{family: "GF/s (platform n/nb grid)"} from the committed devtrace
    fixture — the device-busy-denominated measured numbers, labeled with
    where they ran so a CPU-container fixture can never masquerade as a
    TPU datum. Empty dict when the fixture is absent/unreadable (the
    column prints em-dashes)."""
    sys.path.insert(0, REPO)
    from dlaf_tpu.obs import devtrace
    from dlaf_tpu.obs.aggregate import merge_artifacts

    import glob as _glob

    trace = os.path.join(fixture_dir, "trace.json.gz")
    jsonls = sorted(_glob.glob(os.path.join(fixture_dir, "*.jsonl")))
    if not os.path.exists(trace) or not jsonls:
        return {}
    try:
        records = merge_artifacts(jsonls)
        report = devtrace.attribute(devtrace.load_trace(trace), records)
    except (OSError, ValueError) as e:
        print(f"mfu_table: devtrace fixture unreadable: {e}",
              file=sys.stderr)
        return {}
    platform = "cpu"
    for r in records:
        if r.get("type") == "accuracy" and r.get("platform"):
            platform = r["platform"]
            break
    attrs_by_name = {}
    for r in records:
        if r.get("type") == "span" and r.get("name"):
            attrs_by_name.setdefault(r["name"], r.get("attrs") or {})
    out = {}
    for phase, cell in report["phases"].items():
        family = ENTRY_PHASE_FAMILIES.get(phase)
        if family is None or "measured_gflops" not in cell:
            continue
        a = attrs_by_name.get(phase, {})
        label = (f"{cell['measured_gflops']:.2f} ({platform} "
                 f"{a.get('n', '?')}/{a.get('nb', '?')} "
                 f"{a.get('grid', '1x1')})")
        out[family] = label
    return out


def measured_bound(fixture_dir: str = CRITPATH_FIXTURE_DIR):
    """{family: "bound (platform n/nb grid)"} from the committed critpath
    fixture — the per-step critical-path classification's dominant bound
    (panel/bulk/comm/copy/gap), MEASURED from the schedule join instead of
    modeled from the panel-chain latency. Labeled with platform/shape like
    the measured(dev) column, for the same reason: a CPU-container
    fixture's bound (spin-wait collectives classify as comm) must never
    masquerade as a TPU datum. Empty dict when the fixture is
    absent/unreadable (the column prints em-dashes)."""
    sys.path.insert(0, REPO)
    from dlaf_tpu.obs import critpath, devtrace
    from dlaf_tpu.obs.aggregate import merge_artifacts

    import glob as _glob

    trace = os.path.join(fixture_dir, "trace.json.gz")
    jsonls = sorted(_glob.glob(os.path.join(fixture_dir, "*.jsonl")))
    if not os.path.exists(trace) or not jsonls:
        return {}
    try:
        records = merge_artifacts(jsonls)
        report = critpath.attribute(devtrace.load_trace(trace), records)
    except (OSError, ValueError) as e:
        print(f"mfu_table: critpath fixture unreadable: {e}",
              file=sys.stderr)
        return {}
    platform = "cpu"
    for r in records:
        if r.get("type") == "accuracy" and r.get("platform"):
            platform = r["platform"]
            break
    attrs_by_name = {}
    for r in records:
        if r.get("type") == "span" and r.get("name"):
            attrs_by_name.setdefault(r["name"], r.get("attrs") or {})
    out = {}
    for algo, prog in report["programs"].items():
        family = ALGO_FAMILIES.get(algo)
        if family is None or not prog.get("bound"):
            continue
        a = attrs_by_name.get(algo, {})
        out[family] = (f"{prog['bound']} ({platform} "
                       f"{a.get('n', '?')}/{a.get('nb', '?')} "
                       f"{a.get('grid', '1x1')})")
    return out


#: measured-entry classifier: history `variant` labels per workload family
_FAMILIES = {
    "cholesky": ("chol_", "ozaki", "scan", "xla", "loop", "biggemm",
                 "invgemm"),
    "trsm": ("trsm_",),
    "hegst": ("hegst_",),
    "red2band": ("red2band_",),
    "tridiag": ("tridiag",),       # bench.py dc arms: tridiag, tridiag+dcb1
    "bt_r2b": ("btr2b",),          # bench.py bt arms: btr2b, btr2b+btla1
    "bt_b2t": ("btb2t",),
    "eigensolver": ("eig_", "eigensolver"),
}


def measured(family: str, n: int, nb: int, path: str = HISTORY):
    """Best post-peel-fix TPU f64 GF/s for (family, n, nb), or None."""
    prefixes = _FAMILIES[family]
    best = None
    try:
        with open(path) as f:
            for raw in f:
                try:
                    r = json.loads(raw)
                except ValueError:
                    continue
                v = str(r.get("variant", ""))
                if not (r.get("platform") == "tpu"
                        and r.get("dtype") == "float64"
                        and r.get("n") == n and r.get("nb") == nb
                        and str(r.get("ts", "")) >= PEEL_FIX_TS
                        and isinstance(r.get("gflops"), (int, float))
                        and any(v.startswith(p) or v == p.rstrip("_")
                                for p in prefixes)):
                    continue
                if best is None or r["gflops"] > best:
                    best = r["gflops"]
    except OSError:
        return None
    return best


#: BASELINE configs + the measured single-chip config-#1 ladder. Fields:
#: (label, family, n, nb, grid, chip, note). ``n_meas``/``nb_meas``
#: override where the recorded number ran a rehearsal config.
CONFIGS = [
    ("#1 cholesky d 4096/256 1x1", "cholesky", 4096, 256, "1x1", "v5e", ""),
    ("#1 fused-step ceil 4096/256 1x1", "cholesky", 4096, 256, "1x1",
     "v5e", "panel ceiling at the fused STEP route's one-dispatch/step "
     "model (step_impl=fused, docs/pallas_panel.md) — the `fstep` bench "
     "arm + critpath fixture pair measure what this models"),
    ("#1 ladder 8192/256 1x1", "cholesky", 8192, 256, "1x1", "v5e", ""),
    ("#1 ladder 12288/256 1x1", "cholesky", 12288, 256, "1x1", "v5e", ""),
    ("#1 ladder 16384/256 1x1", "cholesky", 16384, 256, "1x1", "v5e", ""),
    ("#2 trsm d 8192/256 2x2", "trsm", 8192, 256, "2x2", "v5e",
     "single-chip local rehearsal (2x2 ICI unexposed); pre-peel-fix "
     "sessions recorded 128-131 GF/s — re-measure post-fix"),
    ("#3 hegst z 8192/256 2x2", "hegst", 8192, 256, "2x2", "v5e",
     "d-dtype twosolve rehearsal (z is CPU-mesh-verified only)"),
    ("#4 red2band d 16384/512 4x4", "red2band", 16384, 512, "4x4", "v5e",
     "measured at 8192/512 single-chip; 16384 is multi-chip-only"),
    ("#5 gen_eigensolver d 32768/512 8x8", "eigensolver", 32768, 512,
     "8x8", "v5e", "standard-EVP 10n^3/3 model; ICI = traced red2band + "
     "both bt stages (tridiag GSPMD merge collectives + gen stages "
     "excluded); per-stage rows below"),
    # -- eigensolver-pipeline stage rows (configs #4-#5's trailing
    # stages; real flop/roofline models, not red2band proxies) ------------
    ("#5 stage tridiag d 32768/512", "tridiag", 32768, 512, "8x8", "v5e",
     "D&C merge gemms (4n^3/3 model ceiling — deflation reduces it); "
     "dc_level_batch batches each level's merges into one dispatch; "
     "sharded merges ride GSPMD, so no cc-traced ICI row"),
    ("#5 stage bt_band_to_tridiag d 32768/512", "bt_b2t", 32768, 512,
     "8x8", "v5e", "chase back-transform (2n^3): two layout all_to_alls "
     "around a local sweep scan — traced exactly"),
    ("#5 stage bt_reduction_to_band d 32768/512", "bt_r2b", 32768, 512,
     "8x8", "v5e", "reflector-block application (2n^3); bt_lookahead "
     "hoists each panel's gather ahead of the previous bulk "
     "(docs/eigensolver_perf.md)"),
]

#: where the recorded datum ran a different (n, nb) than the config asks
_MEAS_AT = {"#4 red2band d 16384/512 4x4": (8192, 512)}

#: rows whose panel-critical-path ceiling uses a different modeled
#: per-step latency than the product default (the fused-step ceiling row)
_STEP_S = {"#1 fused-step ceil 4096/256 1x1": FUSED_STEP_S}


def build_rows(with_ici=True, dev=None, mb=None):
    rows = []
    dev = dev or {}
    mb = mb or {}
    for label, family, n, nb, grid, chip, note in CONFIGS:
        comp = oz_compute_ceiling(chip)
        hbm = (chol_hbm_ceiling(chip, n, nb)
               if family in ("cholesky", "trsm", "hegst") else None)
        if with_ici:
            ici = ici_ceiling(family, n, nb, grid, chip)
        else:
            ici = None
        panel = panel_ceiling(family, n, nb,
                              step_s=_STEP_S.get(label, PANEL_STEP_S))
        candidates = [comp] + [x for x in (hbm, ici, panel)
                               if x is not None]
        ceil = min(candidates)
        bound = ("panel" if panel is not None and ceil == panel
                 else "ici" if ici is not None and ceil == ici
                 else "hbm" if hbm is not None and ceil == hbm else "mxu")
        n_m, nb_m = _MEAS_AT.get(label, (n, nb))
        got = measured(family, n_m, nb_m)
        mfu = f"{100.0 * got / ceil:.1f}%" if got else "—"
        rows.append((label, f"ozaki s={OZ_SLICES} (bf16 dots)",
                     f"{comp:.0f}", f"{hbm:.0f}" if hbm else "—",
                     f"{ici:.0f}" if ici else "—", bound,
                     f"{got:.1f}" if got else "pending",
                     dev.get(family, "—"), mb.get(family, "—"),
                     mfu, note))
    return rows


def render(with_ici=True, dev=None, mb=None) -> str:
    head = (f"{BEGIN}\n"
            "## MFU / roofline table (scripts/mfu_table.py)\n\n"
            "Route ceilings per chip (f64-equivalent): ozaki compute = "
            f"dot-route peak / {OZ_PAIRS} slice pairs (s={OZ_SLICES}); "
            "HBM roofline from the slice-traffic model in the script "
            "docstring; ICI roofline (multi-chip rows) from the TRACED "
            "per-axis `dlaf_comm_collective_bytes_total` counters over "
            "per-link ICI bandwidth (ring traffic factor; script "
            "docstring) — the ceiling the `comm_lookahead` overlap "
            "(docs/comm_overlap.md) cannot exceed even with perfect "
            "compute/comm overlap. `MFU` = measured / min(compute, HBM, "
            "ICI). Measured values: best post-peel-fix TPU f64 entries "
            "in `.bench_history.jsonl` (v5e, one chip). Single-digit MFU "
            "with no roofline binding = the step chain is "
            "latency/serialization-bound — the gap `cholesky_lookahead` "
            "(docs/lookahead.md) + `comm_lookahead` exist to close; the "
            "N-ladder's rising MFU is that serial fraction amortizing. "
            "The #5 ICI bound sums the traced red2band + back-transform "
            "stage traffic; the `#5 stage` rows carry each trailing "
            "stage's own flop model and roofline (`dc_level_batch` / "
            "`bt_lookahead`, docs/eigensolver_perf.md), so config #5 "
            "reads per stage instead of through a red2band proxy. "
            "The panel-critical-path ceiling (step-chain families: flops "
            "/ (steps x modeled per-step panel-chain latency, "
            f"{PANEL_STEP_S * 1e3:.1f} ms from the 2026-08-01 probes)) "
            "stays folded into the ceiling min — `ceil bound = panel` "
            "still names it as the binding side, where the fused Pallas "
            "panel kernels (`panel_impl`, docs/pallas_panel.md) are the "
            "lever; the `#1 fused-step ceil` row re-prices that ceiling "
            "at the fused STEP route's one-dispatch-per-step model "
            f"({FUSED_STEP_S * 1e3:.2f} ms, `step_impl=fused` — the "
            "panel/strip/slab never round-trip HBM within a step), the "
            "headroom the `fstep` bench arm exists to claim — but its "
            "displayed column is replaced by `measured "
            "bound`: the ISSUE-16 per-step critical-path classification "
            "(`dlaf_tpu.obs.critpath`, docs/observability.md), the "
            "dominant per-step bound (panel/bulk/comm/copy/gap) measured "
            "from the schedule join over the committed "
            "`tests/fixtures/critpath/` fixture rather than modeled. "
            "Like `measured(dev)` it is labeled with the platform/shape "
            "it ran (the CI fixture is a CPU-container 2x2 run whose "
            "spin-wait collectives classify as comm-bound, and it "
            "carries the fixture's documented 2 ms synthetic step gap; "
            "a TPU-captured fixture drops in unchanged). "
            "`measured(dev)` is the ISSUE-14 device-timeline path "
            "(`dlaf_tpu.obs.devtrace` + `--measured`): entry-span flop "
            "models over the phase's attributed DEVICE-busy wall from a "
            "profiler trace — measured time, not a model — replayed "
            "hermetically from the committed "
            "`tests/fixtures/devtrace/` fixture and labeled with the "
            "platform/shape it ran (the CI fixture is a CPU-container "
            "2x2 run: its GF/s validate the measurement path, not the "
            "TPU ceilings; a TPU-captured fixture drops in unchanged — "
            "docs/observability.md device-time attribution).\n\n"
            "| config | route | compute ceil GF/s | HBM ceil GF/s "
            "| ICI ceil GF/s | ceil bound | measured GF/s "
            "| measured(dev) GF/s | measured bound | MFU | note |\n"
            "|---|---|---|---|---|---|---|---|---|---|---|\n")
    body = "".join("| " + " | ".join(r) + " |\n"
                   for r in build_rows(with_ici, dev, mb))
    return head + body + END


def main() -> None:
    if "--trace-ici" in sys.argv:
        _trace_ici_child(json.loads(sys.argv[sys.argv.index("--trace-ici")
                                             + 1]))
        return
    fixture = FIXTURE_DIR
    if "--fixture" in sys.argv:
        i = sys.argv.index("--fixture") + 1
        if i >= len(sys.argv):
            raise SystemExit("mfu_table: --fixture needs a directory")
        fixture = sys.argv[i]
    cp_fixture = CRITPATH_FIXTURE_DIR
    if "--critpath-fixture" in sys.argv:
        i = sys.argv.index("--critpath-fixture") + 1
        if i >= len(sys.argv):
            raise SystemExit("mfu_table: --critpath-fixture needs a "
                             "directory")
        cp_fixture = sys.argv[i]
    dev = mb = None
    if "--measured" in sys.argv:
        dev = measured_device(fixture)
        mb = measured_bound(cp_fixture)
    print(render(with_ici="--no-ici" not in sys.argv, dev=dev, mb=mb))


if __name__ == "__main__":
    main()
