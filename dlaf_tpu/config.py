"""Layered runtime configuration: defaults < user struct < env < CLI.

TPU-native counterpart of the reference's ``configuration`` struct
(``init.h:28-34``) and its layering logic (``src/init.cpp:117-177``): every
field has a built-in default, can be overridden by a user-supplied
``Configuration``, then by a ``DLAF_<NAME>`` environment variable, then by a
``--dlaf:<name>=<value>`` command-line option. ``dlaf:print-config`` mirrors
``--dlaf:print-config`` (``src/init.cpp:190-194``).

The reference's fields are CUDA-stream/umpire-pool counts; the TPU runtime has
no user-managed streams or pools (PJRT owns both), so the fields here are the
knobs this framework actually honors.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence


@dataclasses.dataclass
class Configuration:
    """Runtime knobs (analog of reference ``init.h:28-34``)."""

    #: Print the final configuration at initialize() (``--dlaf:print-config``).
    print_config: bool = False
    #: Rank ordering when building a grid from a flat device list
    #: ("row-major" | "col-major"), reference CommunicatorGrid ctor option.
    grid_ordering: str = "row-major"
    #: Implementation of the band->tridiag bulge chasing stage:
    #: "native" (C++ via ctypes) with automatic fallback to "numpy".
    band_to_tridiag_impl: str = "native"
    #: Worker threads for the native chase's pipelined sweeps (the
    #: reference's SweepWorker pipeline, band_to_tridiag/mc.h:362-380):
    #: 0 = auto (CPU count), 1 = sequential. Any count gives bitwise
    #: identical results (pipelined windows are disjoint).
    chase_threads: int = 0
    #: Host secular-equation solver in the D&C merge: "native" (C++
    #: safeguarded Newton, the laed4 analog) with fallback to "numpy"
    #: (vectorized bisection).
    secular_impl: str = "native"
    #: Deflated-merge size above which the D&C secular solve + z-refinement
    #: run on the device (see eigensolver/tridiag_solver.py; the threshold
    #: drops automatically when the native host solver failed to build).
    #: 0 = auto (default): 4096 on TPU (device = MXU-backed batched math),
    #: device-disabled on CPU — a 2026-08 CPU sweep (n=16384 at
    #: thr 2048/4096/8192/host-only -> 218/135/81/66 s, identical
    #: residuals) shows the CPU backend's "device" route loses to the
    #: native host solver at every size. The reference's
    #: look-ahead/round-robin workspace knobs
    #: (``factorization/cholesky/impl.h:187-189``) have no analog here:
    #: XLA sees the whole step DAG at compile time and owns the overlap.
    secular_device_min_k: int = 0
    #: Local Cholesky trailing-update strategy: "loop" (exact-flop per-column
    #: herk/gemm, the reference's task shape), "biggemm" (ONE masked full
    #: trailing gemm per step — 2x flops on the strict triangle but a single
    #: large MXU op), "invgemm" (biggemm + panel formed by gemm against the
    #: explicit inverse of the diagonal factor instead of a triangular
    #: solve), or "xla" (delegate the whole local factorization to XLA's
    #: fused native cholesky), or "scan" (lax.scan'd uniform step: one
    #: compiled step body looped nt times — O(1) compile time and carry
    #: buffer reuse at ~3x the exact trailing flops; the compile/HBM
    #: escape hatch at large tile counts, algorithms/cholesky.py). Also
    #: "ozaki" (error-free int8-slice trailing on the MXU) and "auto"
    #: (default): ozaki on TPU (the route of the benchmark's
    #: ``chol_d_n4096_1x1`` cell: ``call_s`` 0.0464 s, PERF_LEDGER.jsonl
    #: PR 28; the other forms are not measured on the chip through
    #: benchmark/run.py) and loop elsewhere.
    cholesky_trailing: str = "auto"
    #: Look-ahead (software-pipelined) step formulation for the blocked
    #: Cholesky (and the analogous panel-chain splits in the triangular
    #: scan solve and blocked HEGST): "0" = the plain right-looking step
    #: order, "1" = split every trailing update into "next panel column
    #: first" + "rest of trailing" so panel k+1's potrf/trsm chain
    #: consumes the carried next-column values directly and the bulk
    #: herk/gemm of step k runs concurrently with it (the reference's
    #: high-priority first-column herk + round-robin panel workspaces,
    #: ``factorization/cholesky/impl.h:147-156,187-189``, expressed as
    #: program structure for XLA's scheduler: unrolled forms carry the
    #: next column between steps, scan forms defer the bulk update one
    #: iteration so it overlaps the next latency-bound panel chain).
    #: "auto" (default): 1 on TPU — per-step critical-path latency, not
    #: flops, dominates blocked factorizations there (N=4096 at 133 GF/s
    #: vs N=16384 at 514 is the latency-bound-panel signature) — and 0
    #: elsewhere. Results are bitwise-identical either way on the native
    #: routes (same tile ops, same per-cell application order; enforced
    #: by tests/test_cholesky.py lookahead A/Bs). See docs/lookahead.md.
    cholesky_lookahead: str = "auto"
    #: Communication look-ahead for the distributed builders
    #: (docs/comm_overlap.md): "1" extends the ``cholesky_lookahead``
    #: pipeline across the COLLECTIVES — step k+1's panel broadcast /
    #: all-gather (and the fused diag ``bcast2d``) are emitted BEFORE
    #: step k's bulk trailing product, so XLA's async collective
    #: start/done pairs can run the ICI transfer while the MXU grinds
    #: the bulk gemms (the reference hides the same transfer behind the
    #: trailing update via sender pipelines, ``broadcast_panel.h`` +
    #: ``impl.h:147-156``; arXiv:2112.09017 measures this overlap as the
    #: difference between latency-bound and MXU-bound distributed
    #: factorizations on TPU pods). "0" keeps the plain per-step
    #: emission order. "auto" (default): 1 on TPU, 0 elsewhere. In the
    #: unrolled builders the hoist rides the PR-2 SSA carry, so it only
    #: takes effect when ``cholesky_lookahead`` also resolves 1 (the
    #: scan builders' deferred-bulk bodies already emit their
    #: collectives ahead of the deferred product — there the knob labels
    #: the structure rather than changing it); the distributed
    #: reduction_to_band builder pipelines its panel all-gather under
    #: this knob alone. Results are bitwise-identical either way on the
    #: native routes (same collectives, same payloads, same per-cell
    #: application order; pinned by the comm A/Bs in tests/).
    comm_lookahead: str = "auto"
    #: Level-batched divide-and-conquer merge execution in the tridiagonal
    #: eigensolver (eigensolver/tridiag_solver.py, docs/eigensolver_perf.md):
    #: every merge within one D&C tree level is independent, and "1" runs
    #: all same-shape merges of a level as ONE vmapped device dispatch
    #: (secular solve, qc assembly, Q·C apply) with small merges padded to
    #: the group's max deflated-size bucket — the batch-many-small-problems
    #: idiom arXiv:2112.09017 credits TPU MXU utilization to — while the
    #: host control scan of the next group overlaps the dispatched device
    #: work. "0" walks the tree one merge at a time (the recursive
    #: reference order, ``merge.h:790-887``). "auto" (default): 1 on TPU
    #: (the serialized walk is dispatch-bound there: every small merge
    #: pays a full host->device round trip), 0 elsewhere. Results match
    #: the serialized walk bitwise on the host-secular route; the
    #: device-secular route re-buckets to the group's max k, whose padded
    #: zero terms may reassociate at <= 1 ulp (docs/eigensolver_perf.md
    #: exception table). Counted per level in
    #: ``dlaf_dc_merges_total{mode=batched|serialized}``.
    dc_level_batch: str = "auto"
    #: Look-ahead for the reflector-block back-transform
    #: (bt_reduction_to_band, local + distributed): "1" emits reflector
    #: block k+1's larft/T-factor chain — and, distributed, its panel
    #: gather collectives — BEFORE block k's bulk trmm+gemm application,
    #: so the latency-bound T factor and the ICI transfer hide under the
    #: MXU bulk exactly like ``cholesky_lookahead``/``comm_lookahead`` do
    #: for the factorizations (docs/lookahead.md, docs/comm_overlap.md).
    #: "0" keeps the plain per-block emission order. "auto" (default): 1
    #: on TPU, 0 elsewhere. Bitwise identical either way (the T chain
    #: reads only the constant reflector storage — a pure emission
    #: reorder); hoisted collectives count under
    #: ``dlaf_comm_overlapped_total{algo="bt_r2b_dist"}``. The scan-form
    #: distributed builder already emits its panel gather ahead of the
    #: bulk by construction; there the knob only labels the structure.
    bt_lookahead: str = "auto"
    #: bt_band_to_tridiag reflector application: "blocked" (compact-WY
    #: staircase groups -> larft + two gemms per step level, the MXU form of
    #: the reference's b x b HH re-tiling) or "sweeps" (one batched rank-1
    #: segment update per sweep).
    bt_b2t_impl: str = "blocked"
    #: Sweeps per compact-WY group for bt_b2t_impl="blocked"; 0 = auto
    #: (band size on MXU hardware, min(band, 64) on CPU). Clamped to
    #: [1, min(band+1, n_sweeps)] — band+1 is the disjointness bound of the
    #: blocked level reordering.
    bt_b2t_group: int = 0
    #: Real-f64 level-3 contraction backend for the tile ops (gemm / herk /
    #: her2k / hemm / trmm): "native" (XLA's dot — on TPU, compiler-emulated
    #: double-double arithmetic) or "mxu" (error-free int8 slicing with exact
    #: int32 accumulation, tile_ops/ozaki.py), or "auto" (default): mxu on
    #: TPU, native elsewhere. The TPU resolution is measurement-backed
    #: (2026-08-01 v5e session): the mxu route ran 281-351 GF/s where the
    #: native emulation ran 47-49 (cholesky N=4096/8192), its int8 slice
    #: planes are 4x smaller than the emulation's f32 workspaces (the
    #: native route OOMed red2band n=16384 at 32 GB asked of 15.75), and
    #: scan-form algorithms pair pathologically with the native dot (XLA
    #: sinks the emulation's constant planes into the loop: red2band scan
    #: measured 1.86 GF/s native vs 48.9 unrolled). Triangular *solves*
    #: are unaffected (they are latency-, not throughput-bound; see
    #: ``f64_trsm`` for that side).
    f64_gemm: str = "auto"
    #: Smallest dimension for which f64_gemm="mxu" actually reroutes a
    #: contraction; below it the slicing overhead outweighs the MXU win and
    #: the native path is kept.
    f64_gemm_min_dim: int = 128
    #: int8 slices per operand on the MXU f64 path (tile_ops/ozaki.py):
    #: 8 (56 mantissa bits, 36 gemms per product) down to e.g. 7 (49 bits,
    #: 28 gemms). 0 = auto: 7 on backends whose f64 is the double-f32
    #: emulation (TPU — its ~47-48-bit arithmetic already bounds every
    #: combine/panel op, so the 49-bit dot sacrifices nothing and saves
    #: ~22% of the MXU work; measured 103.9 vs 95.5 GF/s on config #1,
    #: 2026-07-31 v5e session), 8 where f64 is native (f64-grade dots).
    f64_gemm_slices: int = 0
    #: Slice contraction route of the ozaki products: "int8" (s8 x s8 ->
    #: s32 dot), "bf16" (slices cast to bf16 — exact for 7-bit integers —
    #: contracted on the MXU's native bf16 path with f32 accumulation,
    #: integer-exact while k*2^12 <= 2^24, chunked beyond; bit-identical
    #: results), or "auto" (default): bf16 on TPU, int8 elsewhere. The
    #: 2026-08-01 dot_ab session settled the routes on silicon:
    #: bit-identical on device (0/65536 mismatches at k up to 4096) and
    #: at performance parity at the pipeline level (within 1% on full
    #: config #1 — the slice product is HBM-bound, so
    #: the raw s8-dot lowering deficit never binds); bf16 stays the TPU
    #: default as the hardware's first-class MXU path.
    ozaki_dot: str = "auto"
    #: Panel factorization kernels for the blocked algorithms' per-step
    #: potrf + panel-TRSM chain (tile_ops/pallas_panel.py,
    #: docs/pallas_panel.md): "xla" (the generic route — XLA's blocked
    #: Cholesky thunk chain for the diagonal tile, a separate
    #: TriangularSolve per panel strip), "fused" (single-``pallas_call``
    #: VMEM-resident kernels: micro-blocked right-looking potrf ladder +
    #: grid-batched strip solve with the factor's inverse in scratch —
    #: one kernel dispatch per panel step instead of a latency-bound
    #: thunk chain per tile), or "auto" (default): fused on TPU for
    #: f32/bf16 inputs, xla elsewhere (f64/c128 keep their own mixed/
    #: ozaki panel treatment — see ``f64_trsm``). An explicit "fused"
    #: with an unsupported dtype registers the degradation at
    #: ``dlaf_fallback_total{site="panel"}`` (DLAF_STRICT raises);
    #: off-TPU the fused kernels run in interpret mode (CI/parity).
    #: Results are ulp-close, not bitwise, across the two impls; all
    #: knob contracts (lookahead/comm_lookahead/with_info) stay bitwise
    #: WITHIN each impl (tests/test_pallas_panel.py).
    panel_impl: str = "auto"
    #: Fused STEP kernel route for the blocked Cholesky builders
    #: (tile_ops/pallas_panel.py ``fused_step``/``fused_factor_solve``,
    #: docs/pallas_panel.md "Fused step kernel"): "xla" (the panel chain
    #: stays composed ops — ``panel_impl`` decides potrf/solve
    #: individually), "fused" (ONE ``pallas_call`` per blocked step:
    #: potrf ladder + whole-strip solve — and, on the local unrolled
    #: builders, the adjacent trailing-update slab — with the factor,
    #: its triangular inverse, and the solved leading strip block all
    #: VMEM-resident between the ops; removes the per-step
    #: kernel-launch + HBM round-trip that the MFU table pins as the
    #: panel-bound floor, ROADMAP item 4), or "auto" (default): fused
    #: on TPU for f32/bf16 within the ``step_vmem_limit`` budget, xla
    #: elsewhere. Explicit "fused" with an unsupported dtype/block or a
    #: VMEM-budget overflow registers at
    #: ``dlaf_fallback_total{site="step"}`` (DLAF_STRICT raises);
    #: off-TPU explicit "fused" runs in interpret mode (CI/parity).
    #: Results are ulp-close, not bitwise, vs the composed chain; all
    #: knob contracts (lookahead/comm_lookahead/with_info) stay bitwise
    #: WITHIN the fused-step route (tests/test_fused_step.py).
    step_impl: str = "auto"
    #: VMEM budget (bytes) for the fused step kernel's modeled live set
    #: (``pallas_panel.step_vmem_bytes``): block sizes whose kernel
    #: would exceed it degrade to the composed-op step route (counted
    #: under explicit "fused", silent policy under "auto"). The default
    #: caps the kernel at 10 MiB, leaving ~6 MiB of a v5e core's
    #: ~16 MiB VMEM for the compiler's own buffers; the
    #: ``health.inject`` drills exercise the degrade path.
    step_vmem_limit: int = 10 * 2 ** 20
    #: Panel-level factor/solve ops (real f64): "native" (XLA — latency-bound
    #: under TPU f64 emulation), "mixed" (f32 seed + Newton refinement,
    #: tile_ops/mixed.py: refined explicit inverse + matmul for per-tile
    #: panel solves via tile_ops.blas.trsm_panel, and the distributed
    #: cholesky's per-step panel potrf/trsm; the matmul application follows
    #: f64_gemm, so with "mxu" it runs on the int8 path), or "auto"
    #: (default): mixed on TPU (panel-chain probes, 2026-08-01 v5e
    #: session: +0.6 ms/step over pure gemm vs +15.7 ms for native-f64
    #: panels), native elsewhere. Whole-matrix local solves stay native
    #: either way.
    f64_trsm: str = "auto"
    #: Per-k step formulation for the distributed algorithms (triangular
    #: solve/multiply, reduction_to_band + its back-transform, gen_to_std
    #: via its solves) AND the local reduction_to_band: "unrolled" (per-k
    #: steps traced out — exact shapes, compile time linear in the step
    #: count), "scan" (lax.scan'd uniform masked step — O(1) compile,
    #: ~2-3x masked-shape work; the compile-latency escape hatch at large
    #: tile counts, docs/DESIGN.md), or "auto" (default): pick per (step
    #: count, platform) via :func:`resolve_step_mode`. The LOCAL Cholesky
    #: asks the same resolver while cholesky_trailing is "auto"
    #: (algorithms/cholesky.py:local_step_form); the distributed Cholesky
    #: selects its scan form via cholesky_trailing="scan".
    dist_step_mode: str = "auto"
    #: HEGST (gen_to_std) formulation: "blocked" (per-k two-sided update —
    #: hegst diag, panel trsm/hemm, her2k trailing, deferred trailing
    #: solve — ~n^3 flops, the reference's flop discipline,
    #: ``eigensolver/gen_to_std/impl.h:200-740``), "twosolve" (two
    #: whole-matrix triangular solves: ~2x the flops as two dense
    #: MXU-shaped sweeps with no panel round-trips; also the
    #: scan-compatible compile-latency hatch — both blocked forms are
    #: unrolled-only, so when dist_step_mode resolves to "scan" HEGST
    #: routes through "twosolve" regardless), or "auto" (default):
    #: twosolve on TPU, blocked elsewhere. One v5e chip, 2026-08-02
    #: (d/8192/256): twosolve
    #: 385.3 GF/s at 5.2e-11 residual vs blocked 298.4 at 2.2e-9 — the
    #: dense sweeps beat the latency-bound panel round-trips on wall
    #: clock (same reference flop model for both labels) AND on
    #: accuracy; off-TPU the ~n^3 blocked discipline wins as before.
    hegst_impl: str = "auto"
    #: Broadcast realization in comm.collectives.bcast: "psum"
    #: (mask-then-all-reduce — ~2V(p-1)/p per link, the bandwidth shape
    #: for panel payloads) or "tree" (binomial ppermute doubling —
    #: ceil(log2 p) hop latencies, the candidate for small diagonal-tile
    #: payloads). First multi-chip ICI access must A/B these.
    bcast_impl: str = "psum"
    #: Panel Householder-QR factorization route (reduction_to_band's
    #: reflector panels — the sole geqrf consumer; the QR T-factor
    #: algorithm takes precomputed reflectors): "geqrf" (the XLA
    #: primitive — LAPACK on CPU, an XLA-internal expansion on TPU),
    #: "householder" (tile_ops/qr_panel.py: the same column-Householder
    #: algorithm in plain jnp ops), or "auto" (default): householder on
    #: TPU, geqrf elsewhere. History: built as the accuracy suspect for
    #: the session-4d red2band ~1e-5 check failures; the silicon probes
    #: EXONERATED geqrf (backward error ~2e-14 at every panel shape —
    #: the real culprit was the ozaki peel's emulated round,
    #: tile_ops/ozaki.py _peel_slices). The TPU auto choice stands on
    #: PERFORMANCE: red2band 4096/512/band128 scan measured 74.9 GF/s
    #: under householder vs 49.3 under the geqrf expansion (+52%, equal
    #: 7e-14-grade residuals, post-peel-fix, 2026-08-02 v5e) — the
    #: fori_loop sweep beats XLA's expansion on this hardware; off-TPU
    #: geqrf is LAPACK and stays.
    qr_panel: str = "auto"
    #: Column-chunk width for LARGE local triangular solves (elements of
    #: the rhs free axis; rhs columns — rows for side='R' — are
    #: mathematically independent, so the solve maps bitwise-identically
    #: over free-axis chunks). 0 disables; -1 (default) = auto: on TPU,
    #: chunk at 4096 when both solve dimensions are >= 8192 and the mxu
    #: route is active — the whole-matrix emulated-f64 solves (HEGST
    #: twosolve, eigensolver back-substitution) otherwise materialize
    #: their int8/bf16 operand slices, int32 partials, and f64 products
    #: at the FULL rhs width simultaneously, the measured single-chip
    #: OOM at n=16384 (session 4g: HEGST d/16384 RESOURCE_EXHAUSTED with
    #: donation already applied). lax.map over chunks bounds that live
    #: set to one chunk's worth; off-TPU the native solves have no such
    #: workspaces and chunking only costs fusion.
    trsm_rhs_chunk: int = -1
    #: Row-chunk width for the LOCAL reduction-to-band trailing update
    #: (rows of the trailing block; W = A(VT) and the rank-2 update
    #: A -= XV^H + VX^H are row-independent in A, so both map over row
    #: chunks with the chunked gemms bitwise-identical; whole-step
    #: results match to ~1 ulp — XLA re-fuses the small interleaved
    #: panel matmuls, reassociating their reductions). 0 disables; -1
    #: (default) =
    #: auto: on TPU, chunk at 4096 when the trailing dimension is
    #: >= 8192 and the mxu route is active — the trailing gemms
    #: otherwise materialize the emulated-f64 operand slice planes and
    #: per-group product partials at the FULL trailing size (the
    #: measured 19.28 GB compile ask of red2band n=16384/band=128 on
    #: the 15.75 GB chip, session 4f). Chunk widths are clamped so the
    #: per-gemm route gate (f64_gemm_min_dim over ALL gemm dims) cannot
    #: flip; off-TPU the native gemms have no slice workspaces and
    #: chunking only costs fusion.
    red2band_trail_chunk: int = -1
    #: Conditioning guard for the "mixed" fast path, as a limit on the
    #: squared diagonal ratio of the f32 seed factor (empirically
    #: residual ~ 3.5e-14 * estimate for one Newton step; blocks estimated
    #: worse take the native branch inside the compiled program).
    mixed_cond_limit: float = 100.0
    #: Half-precision seed kernel for the mixed panel path: "xla" (native
    #: loop-based cholesky + triangular solve) or "recursive" (trace-time
    #: recursive block decomposition producing factor AND inverse from
    #: gemms + small leaf kernels — trades program size for the XLA loop
    #: dispatch latency that dominates panel steps; tile_ops/mixed.py).
    mixed_seed: str = "xla"
    #: Leaf size of the recursive seed (power of two recommended).
    mixed_seed_base: int = 64
    #: Enable float64/complex128 support (sets jax_enable_x64).
    enable_x64: bool = True
    #: When non-empty, miniapps emit XLA/PJRT execution profiles
    #: (jax.profiler traces with named phases) into this directory
    #: (the green-field tracing hook SURVEY §5 calls for).
    profile_dir: str = ""
    #: Structured-log level for the dlaf_tpu.obs logger ("debug" | "info" |
    #: "warning" | "error" | "off"): the one-shot auto-knob resolution
    #: notices and all other library diagnostics route through it, so CI
    #: and pytest output can silence them with DLAF_LOG=off.
    log: str = "info"
    #: When non-empty, the observability layer (dlaf_tpu.obs) appends
    #: span records, metrics snapshots (collective byte counters, tile-op
    #: counts, span-duration histograms), and log events to this JSON-lines
    #: file; schema-checked by ``python -m dlaf_tpu.obs.validate``. Empty
    #: (default) keeps every instrumented call site a zero-allocation
    #: no-op.
    metrics_path: str = ""
    #: When non-empty, host spans start one jax.profiler trace into this
    #: directory (TraceAnnotation phase names on the profiler timeline;
    #: named_scope phase names in compiled-program op metadata). The
    #: pre-obs ``profile_dir`` knob keeps working; this is the obs-layer
    #: spelling, and the two may point at the same directory.
    trace_dir: str = ""
    #: Opt-in finite guard (``DLAF_CHECK`` / ``--dlaf:check``): robustness
    #: drivers (health.robust_cholesky; miniapp_cholesky wires the CLI
    #: flag) validate inputs and outputs for non-finite values, raising a
    #: structured health.CheckError instead of letting a NaN propagate
    #: silently. Off by default — the guard host-syncs by design.
    check: bool = False
    #: Strict degradation mode (``DLAF_STRICT``): a registered fallback
    #: (native secular/band-chase -> numpy, pallas -> XLA, ozaki -> plain
    #: dot; health.registry) RAISES health.DegradationError instead of
    #: silently taking the degraded path. The CI/bring-up stance where a
    #: missing native library must fail the job, not slow it 100x.
    strict: bool = False
    #: Accuracy telemetry (``DLAF_ACCURACY``, docs/accuracy.md): "1" arms
    #: the in-graph numerical-quality probes (dlaf_tpu.obs.accuracy) —
    #: miniapps and bench arms compute a stochastic Hutchinson residual
    #: estimate per timed run (O(n^2) device work, no full-matrix host
    #: fetch) and the D&C eigensolver records its per-level deflation
    #: fraction, each landing as an ``accuracy`` JSONL record (site,
    #: metric, value, bound_ratio = value/(c*n*eps_eff) with the
    #: platform-honest eps of miniapp/checks.effective_eps) plus a
    #: ``dlaf_accuracy_ratio{site,metric}`` gauge. "full" upgrades the
    #: probes to the exact tile-wise Frobenius residual (O(n^3) device
    #: work, still no host round trip). "0" (default) emits nothing and
    #: is a bitwise passthrough: factor outputs are identical with the
    #: knob on or off (the probes are separate programs over the outputs;
    #: pinned by tests/test_accuracy.py). ``--check-result`` always
    #: verifies regardless of the knob — the knob only picks the
    #: estimator mode ("0" checks with the "1" probe).
    accuracy: str = "0"
    #: Bucket ceilings of the serving layer (``DLAF_SERVE_BUCKETS``,
    #: docs/serving.md): a comma-separated ascending list of matrix sizes
    #: (e.g. "32,64,128") that :class:`dlaf_tpu.serve.Queue` rounds
    #: incoming request shapes up to — one compiled (and ideally warmed)
    #: batched program per ceiling. Empty (default) = power-of-two
    #: ceilings chosen per request (next power of two >= n, min 8); a
    #: request larger than the largest explicit ceiling also falls back
    #: to the next power of two, so no shape is ever rejected (it just
    #: pays a cold compile — the cache-miss signal the serve metrics
    #: surface).
    serve_buckets: str = ""
    #: Lanes per batched serve dispatch (``DLAF_SERVE_BATCH``): the
    #: bucket's vmapped program factors this many problems per dispatch;
    #: the queue dispatches early on deadline expiry with the missing
    #: lanes identity-padded (provably inert — docs/serving.md padding
    #: contract). 16 is the smallest batch for which the measured
    #: dispatch-overhead amortization clears the ISSUE-11 3x
    #: requests/s bar with margin on every platform.
    serve_batch: int = 16
    #: Queue deadline in milliseconds (``DLAF_SERVE_DEADLINE_MS``): a
    #: bucket with pending requests older than this dispatches at the
    #: next ``submit``/``poll`` even if not full. The queue never runs a
    #: background thread — expiry is evaluated against the injected
    #: clock at those calls, so dispatch composition is deterministic
    #: and testable (docs/serving.md deadline semantics).
    serve_deadline_ms: float = 50.0
    #: Admission bound of the serving queue (``DLAF_SERVE_MAX_DEPTH``,
    #: docs/serving.md overload protection): the maximum TOTAL number of
    #: pending (undispatched) requests across every bucket. At the bound
    #: the queue either sheds (``serve_shed``) or force-dispatches the
    #: fullest bucket — either way pending depth provably never exceeds
    #: this knob, so queue memory is bounded under overload. 0 (default)
    #: = unbounded (the pre-PR-12 behavior).
    serve_max_depth: int = 0
    #: Overload response at the ``serve_max_depth`` bound
    #: (``DLAF_SERVE_SHED``): True (default) fails the submit fast with a
    #: structured :class:`dlaf_tpu.health.errors.OverloadError` (shed
    #: counted per bucket under ``dlaf_serve_shed_total``); False applies
    #: backpressure instead — the fullest bucket is dispatched inline to
    #: make room, trading submit latency for zero sheds.
    serve_shed: bool = True
    #: Dispatch retry budget of the serving queue
    #: (``DLAF_SERVE_RETRY_ATTEMPTS``): each batch dispatch runs under a
    #: health.policy RetryPolicy with this many total attempts, so a
    #: transiently failing dispatch (the PR-12 motivation: it used to
    #: poison its tickets with no retry) re-runs before the tickets are
    #: poisoned. 1 = no retry.
    serve_retry_attempts: int = 3
    #: Base backoff between serve dispatch retry attempts, milliseconds
    #: (``DLAF_SERVE_RETRY_BACKOFF_MS``; exponential growth + the policy
    #: engine's deterministic seeded jitter). 0 (default) retries
    #: immediately — dispatch failures are dominated by deterministic
    #: causes (compile error, OOM) where waiting buys nothing; set it
    #: when fronting genuinely transient infrastructure.
    serve_retry_backoff_ms: float = 0.0
    #: Circuit-breaker opening threshold (``DLAF_CIRCUIT_THRESHOLD``,
    #: docs/robustness.md): consecutive failures at one site before the
    #: breaker opens (closed -> open) and calls fail fast with
    #: health.CircuitOpenError instead of re-running a failing dispatch/
    #: primary.
    circuit_threshold: int = 3
    #: Circuit-breaker cooldown, seconds (``DLAF_CIRCUIT_COOLDOWN_S``):
    #: how long an open breaker rejects calls before letting ONE half-open
    #: probe through (success closes it, failure re-opens).
    circuit_cooldown_s: float = 30.0
    #: Fleet size (``DLAF_FLEET_WORKERS``, docs/fleet.md): how many
    #: serve worker replicas the launch helpers / CI drills / bench
    #: fleet arm spawn behind one router. The router itself accepts any
    #: number of ``hello`` connections — this knob sizes the launchers,
    #: not the protocol.
    fleet_workers: int = 3
    #: Router heartbeat interval, milliseconds
    #: (``DLAF_FLEET_HEARTBEAT_MS``): how often the router pings each
    #: routable worker at its clock edges (docs/fleet.md liveness).
    fleet_heartbeat_ms: float = 1000.0
    #: Heartbeat silence budget, milliseconds
    #: (``DLAF_FLEET_HEARTBEAT_TIMEOUT_MS``): an ``up`` worker with no
    #: traffic for this long turns ``suspect`` at the next router clock
    #: edge — its breaker is forced open, its unacknowledged tickets
    #: re-dispatch to siblings, and re-admission follows the half-open
    #: probe discipline. Evaluated against the router's injectable
    #: clock, so timeout drills replay deterministically.
    fleet_heartbeat_timeout_ms: float = 5000.0
    #: Failover switch (``DLAF_FLEET_FAILOVER``): True (default)
    #: re-dispatches a dead worker's unacknowledged tickets to siblings
    #: (at-least-once, zero loss); False poisons them with structured
    #: WorkerLostError + ``ticket_lost`` fleet records — which
    #: ``--require-fleet`` REJECTS, so disabling failover is visible in
    #: CI, never silent (the must-trip drill leg).
    fleet_failover: bool = True
    #: Router ticket-dispatch retry budget
    #: (``DLAF_FLEET_RETRY_ATTEMPTS``): total attempts per dispatch
    #: under the shared policy engine, with worker re-selection each
    #: attempt. Must exceed ``circuit_threshold`` for a sustained
    #: per-worker fault to open that worker's breaker mid-policy and
    #: re-route the remaining attempts to a sibling (docs/fleet.md).
    fleet_retry_attempts: int = 5
    #: Base backoff between router dispatch retries, milliseconds
    #: (``DLAF_FLEET_RETRY_BACKOFF_MS``; exponential + deterministic
    #: seeded jitter). 0 (default) retries immediately — a fleet
    #: re-route targets a DIFFERENT worker, so waiting buys nothing.
    fleet_retry_backoff_ms: float = 0.0
    #: Stage-checkpoint directory for preemption-safe pipeline resume
    #: (``DLAF_RESUME_DIR``, docs/robustness.md §5): when non-empty, the
    #: eigensolver pipeline writes an atomic versioned checkpoint after
    #: each stage (red2band, b2t, tridiag, bt_b2t, bt_r2b) and
    #: ``eigensolver(..., resume=True)`` skips stages whose checkpoint
    #: manifest matches the run's config/grid/dtype fingerprint — a
    #: preempted multi-minute pipeline restarts from the last completed
    #: stage instead of from scratch, bitwise-identically per stage on
    #: the native routes. Empty (default) = no checkpointing.
    resume_dir: str = ""
    #: LRU byte budget of the serve program cache
    #: (``DLAF_SERVE_CACHE_BYTES``): compiled bucket programs are
    #: retained up to this many bytes (per-program cost =
    #: ``memory_analysis()`` peak where the backend reports one, an
    #: aval-derived estimate otherwise), evicting
    #: least-recently-dispatched unpinned programs first;
    #: ``serve.ProgramService.pin`` exempts a program from eviction.
    #: 0 (default) = unbounded.
    serve_cache_bytes: int = 0
    #: Live metrics/health endpoint port (``DLAF_METRICS_PORT``, ISSUE 13,
    #: docs/observability.md live operations): when > 0, dlaf_tpu.obs
    #: starts a stdlib-http daemon thread on 127.0.0.1 serving ``GET
    #: /metrics`` (Prometheus text exposition of the LIVE registry, with
    #: exemplar trace IDs on latency histogram buckets) and ``GET
    #: /healthz`` (JSON: serve-queue depth/shed/breaker states, worst
    #: live accuracy bound_ratio, rank/pid/uptime). Arming the port also
    #: turns the metrics registry on even without DLAF_METRICS_PATH
    #: (scrape-only deployments). 0 (default): zero threads, zero
    #: sockets.
    metrics_port: int = 0
    #: Rolling SLO latency objective, milliseconds (``DLAF_SLO_P99_MS``):
    #: every latency recorded through obs.observe_latency (the serve
    #: queue per request; health.policy.with_policy per successful call)
    #: that exceeds this objective increments the
    #: ``dlaf_slo_breach_total{op}`` burn counter. 0 (default) = no
    #: objective, nothing counted. The windowed
    #: ``dlaf_serve_latency_window{op,bucket,q}`` percentile gauges are
    #: maintained regardless.
    slo_p99_ms: float = 0.0
    #: Rolling SLO window length, seconds (``DLAF_SLO_WINDOW_S``): the
    #: span of the sliding-window quantile estimator behind the
    #: ``dlaf_serve_latency_window`` gauges — a ring of fixed-size epoch
    #: buckets (bounded memory, deterministic under an injected clock;
    #: dlaf_tpu.obs.metrics.SlidingWindow).
    slo_window_s: float = 60.0
    #: SLO breach-burst flight trigger threshold (``DLAF_SLO_BURST``,
    #: ISSUE 14): when at least this many ``dlaf_slo_breach_total``
    #: breaches land inside ONE rolling SLO window (``slo_window_s``,
    #: per op), the flight recorder dumps its ring with reason
    #: ``slo_breach_burst`` — once per recorder cooldown, so a sustained
    #: latency storm leaves ONE incident artifact holding the moments
    #: before the burst instead of a thousand re-dumps. Needs
    #: ``DLAF_FLIGHT_RECORDER`` armed (and ``DLAF_SLO_P99_MS`` set —
    #: no objective, no breaches). 0 disables the trigger.
    slo_burst: int = 5
    #: Flight-recorder ring depth (``DLAF_FLIGHT_RECORDER``): keep the
    #: last N JSONL records in memory (all types, pre-serialization) and
    #: dump them atomically to ``<metrics_path>.flight.jsonl`` on
    #: incident triggers — breaker open, overload shed, recovery
    #: exhaustion, accuracy budget breach, /healthz failure
    #: (dlaf_tpu.obs.flight; validated by ``python -m dlaf_tpu.obs.
    #: validate --require-flight``). Requires DLAF_METRICS_PATH (the ring
    #: captures the sink's record stream). 0 (default) = off; a clean
    #: run must produce NO flight artifact.
    flight_recorder: int = 0
    #: Program telemetry (``DLAF_PROGRAM_TELEMETRY``): the algorithm entry
    #: points and the library's cached-program sites record per-site
    #: compile walls (``dlaf_compile_seconds{site}``), trace counts
    #: (``dlaf_retrace_total{site}`` — first trace = 1, more = retraces),
    #: and ``compiled.memory_analysis()`` HBM gauges
    #: (``dlaf_hbm_bytes{what=args|output|temp|code|peak,site}``), each compile
    #: also landing as a ``program`` record in the ``metrics_path``
    #: artifact (dlaf_tpu.obs.telemetry; docs/observability.md). Off
    #: (default): every instrumented site is a passthrough to the same
    #: jitted callable — bitwise no-op, one attribute read of cost.
    program_telemetry: bool = False

    def _fields(self):
        return {f.name: f for f in dataclasses.fields(self)}


def _parse(value: str, typ):
    if typ is bool:
        return value.strip().lower() in ("1", "true", "yes", "on")
    return typ(value)


def update_configuration(
    user: Optional[Configuration] = None,
    argv: Optional[Sequence[str]] = None,
) -> Configuration:
    """Resolve the effective configuration.

    Precedence (highest wins), mirroring ``src/init.cpp:117-156``:
    CLI ``--dlaf:<name>=<v>`` > env ``DLAF_<NAME>`` > ``user`` struct > default.
    """
    cfg = dataclasses.replace(user) if user is not None else Configuration()
    fields = cfg._fields()
    for name, f in fields.items():
        env = os.environ.get("DLAF_" + name.upper())
        if env is not None:
            setattr(cfg, name, _parse(env, f.type if isinstance(f.type, type) else type(f.default)))
    if argv:
        for arg in argv:
            if not arg.startswith("--dlaf:"):
                continue
            body = arg[len("--dlaf:"):]
            if "=" in body:
                key, val = body.split("=", 1)
            else:
                key, val = body, "true"
            key = key.replace("-", "_")
            if key in fields:
                f = fields[key]
                setattr(cfg, key, _parse(val, f.type if isinstance(f.type, type) else type(f.default)))
    return cfg


#: Allowed values for enum-like knobs, checked at initialize() — a typo'd
#: value must fail loudly, not silently take the other branch (the literal
#: string comparisons at the use sites would otherwise just pick "native").
_VALID_CHOICES = {
    "grid_ordering": ("row-major", "col-major"),
    "band_to_tridiag_impl": ("native", "numpy"),
    "secular_impl": ("native", "numpy"),
    "bt_b2t_impl": ("blocked", "sweeps"),
    "cholesky_lookahead": ("0", "1", "auto"),
    "comm_lookahead": ("0", "1", "auto"),
    "dc_level_batch": ("0", "1", "auto"),
    "bt_lookahead": ("0", "1", "auto"),
    "f64_gemm": ("native", "mxu", "auto"),
    "f64_trsm": ("native", "mixed", "auto"),
    "panel_impl": ("fused", "xla", "auto"),
    "step_impl": ("fused", "xla", "auto"),
    "ozaki_dot": ("int8", "bf16", "auto"),
    "qr_panel": ("geqrf", "householder", "auto"),
    "mixed_seed": ("xla", "recursive"),
    "dist_step_mode": ("unrolled", "scan", "auto"),
    "hegst_impl": ("blocked", "twosolve", "auto"),
    "bcast_impl": ("psum", "tree"),
    "log": ("debug", "info", "warning", "error", "off"),
    "accuracy": ("0", "1", "full"),
}


def _validate(cfg: Configuration) -> None:
    for name, allowed in _VALID_CHOICES.items():
        v = getattr(cfg, name)
        if v not in allowed:
            raise ValueError(f"configuration {name}={v!r}: must be one of {allowed}")
    if cfg.trsm_rhs_chunk < -1:
        raise ValueError(f"trsm_rhs_chunk={cfg.trsm_rhs_chunk}: must be -1 "
                         "(auto), 0 (off), or a positive chunk width")
    if cfg.red2band_trail_chunk < -1:
        raise ValueError(f"red2band_trail_chunk={cfg.red2band_trail_chunk}: "
                         "must be -1 (auto), 0 (off), or a positive chunk "
                         "width")
    if not 0 <= cfg.f64_gemm_slices <= 9:
        raise ValueError(f"f64_gemm_slices={cfg.f64_gemm_slices}: must be in "
                         "[1, 9], or 0 for the platform-adaptive default")
    if cfg.step_vmem_limit < 1:
        raise ValueError(f"step_vmem_limit={cfg.step_vmem_limit}: must be "
                         ">= 1 byte (the fused step kernel's VMEM budget)")
    if cfg.mixed_seed_base < 1:
        raise ValueError(f"mixed_seed_base={cfg.mixed_seed_base}: must be >= 1"
                         " (the recursive seed's leaf size)")
    if cfg.serve_batch < 1:
        raise ValueError(f"serve_batch={cfg.serve_batch}: must be >= 1 "
                         "(lanes per batched serve dispatch)")
    if not cfg.serve_deadline_ms >= 0:
        raise ValueError(f"serve_deadline_ms={cfg.serve_deadline_ms}: must "
                         "be >= 0 (0 = dispatch at the first poll)")
    if cfg.serve_cache_bytes < 0:
        raise ValueError(f"serve_cache_bytes={cfg.serve_cache_bytes}: must "
                         "be >= 0 (0 = unbounded)")
    if cfg.serve_max_depth < 0:
        raise ValueError(f"serve_max_depth={cfg.serve_max_depth}: must be "
                         ">= 0 (0 = unbounded pending depth)")
    if cfg.serve_retry_attempts < 1:
        raise ValueError(f"serve_retry_attempts={cfg.serve_retry_attempts}: "
                         "must be >= 1 (1 = no dispatch retry)")
    if not cfg.serve_retry_backoff_ms >= 0:
        raise ValueError(f"serve_retry_backoff_ms="
                         f"{cfg.serve_retry_backoff_ms}: must be >= 0")
    if cfg.fleet_workers < 1:
        raise ValueError(f"fleet_workers={cfg.fleet_workers}: must be "
                         ">= 1 (replicas behind the fleet router)")
    if not cfg.fleet_heartbeat_ms > 0:
        raise ValueError(f"fleet_heartbeat_ms={cfg.fleet_heartbeat_ms}: "
                         "must be > 0 (the router ping cadence)")
    if not cfg.fleet_heartbeat_timeout_ms >= cfg.fleet_heartbeat_ms:
        raise ValueError(
            f"fleet_heartbeat_timeout_ms={cfg.fleet_heartbeat_timeout_ms}:"
            f" must be >= fleet_heartbeat_ms={cfg.fleet_heartbeat_ms} "
            "(a timeout shorter than one ping interval declares every "
            "healthy worker suspect)")
    if cfg.fleet_retry_attempts < 1:
        raise ValueError(f"fleet_retry_attempts={cfg.fleet_retry_attempts}:"
                         " must be >= 1 (1 = no dispatch retry)")
    if not cfg.fleet_retry_backoff_ms >= 0:
        raise ValueError(f"fleet_retry_backoff_ms="
                         f"{cfg.fleet_retry_backoff_ms}: must be >= 0")
    if not 0 <= cfg.metrics_port <= 65535:
        raise ValueError(f"metrics_port={cfg.metrics_port}: must be in "
                         "[0, 65535] (0 = live exporter off)")
    if not cfg.slo_p99_ms >= 0:
        raise ValueError(f"slo_p99_ms={cfg.slo_p99_ms}: must be >= 0 "
                         "(0 = no latency objective)")
    if not cfg.slo_window_s > 0:
        raise ValueError(f"slo_window_s={cfg.slo_window_s}: must be > 0 "
                         "(the rolling quantile window length)")
    if cfg.slo_burst < 0:
        raise ValueError(f"slo_burst={cfg.slo_burst}: must be >= 0 "
                         "(0 = breach-burst flight trigger off)")
    if cfg.flight_recorder < 0:
        raise ValueError(f"flight_recorder={cfg.flight_recorder}: must be "
                         ">= 0 (0 = flight recorder off; N = ring depth)")
    if cfg.circuit_threshold < 1:
        raise ValueError(f"circuit_threshold={cfg.circuit_threshold}: must "
                         "be >= 1 (consecutive failures before opening)")
    if not cfg.circuit_cooldown_s >= 0:
        raise ValueError(f"circuit_cooldown_s={cfg.circuit_cooldown_s}: "
                         "must be >= 0 (open -> half-open probe delay)")
    parse_serve_buckets(cfg.serve_buckets)   # raises on a malformed list
    # cholesky_trailing is validated against VALID_TRAILING at the use site
    # (algorithms/cholesky.py) to keep the list next to the implementations


def parse_serve_buckets(value: str) -> tuple:
    """``serve_buckets`` parsed to an ascending tuple of positive ints
    (empty tuple = the power-of-two auto policy). A malformed list must
    fail loudly at initialize(), not silently misroute every request to
    the auto buckets."""
    if not str(value).strip():
        return ()
    try:
        buckets = tuple(int(tok) for tok in str(value).split(","))
    except ValueError:
        raise ValueError(f"serve_buckets={value!r}: must be a "
                         "comma-separated list of positive ints")
    if any(b < 1 for b in buckets) or list(buckets) != sorted(set(buckets)):
        raise ValueError(f"serve_buckets={value!r}: ceilings must be "
                         "positive, strictly ascending, and unique")
    return buckets


_active: Optional[Configuration] = None

#: Compiled-program caches (jitted fns / lru-cached program builders) whose
#: traces bake in configuration decisions. Registered via
#: :func:`register_program_cache`; cleared when initialize() lands a config
#: that differs from the active one, so knob changes can never hit a stale
#: trace. (The reference has no analog: its knobs steer a dynamic runtime;
#: ours steer trace-time decisions that persist in compiled programs.)
_PROGRAM_CACHES: list = []


def register_program_cache(fn):
    """Register a cache-bearing callable (``.cache_clear()`` from
    functools.lru_cache or ``.clear_cache()`` from jax.jit) for invalidation
    on configuration changes. Usable as a decorator; returns ``fn``."""
    _PROGRAM_CACHES.append(fn)
    return fn


def _clear_program_caches() -> None:
    for fn in _PROGRAM_CACHES:
        clear = getattr(fn, "cache_clear", None) or getattr(fn, "clear_cache", None)
        if clear is not None:
            clear()


#: Where compiled programs persist when the caller has not placed the
#: cache itself: ``<checkout>/.jax_cache``, derived from this package's
#: location (the path is part of JAX's cache key, so it must not move).
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def _place_compilation_cache() -> None:
    """The one owner of JAX's persistent compilation cache placement. The
    unrolled factorizations cost minutes to compile and seconds to run, so
    the cache is always on. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads
    it itself and nothing is set here. Unset: the fixed
    :data:`DEFAULT_COMPILATION_CACHE_DIR`, and only compiles of 5 s or
    more are persisted (tests/conftest.py records why small cache-loaded
    programs are kept out)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_COMPILATION_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)


def initialize(user: Optional[Configuration] = None,
               argv: Optional[Sequence[str]] = None) -> Configuration:
    """Bring up the runtime (analog of ``dlaf::initialize``, ``init.h:60-75``).

    Resolves configuration and applies process-wide JAX settings (x64). Safe
    to call more than once; later calls re-resolve configuration and drop
    compiled-program caches if anything changed.
    """
    global _active
    cfg = update_configuration(user, argv)
    _validate(cfg)
    if _active is not None and cfg != _active:
        _clear_program_caches()
    if cfg.enable_x64:
        import jax

        jax.config.update("jax_enable_x64", True)
    _place_compilation_cache()
    # bring the observability layer in line with the resolved knobs
    # (DLAF_LOG / DLAF_METRICS_PATH / DLAF_TRACE_DIR; the legacy
    # profile_dir knob doubles as a trace dir so pre-obs profiling
    # configurations keep annotating)
    from . import obs

    obs.configure(log_level=cfg.log, metrics_path=cfg.metrics_path,
                  trace_dir=cfg.trace_dir or cfg.profile_dir,
                  program_telemetry=cfg.program_telemetry,
                  metrics_port=cfg.metrics_port,
                  flight_recorder=cfg.flight_recorder)
    if cfg.print_config:
        print(cfg)
    _active = cfg
    return cfg


def get_configuration() -> Configuration:
    """Active configuration, initializing with defaults on first use."""
    global _active
    if _active is None:
        _active = initialize()
    return _active


def resolve_platform_auto(value: str, *, knob: str, tpu_choice: str,
                          other_choice: str, detail: str) -> str:
    """Shared resolve-and-announce for the platform-keyed "auto" knobs
    (ozaki_dot, qr_panel, f64_gemm, f64_trsm, cholesky_trailing — grep
    for callers rather than trusting this list):
    pick per the PROCESS
    default jax backend — a trace explicitly placed on a non-default
    backend inherits the process choice; set the knob explicitly for
    that case — and print one stderr announcement per (knob, backend,
    choice) so the decision is never silent."""
    if value != "auto":
        return value
    import jax

    backend = jax.default_backend()
    choice = tpu_choice if backend == "tpu" else other_choice
    from .obs import get_logger

    # once per (knob, backend, choice) — the route in effect is visible,
    # not silent (round-2 advisory), via the obs layer's shared one-shot
    # registry (reset/forget hooks live there for tests)
    get_logger("config").warning_once(
        (knob, backend, choice),
        f"{knob}=auto resolved to {choice!r} for default backend "
        f"{backend!r} ({detail}) — set the knob explicitly to override",
        knob=knob, backend=backend, choice=choice)
    return choice


def resolved_f64_gemm() -> str:
    """``f64_gemm`` with "auto" resolved: mxu on TPU, native elsewhere
    (see the knob docstring for the measurement basis)."""
    return resolve_platform_auto(
        get_configuration().f64_gemm, knob="f64_gemm", tpu_choice="mxu",
        other_choice="native",
        detail="int8-slice MXU gemms measured 281-351 GF/s vs 47-49 for "
               "the native f64 emulation, with 4x smaller workspaces — "
               "2026-08-01 v5e session")


def resolved_f64_trsm() -> str:
    """``f64_trsm`` with "auto" resolved: mixed on TPU, native elsewhere
    (see the knob docstring for the measurement basis)."""
    return resolve_platform_auto(
        get_configuration().f64_trsm, knob="f64_trsm",
        tpu_choice="mixed", other_choice="native",
        detail="f32-seed Newton-refined panel solves measured +0.6 ms/step "
               "vs +15.7 for native-f64 panels — 2026-08-01 v5e session")


def resolved_panel_impl() -> str:
    """``panel_impl`` with "auto" resolved: fused on TPU, xla elsewhere
    (platform leg only — the dtype/block-size leg lives in
    ``tile_ops.pallas_panel.panel_uses_fused``, the route's single
    owner)."""
    return resolve_platform_auto(
        get_configuration().panel_impl, knob="panel_impl",
        tpu_choice="fused", other_choice="xla",
        detail="the per-step potrf+trsm chain is latency-bound on TPU "
               "(MFU table: 1.9-7.3% with neither roofline binding); the "
               "fused Pallas panel kernels collapse it to one dispatch "
               "per step (docs/pallas_panel.md)")


def resolved_step_impl() -> str:
    """``step_impl`` with "auto" resolved: fused on TPU, xla elsewhere
    (platform leg only — the dtype/block/VMEM-budget legs live in
    ``tile_ops.pallas_panel.step_uses_fused``, the route's single
    owner)."""
    return resolve_platform_auto(
        get_configuration().step_impl, knob="step_impl",
        tpu_choice="fused", other_choice="xla",
        detail="the remaining panel-bound floor is the kernel-launch + "
               "HBM round-trip between panel factorization and trailing "
               "update at every blocked step (ROADMAP item 4); the fused "
               "step kernel removes the boundary (docs/pallas_panel.md)")


def resolved_cholesky_lookahead() -> bool:
    """``cholesky_lookahead`` with "auto" resolved (True = pipelined):
    1 on TPU, 0 elsewhere (see the knob docstring for the basis)."""
    return resolve_platform_auto(
        get_configuration().cholesky_lookahead, knob="cholesky_lookahead",
        tpu_choice="1", other_choice="0",
        detail="panel-chain latency dominates blocked factorizations on "
               "TPU (config #1: 133 GF/s at N=4096 vs 514 at N=16384); "
               "the pipelined step order exposes panel k+1 to XLA while "
               "the bulk trailing update of step k is in flight") == "1"


def resolved_comm_lookahead() -> bool:
    """``comm_lookahead`` with "auto" resolved (True = collectives
    hoisted): 1 on TPU, 0 elsewhere (see the knob docstring and
    docs/comm_overlap.md)."""
    return resolve_platform_auto(
        get_configuration().comm_lookahead, knob="comm_lookahead",
        tpu_choice="1", other_choice="0",
        detail="ICI transfer time adds serially to the step chain unless "
               "the next panel's collectives are emitted before the bulk "
               "trailing product (arXiv:2112.09017's overlapped SUMMA "
               "updates); off-TPU the thunk executor runs collectives "
               "serially anyway") == "1"


def resolved_dc_level_batch() -> bool:
    """``dc_level_batch`` with "auto" resolved (True = level-batched D&C
    merges): 1 on TPU, 0 elsewhere (see the knob docstring and
    docs/eigensolver_perf.md)."""
    return resolve_platform_auto(
        get_configuration().dc_level_batch, knob="dc_level_batch",
        tpu_choice="1", other_choice="0",
        detail="the serialized merge walk pays one host->device dispatch "
               "round trip per small merge; batching a level's merges "
               "into one vmapped program is the arXiv:2112.09017 idiom "
               "that earns MXU utilization on many small problems") == "1"


def resolved_bt_lookahead() -> bool:
    """``bt_lookahead`` with "auto" resolved (True = pipelined reflector
    blocks): 1 on TPU, 0 elsewhere (see the knob docstring and
    docs/eigensolver_perf.md)."""
    return resolve_platform_auto(
        get_configuration().bt_lookahead, knob="bt_lookahead",
        tpu_choice="1", other_choice="0",
        detail="the reflector-block T-factor chain (and its panel gather "
               "collectives, distributed) is latency-bound and reads only "
               "constant reflector storage; emitting block k+1's chain "
               "before block k's bulk application lets it hide under the "
               "MXU bulk") == "1"


#: Step counts at which ``dist_step_mode="auto"`` switches to the scan
#: formulation, per platform; the local Cholesky's step form comes from
#: the same table (algorithms/cholesky.py:local_step_form). What the chip
#: has shown (one v5e, through benchmark/run.py; PERF.md section 6): at 32
#: steps (local Cholesky, N=16384, nb=512, f64; PR 31) the unrolled
#: program took 779.8 s to its first call, cold, ran 1.338 s a call and
#: left the 40 GiB host out of memory, the telescoped scan 149.5 s (12 s
#: from the cache) and 1.2284 s a call; the distributed solve at 32 steps
#: (trsm_d_n8192_2x2, PR 27) compiles 110-120 s in its scan form. At 16
#: steps (chol_d_n4096_1x1) the unrolled program compiles ~290 s cold and
#: is the measured route; its scan form is not measured on the chip, nor
#: is any other step count: 32 rests on the one comparison above. The
#: CPU's 128 rests on no measurement of this round (compiles there are
#: cheap; tier-1 runs both forms).
STEP_MODE_AUTO_SCAN_AT = {"tpu": 32, "cpu": 128}


def resolve_step_mode(steps: int, platform: Optional[str] = None) -> str:
    """Effective step formulation for an algorithm with ``steps`` traced
    per-k steps: the configured ``dist_step_mode``, with ``"auto"``
    resolved per (step count, platform) from the measured compile
    constants (:data:`STEP_MODE_AUTO_SCAN_AT`). ``platform`` defaults to
    the jax default backend."""
    mode = get_configuration().dist_step_mode
    if mode != "auto":
        return mode
    if platform is None:
        import jax

        platform = jax.default_backend()
    return "scan" if steps >= STEP_MODE_AUTO_SCAN_AT.get(platform, 128) \
        else "unrolled"


def finalize() -> None:
    """Tear down (analog of ``dlaf::finalize``); PJRT owns real resources."""
    global _active
    _active = None
