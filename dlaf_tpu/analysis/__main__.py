"""``python -m dlaf_tpu.analysis`` — the static-analysis CI gate.

Runs the jaxpr graph auditor (:mod:`.graphcheck`) and the AST convention
linter (:mod:`.lint`), diffs the findings against the committed baseline
(``.analysis_baseline.json``), and exits 1 on any finding not in the
baseline — same only-gets-cleaner semantics as the bench/accuracy gates.

``--drill NAME`` runs one seeded-bad must-trip program (:mod:`.drills`)
instead: exit 1 with the expected rule named in the log proves the gate
can fail; exit 3 means the CHECK is broken (it no longer flags its own
drill) — CI requires specifically 1.

Must run with the virtual CPU platform so the 2x2 audit meshes exist;
invoked as a module this file forces it (before the first jax import,
the same constraint tests/conftest.py documents).
"""

from __future__ import annotations

import argparse
import os
import sys


def _force_virtual_devices() -> None:
    """Force >= 8 virtual CPU devices, BEFORE the first jax import.
    No-op when the caller already forced a device count."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    # static auditing is hermetic by design and never takes a chip
    os.environ["JAX_PLATFORMS"] = "cpu"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m dlaf_tpu.analysis",
        description="jaxpr graph auditor + repo-convention linter "
                    "(docs/static_analysis.md)")
    parser.add_argument("--root", default=".",
                        help="repo root to lint / find the baseline in")
    # mutually exclusive: both at once would skip every checker and
    # report a vacuously clean gate
    only = parser.add_mutually_exclusive_group()
    only.add_argument("--lint-only", action="store_true",
                      help="skip the graph auditor")
    only.add_argument("--graph-only", action="store_true",
                      help="skip the linter")
    parser.add_argument("--baseline", default=None,
                        help="baseline path (default <root>/"
                             ".analysis_baseline.json)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="grandfather ALL current findings and exit 0")
    parser.add_argument("--hbm-factor", type=float, default=None,
                        help="materialized-intermediate budget as a "
                             "multiple of program input bytes")
    parser.add_argument("--drill", default=None,
                        help="run one seeded-bad must-trip drill")
    parser.add_argument("--list-drills", action="store_true")
    args = parser.parse_args(argv)

    if "jax" not in sys.modules:
        _force_virtual_devices()

    from . import BASELINE_PATH, diff_baseline, load_baseline, write_baseline
    from . import lint as lint_mod

    if args.list_drills:
        from . import drills as drills_mod

        print("\n".join(sorted(drills_mod.DRILLS)))
        return 0

    if args.drill:
        from . import drills as drills_mod

        try:
            findings, expected = drills_mod.run(args.drill)
        except KeyError as e:
            # a typo'd drill name must exit 2 (usage error), never 1 —
            # rc=1 is the "drill tripped" success contract CI greps for
            parser.error(str(e))
        for f in findings:
            print(f)
        missing = set(expected) - {f.rule for f in findings}
        if missing:
            print(f"DRILL BROKEN: {args.drill} did not trip "
                  f"{sorted(missing)} — the checker lost its teeth",
                  file=sys.stderr)
            return 3
        print(f"drill {args.drill}: tripped "
              f"{sorted(set(expected))} as required")
        return 1

    findings = []
    if not args.lint_only:
        from . import graphcheck as graphcheck_mod

        kw = {}
        if args.hbm_factor is not None:
            kw["hbm_factor"] = args.hbm_factor
        findings.extend(graphcheck_mod.run(**kw))
    if not args.graph_only:
        try:
            findings.extend(lint_mod.run(args.root))
        except FileNotFoundError as e:
            # zero files scanned = misconfiguration, not a clean tree
            parser.error(str(e))

    baseline_path = args.baseline or os.path.join(args.root, BASELINE_PATH)
    if args.write_baseline:
        if args.lint_only or args.graph_only:
            # a partial run would overwrite the shared baseline with only
            # the selected checker's findings, silently erasing the other
            # checker's grandfathered keys
            parser.error("--write-baseline requires a full run (drop "
                         "--lint-only/--graph-only)")
        write_baseline(baseline_path, findings)
        print(f"wrote {len(findings)} finding key(s) to {baseline_path}")
        return 0

    baseline = load_baseline(baseline_path)
    new, stale = diff_baseline(findings, baseline)
    old = len(findings) - len(new)
    print(f"dlaf_tpu.analysis: {len(findings)} finding(s) "
          f"({len(new)} new, {old} baselined), "
          f"{len(stale)} stale baseline key(s)")
    for key in stale:
        print(f"  stale baseline entry (fixed? remove it): {key}")
    for f in new:
        print(f"  NEW {f}")
    if new:
        print(f"FAILED: {len(new)} new finding(s) — fix them or, for a "
              f"deliberate grandfather, rerun with --write-baseline "
              f"(docs/static_analysis.md)", file=sys.stderr)
        return 1
    print("analysis gate: PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
