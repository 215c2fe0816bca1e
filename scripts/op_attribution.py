#!/usr/bin/env python3
"""Own device time per HLO instruction of one traced benchmark run, joined to
the source lines that emitted it.

    python3 scripts/op_attribution.py --workload chol_d_n4096_1x1 --seed 7 \\
        --out chiprun_out/attr [--root <checkout>] [--opcode copy] \
        [--program _cholesky_local_scan] [--calls 1]

Runs ``benchmark/run.py --trace 1`` of ``--root`` in this process (it holds
the chip), then reads the run's xplane once more with the benchmark's own
readers: every ``XLA Ops`` event of the traced window is given to the ``XLA
Modules`` event that encloses it, own
time (``trace_reduce.self_times``) is summed per (module, instruction name),
and the instruction name is looked up in the compiled text of the local
entry's program (``SITE_PROGRAMS``: the Cholesky's one program,
``jit_cholesky_local_on_tiles``, with the layout moves and the builder the
entry took, ``_cholesky_local`` or, from 32 block steps on,
``_cholesky_local_scan``; ``reduction_to_band``'s ``_red2band_local`` /
``_red2band_local_scan``: ``--program`` names the builder at which a
source chain stops, by default the one the run dispatched;
``.lower(...).compile().as_text()``, which keeps
``metadata={op_name=... stack_frame_id=...}`` and the tables that resolve a
frame to file, line and function; the trace's event names do not, PERF.md
section 3). Writes ``<out>/attribution.json`` (with own time by the source
file of the innermost frame, ``by_file``, and by the builder's own line
that emitted the instruction, ``by_site``) and the compiled text (gzipped), and prints the ``--opcode``
rows by result shape and source line.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import os
import re
import sys

_META = re.compile(r'op_name="([^"]*)"(?:[^}]*?stack_frame_id=(\d+))?')
_RESULT = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)")
_OPERAND = re.compile(r"%([A-Za-z_][\w.\-]*)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_MODULE = re.compile(r"HloModule ([\w.\-]+)")
#: the local entries' telemetry sites and the builder each dispatches
SITE_PROGRAMS = {"cholesky.local": "_cholesky_local",
                 "cholesky.local_scan": "_cholesky_local_scan",
                 "reduction_to_band.local": "_red2band_local",
                 "reduction_to_band.local_scan": "_red2band_local_scan"}


def frame_tables(text: str) -> dict:
    """The module text's ``FileNames`` / ``FunctionNames`` / ``FileLocations``
    / ``StackFrames`` tables as ``{table: {id: row text}}``."""
    tables, current = {}, None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            current = tables.setdefault(line, {})
        elif current is not None:
            row = _TABLE_ROW.match(line)
            if row:
                current[int(row.group(1))] = row.group(2)
            elif line.strip():
                current = None
    return tables


def frames(tables: dict, frame_id: int):
    """``(file name, line, function)`` of a stack frame and its callers,
    innermost first (a frame's ``parent_frame_id`` is its parent's id plus
    one; 0 is none)."""
    while frame_id and frame_id in tables.get("StackFrames", {}):
        ids = dict(kv.split("=") for kv in re.findall(
            r"\w+=\d+", tables["StackFrames"][frame_id]))
        loc = dict(kv.split("=") for kv in re.findall(
            r"\w+=\d+", tables["FileLocations"][int(ids["file_location_id"])]))
        fn = tables["FunctionNames"][int(loc["function_name_id"])].strip('"')
        path = tables["FileNames"][int(loc["file_name_id"])].strip('"')
        yield os.path.basename(path), int(loc["line"]), fn
        frame_id = int(ids["parent_frame_id"]) - 1


def frame_chain(tables: dict, frame_id: int, stop="_cholesky_local") -> str:
    """``file:line(function) < caller ...`` from the innermost frame out to
    the first frame in ``stop``, eight frames at most."""
    out = []
    for path, line, fn in frames(tables, frame_id):
        out.append(f"{path}:{line}({fn})")
        if fn == stop or len(out) >= 8:
            break
    return " < ".join(out)


def builder_site(tables: dict, frame_id: int, stop: str) -> str:
    """``file:line`` of the innermost frame in the builder's own file (the
    file of the frame of function ``stop``): the line of the builder that
    emitted the instruction, however deep the library calls under it."""
    chain = list(frames(tables, frame_id))
    home = next((path for path, _line, fn in chain if fn == stop), None)
    return next((f"{path}:{line}" for path, line, _fn in chain
                 if path == home), "")


def instruction_metadata(text: str) -> dict:
    """``{instruction name: (op_name, stack frame id)}`` of a compiled
    module's text, for the instructions that carry metadata."""
    out = {}
    for line in text.splitlines():
        head, sep, rest = line.strip().partition(" = ")
        if not sep:
            continue
        m = _META.search(rest)
        if m:
            out[head.replace("ROOT ", "").lstrip("%")] = (
                m.group(1), int(m.group(2) or 0))
    return out


def module_of(modules, start):
    """Name of the module event that covers ``start`` (sorted by start)."""
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= start < modules[i][1]:
        return modules[i][2]
    return "?"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="chol_d_n4096_1x1")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--opcode", default="copy")
    ap.add_argument("--calls", type=int, default=0,
                    help="read the window's first N calls only (a window "
                         "whose device trace was cut off at the profiler's "
                         "event limit: red2band_d_n8192_1x1 holds one call "
                         "and a half); default: the whole window")
    ap.add_argument("--program", default=None,
                    help="builder function at which a source chain stops "
                         "(default: the one the run dispatched)")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    os.makedirs(args.out, exist_ok=True)
    for p in (root, os.path.join(root, "benchmark")):
        sys.path.insert(0, p)

    import run as bench
    import span_reduce
    import trace_reduce
    from dlaf_tpu.obs import telemetry

    # the factorization's program as the entry point asks for it
    captured = {}
    plain_call = telemetry.call

    def capturing_call(site, fn, *a, **kw):
        if site in SITE_PROGRAMS and not captured:
            import jax

            captured["lower"] = (fn, [jax.ShapeDtypeStruct(x.shape, x.dtype)
                                      for x in a], kw)
            captured["site"] = site
        return plain_call(site, fn, *a, **kw)

    telemetry.call = capturing_call
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"],
                    root=root)
    telemetry.call = plain_call
    if rc:
        return rc

    out_dir = os.path.join(root, "benchmark", "out", args.workload)
    path = trace_reduce.newest_xplane(os.path.join(out_dir, "trace"))
    devices, _spans, _listing = trace_reduce.read_xplane(path)
    modules, host_spans = span_reduce.load(path)
    window = next((s, e) for s, e, n in host_spans
                  if n == span_reduce.WINDOW)
    window_s = (window[1] - window[0]) / 1e9
    with open(os.path.join(out_dir, "walls.json")) as f:
        calls = len(json.load(f))
    if args.calls:
        spans = span_reduce.calls_of(host_spans)[:args.calls]
        window, calls = (spans[0][0], spans[-1][1]), len(spans)
        window_s = (window[1] - window[0]) / 1e9

    # own time per (module, instruction): self_times keys by name, so key
    # each device's events by the program that encloses them first
    own = collections.Counter()
    for plane, events in devices.items():
        programs = sorted((s, e, n.split("(")[0])
                          for s, e, n in modules.get(plane, []))
        own.update(trace_reduce.self_times(
            [(s, e, module_of(programs, s) + "\t" + n)
             for s, e, n in trace_reduce.clip(events, window)]))

    meta, tables, program = {}, {}, None
    builder = args.program or SITE_PROGRAMS.get(captured.get("site"),
                                                "_cholesky_local")
    if captured:
        fn, avals, kw = captured["lower"]
        text = fn.lower(*avals, **kw).compile().as_text()
        program = _MODULE.match(text).group(1)
        with gzip.open(os.path.join(args.out,
                                    builder.lstrip("_") + ".hlo.txt.gz"),
                       "wt") as f:
            f.write(text)
        meta, tables = instruction_metadata(text), frame_tables(text)

    rows = collections.defaultdict(lambda: [0, 0])
    by_label = collections.defaultdict(int)
    by_file = collections.defaultdict(int)
    by_site = collections.defaultdict(int)
    for key, ns in own.items():
        module, name = key.split("\t", 1)
        label, opcode, _stem = trace_reduce.parse_op(name)
        by_label[label] += ns
        inst = name.partition(" = ")[0].lstrip("%")
        shape = _RESULT.search(name)
        op_name, frame, via = "", 0, ""
        if program and module.startswith(program):
            # a copy the compiler put in has no metadata of its own: take
            # its first operand's
            for cand in [inst] + _OPERAND.findall(name.partition(" = ")[2]):
                if cand in meta:
                    op_name, frame = meta[cand]
                    via = "" if cand == inst else "via operand: "
                    break
        # the op_name's tail (the primitive and its nearest scopes)
        tail = via + "/".join(op_name.split("/")[-3:])
        chain = frame_chain(tables, frame, builder)
        # an instruction with an op_name and no frame (XLA's expansions:
        # triangular_solve) goes by its primitive
        bare = f"no frame: {op_name.rpartition('/')[2]} ({module})" \
            if op_name else f"no metadata ({module})"
        by_file[chain.split(":")[0] or bare] += ns
        by_site[builder_site(tables, frame, builder) or bare] += ns
        row = rows[(module, label, opcode, shape.group(1) if shape else "",
                    chain, tail)]
        row[0] += ns
        row[1] += 1
    table = sorted(([*k, v[0] / 1e9, v[1]] for k, v in rows.items()),
                   key=lambda r: -r[6])
    busy = sum(own.values()) / 1e9
    with open(os.path.join(args.out, "attribution.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "program": builder,
                   "calls": calls, "window_s": window_s,
                   "own_s_total": busy,
                   "by_label": sorted(([k, v / 1e9]
                                       for k, v in by_label.items()),
                                      key=lambda kv: -kv[1]),
                   "by_file": sorted(([k, v / 1e9]
                                      for k, v in by_file.items()),
                                     key=lambda kv: -kv[1]),
                   "by_site": sorted(([k, v / 1e9]
                                      for k, v in by_site.items()),
                                     key=lambda kv: -kv[1]),
                   "rows": table}, f, indent=1)
    print(f"[attribution] calls={calls} own_s_total={busy:.4f} "
          f"window_s={window_s:.4f}")
    for label, ns in sorted(by_label.items(), key=lambda kv: -kv[1])[:16]:
        print(f"[label] {ns / 1e9:9.5f} s  {100 * ns / 1e9 / busy:5.1f}%  "
              f"{label}")
    print(f"[rows] opcode={args.opcode}: module, result, source, op_name "
          "tail, own s in window, per call ms, instructions")
    for module, label, opcode, shape, src, tail, sec, count in table:
        if opcode == args.opcode and sec >= 1e-4:
            print(f"[row] {module}  {shape}  {src}  {tail}  {sec:.5f}  "
                  f"{1e3 * sec / calls:.4f}  {count}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
