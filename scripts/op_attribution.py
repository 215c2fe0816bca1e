#!/usr/bin/env python3
"""Own device time per HLO instruction of one traced benchmark run, joined to
the source lines and the builder's phases that emitted it.

    python3 scripts/op_attribution.py --workload chol_d_n4096_1x1 --seed 7 \\
        --out chiprun_out/attr [--root <checkout>] [--opcode copy] \
        [--program _cholesky_local_scan] [--calls 1]

Runs ``benchmark/run.py --trace 1`` of ``--root`` in this process (it holds
the chip), then reads the run's xplane once more with the benchmark's own
readers: every ``XLA Ops`` event of the traced window is given to the ``XLA
Modules`` event that encloses it, own
time (``trace_reduce.self_times``) is summed per (module, instruction name),
and the instruction name is looked up in the compiled text of every program
an entry dispatched through ``telemetry.call`` (on a four-chip cell the
least busy device's events only, the plane the benchmark's readers read;
``telemetry.programs()`` /
``telemetry.compiled(site)``: the entries remember what they dispatched
while the metrics sink is on, so the local Cholesky's
``jit_cholesky_local_on_tiles``, the local reduction's
``jit__red2band_local_scan`` and the four-chip solve's ``jit_run`` are all
found the same way; ``compiled.as_text()`` keeps
``metadata={op_name=... stack_frame_id=...}`` and the tables that resolve a
frame to file, line and function; the trace's event names do not). Writes
``<out>/attribution.json`` with own time by the source file of the
innermost frame (``by_file``), by the builder's own line that emitted the
instruction (``by_site``: the innermost frame in the file of the entry that
called ``telemetry.call``; ``--program`` names a function at which a source
chain stops instead) and by the builder's phase (``by_phase``:
``telemetry.phase_table(site)`` placed by ``benchmark/phase_table.py``'s
``resolve``, which is what the benchmark's ``phase_ms.*`` use), and the
compiled texts (gzipped), and prints the ``--opcode`` rows by result shape
and source line.
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

_FRAME = re.compile(r"stack_frame_id=(\d+)")
_RESULT = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
#: the library's frame that dispatches a program: its caller is the entry
DISPATCH = ("telemetry.py", "call")


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


def frame_chain(tables: dict, frame_id: int, stop="_cholesky_local",
                home=None) -> str:
    """``file:line(function) < caller ...`` from the innermost frame out to
    the first frame in function ``stop`` (or, with ``stop`` None, the first
    in the file ``home``), eight frames at most."""
    out = []
    for path, line, fn in frames(tables, frame_id):
        out.append(f"{path}:{line}({fn})")
        if (fn == stop if stop else path == home) or len(out) >= 8:
            break
    return " < ".join(out)


def entry_file(tables: dict):
    """The source file of the entry that dispatched the program: the file
    of the caller of ``telemetry.call``, taken from the frame tables (the
    builder is a function of the same file: ``cholesky.py``,
    ``triangular.py``, ``reduction_to_band.py``); None without such a
    frame."""
    for frame_id in sorted(tables.get("StackFrames", {})):
        chain = list(frames(tables, frame_id))
        if len(chain) > 1 and chain[0][0::2] == DISPATCH:
            return chain[1][0]
    return None


def builder_site(tables: dict, frame_id: int, stop=None, home=None) -> str:
    """``file:line`` of the innermost frame in the builder's own file (the
    file of the frame of function ``stop``, else ``home``): the line of the
    builder that emitted the instruction, however deep the library calls
    under it."""
    chain = list(frames(tables, frame_id))
    if stop:
        home = next((path for path, _line, fn in chain if fn == stop), None)
    return next((f"{path}:{line}" for path, line, _fn in chain
                 if path == home), "")


def instruction_metadata(text: str) -> dict:
    """``{instruction name: (op_name, stack frame id)}`` of a compiled
    module's text, for the instructions that carry metadata
    (``obs.scopes.instructions`` reads the lines)."""
    from dlaf_tpu.obs import scopes

    out = {}
    for name, op_name, rest in scopes.instructions(text):
        if op_name:
            frame = _FRAME.search(rest)
            out[name] = (op_name, int(frame.group(1)) if frame else 0)
    return out


def module_of(modules, start):
    """Name of the module event that covers ``start`` (sorted by start)."""
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= start < modules[i][1]:
        return modules[i][2]
    return "?"


def least_busy(devices: dict, reduced) -> dict:
    """``devices`` cut to the plane the benchmark's readers read: the
    traced run's least busy device (``trace_reduced.json``'s
    ``worst_device``), so that a four-chip cell's times are ONE chip's, as
    its ``phase_ms.*`` and ``device_busy_s`` are, and not the sum of four.
    All of them where the reduced trace names none (a one-plane trace)."""
    worst = (reduced or {}).get("worst_device")
    return {worst: devices[worst]} if worst in devices else devices


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
                         "(default: the first frame in the file of the "
                         "entry that dispatched the program)")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    os.makedirs(args.out, exist_ok=True)
    for p in (root, os.path.join(root, "benchmark")):
        sys.path.insert(0, p)

    import phase_table
    import run as bench
    import span_reduce
    import trace_reduce
    from dlaf_tpu.obs import telemetry

    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"],
                    root=root)
    if rc:
        return rc

    out_dir = os.path.join(root, "benchmark", "out", args.workload)
    path = trace_reduce.newest_xplane(os.path.join(out_dir, "trace"))
    devices, _spans, _listing = trace_reduce.read_xplane(path)
    with open(os.path.join(out_dir, "trace_reduced.json")) as f:
        devices = least_busy(devices, json.load(f))
    print(f"[attribution] device planes read: {sorted(devices)}")
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

    # every program an entry dispatched, as the entry remembered it
    dispatched = {}
    for site in telemetry.programs():
        text = telemetry.compiled(site).as_text()
        table = telemetry.phase_table(site)
        tables = frame_tables(text)
        dispatched[table["module"]] = {
            "site": site, "meta": instruction_metadata(text),
            "tables": tables, "table": table, "home": entry_file(tables)}
        with gzip.open(os.path.join(args.out, site + ".hlo.txt.gz"),
                       "wt") as f:
            f.write(text)
        print(f"[program] site={site} module={table['module']} "
              f"entry_file={dispatched[table['module']]['home']} "
              f"phase_instructions={table['counts']} "
              f"stale={table['stale']}")

    rows = collections.defaultdict(lambda: [0, 0])
    by_label = collections.defaultdict(int)
    by_file = collections.defaultdict(int)
    by_site = collections.defaultdict(int)
    by_phase = collections.defaultdict(int)
    for key, ns in own.items():
        module, name = key.split("\t", 1)
        label, opcode, _stem = trace_reduce.parse_op(name)
        by_label[label] += ns
        inst = phase_table.instruction(name)
        shape = _RESULT.search(name)
        op_name, frame, via, tables, home = "", 0, "", {}, None
        prog = next((d for m, d in dispatched.items()
                     if module.startswith(m)), None)
        if prog is not None:
            tables, home = prog["tables"], prog["home"]
            # a copy the compiler put in has no metadata of its own: take
            # its first operand's
            for cand in [inst] + prog["table"]["operands"].get(inst, []):
                if cand in prog["meta"]:
                    op_name, frame = prog["meta"][cand]
                    via = "" if cand == inst else "via operand: "
                    break
            phase, how = phase_table.resolve(prog["table"], inst)
            by_phase[f"{phase or phase_table.UNATTRIBUTED} "
                     f"({prog['site']}{', via ' + how if how else ''})"] += ns
        # the op_name's tail (the primitive and its nearest scopes)
        tail = via + "/".join(op_name.split("/")[-3:])
        chain = frame_chain(tables, frame, args.program, home)
        # an instruction with an op_name and no frame (XLA's expansions:
        # triangular_solve) goes by its primitive
        bare = f"no frame: {op_name.rpartition('/')[2]} ({module})" \
            if op_name else f"no metadata ({module})"
        by_file[chain.split(":")[0] or bare] += ns
        by_site[builder_site(tables, frame, args.program, home) or bare] += ns
        row = rows[(module, label, opcode, shape.group(1) if shape else "",
                    chain, tail)]
        row[0] += ns
        row[1] += 1
    table = sorted(([*k, v[0] / 1e9, v[1]] for k, v in rows.items()),
                   key=lambda r: -r[6])
    busy = sum(own.values()) / 1e9
    with open(os.path.join(args.out, "attribution.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "programs": {m: d["site"]
                                for m, d in dispatched.items()},
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
                   "by_phase": sorted(([k, v / 1e9]
                                       for k, v in by_phase.items()),
                                      key=lambda kv: -kv[1]),
                   "rows": table}, f, indent=1)
    print(f"[attribution] calls={calls} own_s_total={busy:.4f} "
          f"window_s={window_s:.4f}")
    for label, ns in sorted(by_label.items(), key=lambda kv: -kv[1])[:16]:
        print(f"[label] {ns / 1e9:9.5f} s  {100 * ns / 1e9 / busy:5.1f}%  "
              f"{label}")
    for label, ns in sorted(by_phase.items(), key=lambda kv: -kv[1]):
        print(f"[phase] {ns / 1e9:9.5f} s  {1e3 * ns / 1e9 / calls:9.4f} "
              f"ms a call  {label}")
    print(f"[rows] opcode={args.opcode}: module, result, source, op_name "
          "tail, own s in window, per call ms, instructions")
    for module, label, opcode, shape, src, tail, sec, count in table:
        if opcode == args.opcode and sec >= 1e-4:
            print(f"[row] {module}  {shape}  {src}  {tail}  {sec:.5f}  "
                  f"{1e3 * sec / calls:.4f}  {count}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
