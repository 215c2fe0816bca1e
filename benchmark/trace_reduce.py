"""Profiler trace (xplane) -> per-device busy union, operation table, idle
gaps. The only reader of the trace the benchmark has.

``read_xplane`` turns the profiler's file into plain tuples; everything else
works on those tuples, so the arithmetic is checked on a hand-made event list
(benchmark/tests). Times are nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import glob
import os
import re

#: Line of a device plane that holds one event per executed HLO operation.
#: On the v5e an event's name is the instruction's whole HLO text:
#: ``%subtract_select_fusion.1509 = (f32[...]) fusion(...), kind=kOutput, ...``
OPS_LINE = "XLA Ops"
COLLECTIVE_TOKENS = ("all-reduce", "all-gather", "all-to-all",
                     "reduce-scatter", "collective-permute",
                     "collective-broadcast", "send", "recv")
MATMUL_TOKENS = ("convolution", "dot", "cholesky", "triangular-solve")
#: Operations that only enclose others (their time is their children's).
CONTROL_OPCODES = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SUFFIX = re.compile(r"(\.\d+|\.clone\d*|\.remat\d*)+$")
_DETAIL = re.compile(r'kind=(k\w+)|custom_call_target="([^"]+)"')


def newest_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def read_xplane(path: str, host_prefixes=("bench_", "stage.")):
    """``(devices, host_spans, listing)``: ``devices`` maps a device plane's
    name to its operation events ``(start, end, name)``; ``host_spans`` are
    the host annotations ``(start, end, name)`` whose name starts with one of
    ``host_prefixes``; ``listing`` names every plane and line with its event
    count (what a reader looks at by hand first)."""
    from jax.profiler import ProfileData

    devices, host_spans, listing = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            listing.append([plane.name, line.name, len(events)])
            if is_device and line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(events)
            elif not is_device:
                host_spans.extend(e for e in events
                                  if e[2].startswith(host_prefixes))
    return devices, host_spans, listing


def parse_op(text: str):
    """``(label, opcode, stem)`` of a device event's name. ``stem`` is the
    instruction's name without its number, ``label`` the stem with the
    fusion kind or custom-call target: what the operation table groups by.
    A bare name (no HLO text) is its own stem and opcode."""
    head, sep, rest = text.partition(" = ")
    stem = _SUFFIX.sub("", head.lstrip("%")) if sep else text.split(".")[0]
    found = _OPCODE.search(" " + rest) if sep else None
    detail = _DETAIL.search(rest) if sep else None
    label = stem + (" " + (detail.group(1) or detail.group(2))
                    if detail else "")
    return label, found.group(1) if found else stem, stem


def classify(text: str) -> str:
    """``matmul`` | ``collective`` | ``control`` | ``other`` for one device
    operation. On the TPU a matrix product is a ``convolution`` or a fusion
    of kind ``kOutput`` (a convolution with its epilogue fused in; XLA names
    it after the epilogue, e.g. ``subtract_select_fusion``)."""
    label, opcode, stem = parse_op(text)
    if opcode in CONTROL_OPCODES:
        return "control"
    if any(t in opcode or t in stem for t in COLLECTIVE_TOKENS):
        return "collective"
    if label.endswith(" kOutput") \
            or any(t in opcode or t in stem for t in MATMUL_TOKENS):
        return "matmul"
    return "other"


def clip(events, window):
    """Events cut to ``window = (start, end)``; those outside are dropped."""
    cut = ((max(s, window[0]), min(e, window[1]), name)
           for s, e, name in events)
    return [ev for ev in cut if ev[1] > ev[0]]


def busy_union(events):
    """Merged ``[(start, end)]`` of the events' intervals, in order."""
    merged = []
    for s, e in sorted((ev[0], ev[1]) for ev in events):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(e, merged[-1][1]))
        else:
            merged.append((s, e))
    return merged


def self_times(events):
    """``{name: ns}`` of each operation's own time: its duration
    less the part its nested operations cover (a ``while`` encloses its
    body's operations on the same line)."""
    out = {}
    stack = []          # [end, key, child_ns, start]

    def close(item):
        end, key, child, start = item
        out[key] = out.get(key, 0) + max(end - start - child, 0)
        if stack:
            stack[-1][2] += end - start

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack and e > stack[-1][0]:
            e = stack[-1][0]        # overlap without nesting: cut to parent
        stack.append([e, name, 0, s])
    while stack:
        close(stack.pop())
    return out


def idle_gaps(merged, window):
    """``[(start, end)]`` of the window not covered by ``merged``."""
    gaps, at = [], window[0]
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if window[1] > at:
        gaps.append((at, window[1]))
    return gaps


def label_gap(gap, host_spans, call_name="bench_call"):
    """What the host was doing at the gap's midpoint: the innermost stage
    annotation that covers it, else ``in_call`` inside a call annotation,
    else ``between_calls``."""
    mid = (gap[0] + gap[1]) / 2
    best, in_call = None, False
    for s, e, name in host_spans:
        if not s <= mid < e:
            continue
        if name == call_name:
            in_call = True
        elif best is None or e - s < best[0]:
            best = (e - s, name)
    if best is not None:
        return best[1]
    return "in_call" if in_call else "between_calls"


def worst_device(reduced):
    """The least busy device's record, or None without a device trace."""
    if not reduced or not reduced.get("devices"):
        return None
    return reduced["devices"][reduced["worst_device"]]


def class_share(reduced, cls: str):
    """Percent of the worst device's own operation time (control operations
    left out) that is in class ``cls``; None without a device trace."""
    dev = worst_device(reduced)
    total = sum(v for k, v in dev["classes"].items() if k != "control") \
        if dev else 0
    return 100.0 * dev["classes"][cls] / total if total else None


def reduce_trace(devices, host_spans, window, top=10):
    """The reduced trace the per-layer readers get, for ``window`` on the
    trace's clock: per device the busy ns, own ns by class and by operation
    name; for the least busy (worst) device the operation table and the idle
    gaps by what the host was doing, ``top`` entries each, in seconds."""
    span = max(window[1] - window[0], 0)
    per_device, merged_of = {}, {}
    for dev, events in devices.items():
        events = clip(events, window)
        merged_of[dev] = busy_union(events)
        classes = {"matmul": 0, "collective": 0, "control": 0, "other": 0}
        table = {}
        for name, ns in self_times(events).items():
            classes[classify(name)] += ns
            label = parse_op(name)[0]
            table[label] = table.get(label, 0) + ns
        per_device[dev] = {
            "busy_ns": sum(e - s for s, e in merged_of[dev]),
            "classes": classes, "ops": table, "n_events": len(events)}
    if not per_device or not span:
        return {"devices": {}, "window_s": span / 1e9}
    worst = min(per_device, key=lambda d: per_device[d]["busy_ns"])
    by_label, singles = {}, []
    for g in idle_gaps(merged_of[worst], window):
        label = label_gap(g, host_spans)
        by_label[label] = by_label.get(label, 0) + g[1] - g[0]
        singles.append((g[1] - g[0], label))
    idle = sorted(([k, v / 1e9] for k, v in by_label.items()),
                  key=lambda kv: -kv[1])
    idle += [[f"longest:{label}", ns / 1e9] for ns, label
             in sorted(singles, reverse=True)[:max(top - len(idle), 0)]]
    ops = sorted(per_device[worst]["ops"].items(), key=lambda kv: -kv[1])
    return {
        "window_s": span / 1e9, "worst_device": worst, "devices": per_device,
        "busy_s_mean": sum(d["busy_ns"] for d in per_device.values())
        / len(per_device) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in ops[:top]],
        "idle_gaps": idle[:top],
    }
