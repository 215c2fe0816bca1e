"""Own device time of one call by the builders' phases: what the readers
``phase_ms.*`` share, and where ``program_temp_gib`` / ``program_code_mib``
get the program from.

The library names what its step builders emit (``obs.named_span``:
``cholesky.panel`` / ``.strip`` / ``.bulk``, ``red2band.panel`` / ``.larft`` /
``.w`` / ``.x`` / ``.update``, ``trsm.panel`` / ``.bulk``, ``layout``); the
names are scopes in the compiled program's ``op_name`` metadata and cost
nothing at run time. While the metrics sink is on every entry remembers the
program it dispatched (``obs.telemetry.programs()``), and
``telemetry.phase_table(site)`` hands out ``instruction -> phase`` from that
program's compiled text. On the v5e an event of the ``XLA Ops`` line is named
by its instruction's whole HLO text, so the join is by instruction name.

How one event of the entry's program is placed, in this order:

* ``call``: it lies inside a ``call`` event whose own instruction has a
  phase: a kernel emitted once and called from several sites takes the phase
  of the site that called it;
* ``direct``: its instruction carries a phase;
* ``operand``: its instruction carries none (a compiler's ``copy`` /
  ``bitcast`` has no metadata at all) and takes the phase of its first
  operand that has one, three levels up at most;
* ``neighbour``: its instruction is *shared* (:func:`shared_instructions`)
  and no ``call`` encloses it: the nearest earlier event of the same
  program run that is placed ``direct`` and is not shared;
* else ``unattributed``.

A ``while`` event's own time (its duration less its body's events) goes to
its own phase. Only COMPLETE calls are read (``panel_sweep.py``'s rule: the
device's trace is cut at 4.56-4.76 million events, so the window's last call
may lack its end): a ``bench_call`` span counts where it holds as many
events as the fullest one. Times are a call's: summed over the complete
calls, divided by their number. Everything works on plain tuples, so the
arithmetic is checked on a hand-made event list and table
(benchmark/tests/test_phase_metrics.py).

None, and no wrong split, where the tree has no ``telemetry.programs`` (a
tree before PR 35), the run dispatched nothing through ``telemetry.call``,
the trace has no device plane, or the table is ``stale``: the executable
came from a persistent cache an older tree wrote (jax keeps metadata out of
the cache key), so it carries that tree's scopes, or none.
"""

import bisect
import functools
import time

import panel_sweep
import span_reduce
import trace_reduce

KEY = "phase_split"
UNATTRIBUTED = "unattributed"
#: how far up the operands an instruction without a phase looks for one
OPERAND_DEPTH = 3
GIB = float(2 ** 30)
MIB = float(2 ** 20)


def instruction(name: str) -> str:
    """The instruction's name of a device event's name (its HLO text)."""
    return name.partition(" = ")[0].lstrip("%")


@functools.lru_cache(maxsize=1 << 18)
def name_parts(name: str):
    """``(instruction, opcode)`` of an event's name, kept: millions of events
    carry a few hundred thousand distinct HLO texts."""
    return instruction(name), trace_reduce.parse_op(name)[1]


def resolve(table: dict, inst: str, depth: int = OPERAND_DEPTH):
    """``(phase, "direct" | "operand")`` of an instruction by the table
    alone, ``(None, None)`` where neither it nor an operand within ``depth``
    levels carries a phase."""
    memo = table.setdefault("_resolved", {})
    if inst in memo:
        return memo[inst]
    phase = table["phases"].get(inst)
    found = (phase, "direct") if phase is not None else (None, None)
    if phase is None and depth > 0:
        for operand in table["operands"].get(inst, ()):
            via = resolve(table, operand, depth - 1)[0]
            if via is not None:
                found = (via, "operand")
                break
    if depth == OPERAND_DEPTH:
        memo[inst] = found
    return found


def own_times(events):
    """``[(start, end, name, own ns, parent index or None)]`` of one line's
    events in start order: ``own`` is the duration less the part the nested
    events cover (``trace_reduce.self_times``' rule, kept per event)."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            e = min(e, out[parent][1])     # overlap without nesting: cut
            out[parent][3] -= e - s
        out.append([s, e, name, e - s, parent])
        stack.append(len(out) - 1)
    return [(s, e, name, max(own, 0), parent)
            for s, e, name, own, parent in out]


def shared_instructions(timed) -> set:
    """Instructions whose events cannot be trusted to be their own call
    site's: those the trace shows directly inside ``while`` events of more
    than one instruction (a body's instruction has one enclosing loop; a
    kernel emitted once and called from several loops shows up in each
    under the name it was emitted with)."""
    loops = {}
    for _s, _e, name, _own, parent in timed:
        if parent is not None:
            loop, opcode = name_parts(timed[parent][2])
            if opcode == "while":
                loops.setdefault(name, set()).add(loop)
    return {name_parts(name)[0] for name, seen in loops.items()
            if len(seen) > 1}


def place(events, table: dict) -> dict:
    """``{(phase, placement): ns}`` of the events of one run of the table's
    program (``placement``: ``direct`` / ``operand`` / ``call`` /
    ``neighbour``; ``(unattributed, unattributed)`` for what no rule
    places): the values sum to the events' own time."""
    timed = own_times(events)
    shared = shared_instructions(timed)
    out = {}
    inherited = []              # per event: the phase of the innermost
    last_direct = None          # enclosing ``call`` event that has one
    for _s, _e, name, own, parent in timed:
        inst, opcode = name_parts(name)
        phase, how = resolve(table, inst)
        around = inherited[parent] if parent is not None else None
        inherited.append(phase if opcode == "call" and phase is not None
                         else around)
        if around is not None:
            phase, how = around, "call"
        elif inst in shared:
            phase, how = last_direct, "neighbour"
        elif how == "direct":
            last_direct = phase
        key = (phase, how) if phase is not None \
            else (UNATTRIBUTED, UNATTRIBUTED)
        out[key] = out.get(key, 0) + own
    return out


def by_start(events):
    """``(events sorted by start, their starts)``: what :func:`between`
    slices."""
    events = sorted(events, key=lambda ev: ev[0])
    return events, [ev[0] for ev in events]


def between(events, starts, lo, hi):
    """The events that start in ``[lo, hi)``, cut at ``hi`` (a slice by
    bisection: the window's events are walked once a call, not once a call
    and a program)."""
    inside = events[bisect.bisect_left(starts, lo):
                    bisect.bisect_left(starts, hi)]
    return [ev if ev[1] <= hi else (ev[0], hi, ev[2]) for ev in inside]


def program_runs(module_events, events, span):
    """``{(module name, run index): [events]}`` of the operations that start
    inside ``span``, each given to the program run (``XLA Modules`` event)
    that encloses its start; ``("?", -1)`` for operations outside every
    run."""
    events, starts = by_start(events)
    runs = sorted((s, e, n.split("(")[0])
                  for s, e, n in trace_reduce.clip(module_events, span))
    out, at = {}, span[0]
    for i, (s, e, module) in enumerate(runs):
        stray = between(events, starts, at, s)
        if stray:
            out.setdefault(("?", -1), []).extend(stray)
        out[(module, i)] = between(events, starts, s, e)
        at = max(at, e)
    stray = between(events, starts, at, span[1])
    if stray:
        out.setdefault(("?", -1), []).extend(stray)
    return out


def entry_site(run):
    """``(site, telemetry)`` of the program the run dispatched through
    ``telemetry.call`` (each cell dispatches one; of several, the first),
    its executable asked for so that the ``dlaf_hbm_bytes`` gauges are
    set; ``(None, None)`` on a tree without ``telemetry.programs``, and
    where the executable cannot be had (said on stdout, never raised: a
    reader with nothing to read returns None)."""
    if "phase_site" in run:
        return run["phase_site"]
    run["phase_site"] = (None, None)
    try:
        from dlaf_tpu.obs import telemetry

        sites = telemetry.programs()
        if sites and telemetry.compiled(sites[0]) is not None:
            run["phase_site"] = (sites[0], telemetry)
    except (ImportError, AttributeError):
        pass                        # a tree before PR 35
    except Exception as exc:
        print(f"[phases] no executable: {exc!r}", flush=True)
    return run["phase_site"]


def hbm_bytes(run, what: str):
    """``dlaf_hbm_bytes{what, site}`` of the entry's program, read from the
    live registry (``run["counters"]`` was taken before any reader asked for
    the executable); None without one."""
    site, _telemetry = entry_site(run)
    if site is None:
        return None
    from dlaf_tpu import obs

    for m in obs.registry().snapshot():
        labels = m.get("labels", {})
        if m.get("name") == "dlaf_hbm_bytes" and labels.get("site") == site \
                and labels.get("what") == what:
            return float(m["value"])
    return None


def complete_calls(host_spans, window, events):
    """``(complete, all)``: ``[(start, end, events inside)]`` of the
    window's ``bench_call`` spans, and of those that hold as many events as
    the fullest (every call runs the same programs, so a whole one has the
    most)."""
    events, starts = by_start(events)
    calls = [(s, e, between(events, starts, s, e))
             for s, e in span_reduce.calls_of(host_spans)
             if s >= window[0] and e <= window[1]]
    most = max((len(c[2]) for c in calls), default=0)
    return [c for c in calls if c[2] and len(c[2]) >= 0.999 * most], calls


def split_calls(module_events, calls, table: dict) -> dict:
    """``{"phases": {phase: ms a call}, "placed": {phase: {placement: ms a
    call}}, "others": {module name: ms a call}, "program_ms", "calls"}``
    over ``calls = [(start, end, events)]``: the table's program by phase,
    every other program of the calls by its name."""
    placed, others = {}, {}
    for s, e, events in calls:
        for (module, _i), evs in program_runs(module_events, events,
                                              (s, e)).items():
            if module.startswith(table["module"]):
                for (phase, how), ns in place(evs, table).items():
                    row = placed.setdefault(phase, {})
                    row[how] = row.get(how, 0) + ns
            else:
                others[module] = others.get(module, 0) + sum(
                    t[3] for t in own_times(evs))
    scale = 1e6 * max(len(calls), 1)
    out = {"placed": {phase: {how: ns / scale for how, ns in row.items()}
                      for phase, row in placed.items()},
           "others": {k: v / scale for k, v in others.items()},
           "calls": len(calls)}
    out["phases"] = {phase: sum(row.values())
                     for phase, row in out["placed"].items()}
    out["program_ms"] = sum(out["phases"].values())
    return out


def split(run):
    """The phase split of the traced run (:func:`split_calls`' dict, with
    the table's ``site``), read once and kept on ``run``; None where there
    is nothing to read."""
    if KEY in run:
        return run[KEY]
    run[KEY] = None
    t0 = time.perf_counter()
    site, telemetry = entry_site(run)
    if site is None:
        return None
    try:
        table = telemetry.phase_table(site)
    except Exception as exc:
        print(f"[phases] site={site} no phase table: {exc!r}", flush=True)
        return None
    if table is None:
        return None
    if table["stale"]:
        print(f"[phases] site={site} stale: the executable carries no phase "
              "scope (a persistent cache an older tree wrote); no phase_ms",
              flush=True)
        return None
    trace = run.get("trace")
    loaded = span_reduce.load_run(run) \
        if trace_reduce.worst_device(trace) else None
    if loaded is None or loaded[0] is None:
        return None                 # no device plane (a CPU trace)
    module_events, host_spans, window = loaded
    t1 = time.perf_counter()
    events = panel_sweep.device_events(span_reduce.trace_path(),
                                       trace["worst_device"])
    whole, calls = complete_calls(host_spans, window, events)
    del events
    if not whole:
        return None
    found = split_calls(module_events, whole, table)
    found["site"] = site
    run[KEY] = found
    print(f"[phases] site={site} module={table['module']} "
          f"calls_in_window={len(calls)} complete={len(whole)} "
          f"program_ms={found['program_ms']:.6g} "
          f"instructions={table['counts']} table_s={t1 - t0:.1f} "
          f"pass_s={time.perf_counter() - t1:.1f}", flush=True)
    for phase, ms in sorted(found["phases"].items(), key=lambda kv: -kv[1]):
        share = 100 * ms / found["program_ms"] if found["program_ms"] else 0
        print(f"[phases] {phase} ms_per_call={ms:.6g} share={share:.4g}% "
              + " ".join(f"via_{how}={v:.6g}" for how, v
                         in sorted(found["placed"][phase].items())),
              flush=True)
    print("[phases] other programs " + " ".join(
        f"{k}={v:.6g}" for k, v in sorted(found["others"].items(),
                                          key=lambda kv: -kv[1])),
          flush=True)
    return found
