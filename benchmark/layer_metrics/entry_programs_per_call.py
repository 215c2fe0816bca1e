"""Device programs an entry point dispatches per call on its local branch:
``dlaf_entry_programs_total{entry}`` over ``dlaf_entry_calls_total{entry}``,
both summed over the entries that have both (an entry that counts its
calls but not yet its programs, or runs its distributed branch, is left
out of both sums). The library counts a program where it dispatches it
and a call in ``obs.entry_span``, while the metrics sink is on; warm-up,
checks and window alike, so the length of the run does not enter. The
local Cholesky reads 1.0 since PR 30 (tiles -> global, the factorization
and global -> tiles are one program); 3 before it, had it been counted.
None where either counter is absent (the parent)."""

PROGRAMS = "dlaf_entry_programs_total"
CALLS = "dlaf_entry_calls_total"


def read(run, name):
    by_entry = {PROGRAMS: {}, CALLS: {}}
    for m in run.get("counters") or ():
        entry = m.get("labels", {}).get("entry")
        if m.get("name") in by_entry and entry is not None:
            tally = by_entry[m["name"]]
            tally[entry] = tally.get(entry, 0.0) + m["value"]
    both = by_entry[PROGRAMS].keys() & by_entry[CALLS].keys()
    calls = sum(by_entry[CALLS][e] for e in both)
    if not calls:
        return None
    return sum(by_entry[PROGRAMS][e] for e in both) / calls
