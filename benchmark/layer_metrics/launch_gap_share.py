"""Share of the traced window in which no *program* was executing on the
least busy device: 1 - union of the ``XLA Modules`` intervals / window. The
denominator and the device are ``device_idle_share``'s, and a program's
interval encloses its operations, so this is never more than that: the
difference is idle inside programs (the compiler's and the step builder's),
this part is idle between them (the host's: dispatch, fence, fresh copy).

Also prints the table a ``perf_opt`` issue reads, on ``[launch_gaps]`` lines
and into ``benchmark/out/<cell>/launch_gaps.json``: the between-program idle
of one call by the innermost ``stage.*`` span the program had open."""

import json
import os

import span_reduce


def read(run, name):
    loaded = span_reduce.load_run(run)
    if loaded is None or loaded[0] is None:
        return None
    modules, host_spans, window = loaded
    table = span_reduce.gap_table(modules, host_spans, window)
    for label, per_call_s, gaps_per_call, longest_s in table:
        print(f"[launch_gaps] label={label!r} per_call_s={per_call_s:.6g} "
              f"gaps_per_call={gaps_per_call:.3g} longest_s={longest_s:.6g}",
              flush=True)
    out = os.path.join(os.path.dirname(os.environ["DLAF_METRICS_PATH"]),
                       "launch_gaps.json")
    with open(out, "w") as f:
        json.dump({"columns": ["label", "per_call_s", "gaps_per_call",
                               "longest_s"], "rows": table}, f, indent=1)
    return span_reduce.gap_share(modules, window)
