"""Device time of one Householder column step of a reduction to band's
panel factorizations, in microseconds: the time inside the panel loops of
the traced window (``panel_sweep.sweep``: the column sweeps, 128 steps
a panel, with the T factor's inversion) over the calls of the window and
over the columns one call sweeps, ``dlaf_red2band_panel_columns_total{form}``
summed over ``form``. The library counts the columns from shapes when it
traces a builder, and a cell's program is traced once a process, so the
counter is one call's (8064 at N=8192, band 128). A column step is a
masked reduction over a column, a rank-1 update of the (m, band) panel and
the emulated-f64 dot between them: sequential, so this time times the
column count is a floor of the call. None where the registry holds no such
counter (a tree before PR 33) or the trace no panel loop."""

import panel_sweep

COLUMNS = "dlaf_red2band_panel_columns_total"


def read(run, name):
    columns = sum(m["value"] for m in run.get("counters") or ()
                  if m.get("name") == COLUMNS)
    found = panel_sweep.sweep(run)
    if not columns or not found:
        return None
    return found["panel_ns"] / found["calls"] / columns / 1e3
