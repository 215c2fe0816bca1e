"""Temporaries of the program the run dispatched, in GiB:
``dlaf_hbm_bytes{what=temp, site}`` from the executable's
``memory_analysis()`` (the allocator's own account of the workspace beside
arguments and result), set when ``benchmark/phase_table.py`` asks
``telemetry.compiled(site)`` for the entry's program. What ``peak_hbm_gib``
moves with once the harness's input staging is not the peak. None on a tree
whose entries do not remember their program (before PR 35)."""

import phase_table


def read(run, name):
    value = phase_table.hbm_bytes(run, "temp")
    return None if value is None else value / phase_table.GIB
