"""Resident code of the program the run dispatched, in MiB:
``dlaf_hbm_bytes{what=code, site}`` from the executable's
``memory_analysis()`` (``generated_code_size_in_bytes``), set when
``benchmark/phase_table.py`` asks ``telemetry.compiled(site)`` for the
entry's program. Every loaded program's code is in ``peak_hbm_gib``; on
``red2band_d_n8192_1x1`` it is all of that metric that moves (291 MiB with
repeated kernels shared, 398 inlined: PERF.md, PR 34). None on a tree whose
entries do not remember their program (before PR 35)."""

import phase_table


def read(run, name):
    value = phase_table.hbm_bytes(run, "code")
    return None if value is None else value / phase_table.MIB
