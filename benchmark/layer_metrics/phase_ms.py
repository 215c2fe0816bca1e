"""Own device time of one call in one phase of the entry's step builder, in
milliseconds: ``phase_ms.panel``, ``.strip``, ``.bulk`` (the Cholesky
builders and the distributed solve), ``.larft``, ``.w``, ``.update`` (the
reduction to band), ``.unattributed`` (the program's time no rule places).
The least busy device's ``XLA Ops`` events of the window's complete calls
are joined by instruction name to ``telemetry.phase_table(site)`` of the
program the run dispatched (``benchmark/phase_table.py`` says how an event
is placed); the seven entries share one pass over the xplane. None where the
tree has no phase table, the table is ``stale`` (an executable from an older
tree's persistent cache), the trace has no device plane, or the program
carries no such phase."""

import phase_table


def read(run, name):
    found = phase_table.split(run)
    return found["phases"].get(name.split(".", 1)[1]) if found else None
