"""Share of the least busy device's own operation time spent inside the
panel factorizations of a reduction to band, in percent: the Householder
column sweep (``tile_ops/qr_panel.py:householder_qr``, one ``fori_loop`` a
panel) and the T factor's triangular inverse (``tile_ops/lapack.py:larft``),
found in the run's xplane by structure and summed over the window's complete
calls (``benchmark/panel_sweep.py`` says how). The denominator is every
operation's own time on that device in those calls, loops' own time
included: the time the device was busy. None where the trace has no device
plane or no such loop."""

import panel_sweep


def read(run, name):
    found = panel_sweep.sweep(run)
    return 100.0 * found["panel_ns"] / found["own_ns"] if found else None
