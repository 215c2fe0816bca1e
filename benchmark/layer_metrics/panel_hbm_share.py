"""The panel sweep's share of its memory roofline, in percent: the bytes
the panel factorizations of one call have to move, over the device time
inside the panel loops of one call (``panel_sweep.sweep``), over the
chip's memory bandwidth (``peaks.json``, ``hbm_bytes_per_s``).

``panel_bytes`` is the least any implementation moves: panel ``k`` of a
reduction of order ``n`` to ``band`` sub-diagonals is the ``n - band (k +
1)`` rows below the band of ``band`` columns of float64; it is read once
and its reflectors are written once. (The flops of the sweep, ``2 m band^2``
a panel, are 1e-5 of the chip's peak time: the sweep is bound by memory or
by latency, never by the MXU.) The same work whatever implements it, so the
share cannot pass 100%; today's sweep passes over the panel several times a
column, 128 columns a panel, and reads far below it. None where the trace
holds no panel loop or the device has no row in ``peaks.json``."""

import json
import os

import panel_sweep

HERE = os.path.dirname(os.path.abspath(__file__))


def panel_bytes(n: int, band: int, itemsize: int = 8) -> int:
    """Bytes of the panels of one call, each read once and written once."""
    panels = max(-(-n // band) - 1, 0)
    return 2 * itemsize * band * sum(n - band * (k + 1)
                                     for k in range(panels))


def read(run, name):
    found = panel_sweep.sweep(run)
    config = run.get("config") or {}
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        peak = json.load(f).get((run.get("device") or {}).get("kind"))
    if not found or not peak:
        return None
    seconds = found["panel_ns"] / found["calls"] / 1e9
    moved = panel_bytes(config["n"], config["args"]["band_size"])
    return 100.0 * moved / seconds / peak["hbm_bytes_per_s"]
