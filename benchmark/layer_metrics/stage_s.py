"""``stage_s.<stage>``: median over the traced calls of the ``PhaseTimer``
wall of the eigensolver stage ``stage.<stage>`` (each stage is fenced)."""

import statistics


def read(run, name):
    stage = "stage." + name.split(".", 1)[1]
    walls = [w[stage] for w in run.get("stage_walls", []) if stage in w]
    return statistics.median(walls) if walls else None
