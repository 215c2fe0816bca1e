"""``A = L L^H`` through ``dlaf_tpu.algorithms.cholesky`` at a size whose
local factorization is the scan-form program (``chol-d-n16384-nb512``: 32
block steps). Everything is ``ops/cholesky.py``'s — input, call, check, flop
model — but ``build`` first asks the library which builder its local branch
takes for this step count, and refuses at once where that is not the scan
form.

Why the cell has an op file of its own (PERF.md section 6, PR 31): on a tree
whose local route is the unrolled builder whatever the step count (every
commit before PR 31), this configuration spends 13 minutes compiling 32
unrolled steps (first call 779.8 s on one v5e, my chip run, PR 31) and the
process is then killed at the chip machine's 40 GiB of host memory: a run
that neither ends nor fails soon. Such a tree cannot run the configuration;
this file says so in seconds, before any input is made.
"""

from __future__ import annotations

import importlib
import importlib.util
import os


def _sibling(name: str):
    """``ops/<name>.py`` as a module (the harness loads op files by path;
    the directory is on no import path)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_ops_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_plain = _sibling("cholesky")
fresh, call, host, check, flops = (_plain.fresh, _plain.call, _plain.host,
                                   _plain.check, _plain.flops)


def local_step_form(steps: int):
    """The library's answer (``algorithms/cholesky.py:local_step_form``), or
    None on a tree that has no such question to ask."""
    mod = importlib.import_module("dlaf_tpu.algorithms.cholesky")
    ask = getattr(mod, "local_step_form", None)
    return None if ask is None else ask(steps)


def build(config: dict, seed: int, devices) -> dict:
    steps = -(-config["n"] // config["nb"])
    form = local_step_form(steps)
    if form != "scan":
        raise SystemExit(
            f"benchmark: this tree's local Cholesky takes the "
            f"{form or 'unrolled'} builder at {steps} block steps (n="
            f"{config['n']}, nb={config['nb']}); the configuration needs the "
            f"scan form (the unrolled program compiles for 13 minutes and "
            f"does not fit the host). Nothing was run.")
    return _plain.build(config, seed, devices)
