"""Shared miniapp option scaffolding.

TPU-native counterpart of the reference's
``miniapp/include/dlaf/miniapp/options.h:38-338`` (``MiniappOptions``: grid
rows/cols, nruns, nwarmups, check-result mode, backend, element type) and the
string->template dispatch of ``dispatch.h:1-75`` (here: string -> dtype/
backend values). Every miniapp parses these plus its own size options and the
``--dlaf:*`` runtime options (forwarded to :mod:`dlaf_tpu.config`).
"""

from __future__ import annotations

import argparse
import dataclasses
import enum

import numpy as np

from ..types import ELEMENT_TYPES


class CheckIterFreq(enum.Enum):
    """``--check-result`` mode (reference ``options.h`` CheckIterFreq)."""

    NONE = "none"
    LAST = "last"
    ALL = "all"


@dataclasses.dataclass
class MiniappOptions:
    grid_rows: int = 1
    grid_cols: int = 1
    nruns: int = 1
    nwarmups: int = 1
    check: CheckIterFreq = CheckIterFreq.NONE
    dtype: type = np.float64
    backend: str = "default"  # 'default' | 'mc' | 'tpu'


def add_miniapp_arguments(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--grid-rows", type=int, default=1,
                        help="process grid rows (reference --grid-rows)")
    parser.add_argument("--grid-cols", type=int, default=1,
                        help="process grid cols (reference --grid-cols)")
    parser.add_argument("--nruns", type=int, default=1, help="timed runs")
    parser.add_argument("--nwarmups", type=int, default=1, help="warmup runs")
    parser.add_argument("--check-result", choices=[c.value for c in CheckIterFreq],
                        default="none", help="verify the result")
    parser.add_argument("--type", choices=list(ELEMENT_TYPES), default="d",
                        help="element type s/d/c/z (reference --type)")
    parser.add_argument("--backend", choices=["default", "mc", "tpu"],
                        default="default",
                        help="'mc' forces the XLA-CPU backend, 'tpu' a TPU device")


def announce_donation() -> None:
    """Print the donation marker line. Miniapps whose timed runs donate
    their per-run input copies (the reference's in-place semantics) call
    this once before the run loop, so a log says whether the measured
    program aliased its input (donated and undonated timings must stay
    distinguishable)."""
    print("[meta] donate=1", flush=True)


def parse_miniapp_options(args: argparse.Namespace) -> MiniappOptions:
    return MiniappOptions(
        grid_rows=args.grid_rows, grid_cols=args.grid_cols,
        nruns=args.nruns, nwarmups=args.nwarmups,
        check=CheckIterFreq(args.check_result),
        dtype=ELEMENT_TYPES[args.type], backend=args.backend)


def select_devices(opts: MiniappOptions):
    """Device list for the requested backend. The platform comes from
    JAX's own rules (``JAX_PLATFORMS``, else discovery); ``--backend mc``
    takes the process's CPU devices, ``--backend tpu`` insists that the
    default backend is a TPU."""
    import jax

    devs = jax.devices("cpu") if opts.backend == "mc" else jax.devices()
    if opts.backend == "tpu" and devs[0].platform != "tpu":
        raise SystemExit("--backend tpu requested but the visible devices "
                         f"are {devs[0].platform!r}")
    need = opts.grid_rows * opts.grid_cols
    if len(devs) < need:
        raise SystemExit(
            f"grid {opts.grid_rows}x{opts.grid_cols} needs {need} devices but "
            f"only {len(devs)} are visible; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} with "
            f"JAX_PLATFORMS=cpu to emulate, or shrink the grid")
    return devs
