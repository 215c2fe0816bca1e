#!/usr/bin/env python3
"""The one-pass peel of a deep product's wide operand against the chain, bit
for bit, on the chip (``dlaf_tpu/tile_ops/ozaki.py:_peel_stack``).

    python3 scripts/peel_chip_check.py [--out chiprun_out/peel_check.json]

For ``s`` = 7 and 8, at shapes that cover the kernel's blocking (strips,
edge blocks on both axes, the chase's 255 rows, a block narrower than a
strip):

1. ``stack``: ``_peel_stack`` against ``jnp.stack(_peel_slices(xn, s))`` on
   adversarial blocks sent from the host (the chip's transfer of an f64
   array need not normalize its double-f32 pair) and on blocks normalized
   on the chip (``_normalize`` of rows over twelve decades);
2. ``exact``: the kernel on double-f32 planes whose sum IEEE binary64 holds
   exactly (ties of the pair and un-normalized pairs among them) against
   :func:`host_chain`, the chain in binary64 on the host, which is exact on
   such sums;
3. ``product``: ``matmul_f64``'s ``"slices"`` form, A wide and B wide,
   against the same product with the wide operand peeled by the chain.

Prints one JSON object of the elements (products) that differ, writes it to
``--out``, and exits 1 if any differs. It refuses to run off a TPU unless
``--cpu`` is given (tests/test_ozaki.py rehearses it so at small shapes:
there ``_peel_stack`` is the chain and the kernel runs in interpret mode).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHAPES = [(255, 4096), (128, 255), (300, 1100), (2048, 2048), (255, 640)]
#: (m, k, n) of deep products: A wide, B wide, and a tie (B wide)
PRODUCTS = [(1024, 2048, 128), (128, 255, 4096), (128, 8192, 128)]


def adversarial_block(shape, seed):
    """A normalized f64 block, entries in ``[-1/2, 1/2]``, mixing the values
    a peel can get wrong: exact round-to-nearest ties at every slice's
    scale (``(j + 1/2) 2^-7(t+1)``), the same ties broken 53 bits down, f64
    values of a few scattered bits (their residuals land on exact f32
    ties), magnitudes below every slice (``2^-70 .. 2^-40``), ``+-1/2``,
    uniform entries, and every seventh row zero."""
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape))
    t = rng.integers(0, 8, n)
    tie = (rng.integers(-64, 64, n) + 0.5) * 2.0 ** (-7.0 * (t + 1))
    sparse = sum(rng.choice([-1.0, 1.0], n)
                 * np.ldexp(1.0, rng.integers(0, 53, n))
                 for _ in range(4)) * 2.0 ** -55
    kinds = [rng.uniform(-0.5, 0.5, n), tie,
             tie + sparse * 2.0 ** (-7.0 * (t + 2)), sparse,
             rng.uniform(-0.5, 0.5, n) * 2.0 ** rng.integers(-70, -40, n),
             rng.choice([-0.5, 0.5], n)]
    x = np.choose(rng.integers(0, len(kinds), n), kinds)
    x = np.clip(x, -0.5, 0.5).reshape(shape)
    x[::7] = 0.0
    return x


def double_f32_pairs(shape, seed):
    """``(hi, lo)``, f32 planes whose sum IEEE binary64 holds exactly, in
    ``[-1/2, 1/2]``: 48-bit values split as an f64 array's planes; values
    whose residual is an exact tie at slice ``t`` (``I_t = j + 1/2`` after
    up to three exact slices), and the same ties broken up to 18 bits
    further down; ties of the pair itself (``lo = +-ulp(hi)/2``, ``hi``
    odd, and the same off by ``2^-20 ulp``); un-normalized pairs
    (``ulp(hi)/2 < |lo| <= 2 ulp(hi)``), a slice's tie among them held one
    ulp off with ``lo = -+ulp/2``; ``+-1/2``; magnitudes below ``2^-45``;
    every seventh row zero."""
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape))
    sign = rng.choice([-1.0, 1.0], n)

    def split(x):
        hi = x.astype(np.float32)
        return hi, (x - hi).astype(np.float32)

    def bits48(lo_exp, hi_exp):
        m = rng.integers(2 ** 47, 2 ** 48, n).astype(np.float64)
        return sign * np.ldexp(m, -48 - rng.integers(lo_exp, hi_exp, n))

    t = rng.integers(0, 8, n)
    tie = (rng.integers(-64, 64, n) + 0.5) * 2.0 ** (-7.0 * (t + 1))
    for back in range(1, 4):
        u = t - back
        tie = tie + np.where(u >= 0, rng.integers(-63, 64, n), 0) \
            * 2.0 ** (-7.0 * (u + 1))
    broken = tie + sign * 2.0 ** (-7.0 * (t + 1) - 1 - rng.integers(1, 19, n))
    odd = (rng.uniform(-0.49, 0.49, n).astype(np.float32).view(np.int32)
           | 1).view(np.float32)
    ulp = np.spacing(np.abs(odd))
    off = rng.choice([0.0, -1.0, 1.0], n) * ulp * np.float32(2.0 ** -20)
    pair_tie = (odd, (sign * ulp / 2 + off).astype(np.float32))
    loose_hi = rng.uniform(-0.49, 0.49, n).astype(np.float32)
    c = rng.integers(2 ** 19 + 1, 2 ** 21 + 1, n).astype(np.float64)
    loose = (loose_hi, (sign * c * 2.0 ** -20
                        * np.spacing(np.abs(loose_hi))).astype(np.float32))
    # a slice's tie held as an un-normalized pair, one ulp off with half an
    # ulp back: the pair rounds to the tie, its high plane off it (seen on
    # the chip, where a transfer left such pairs)
    at = (rng.integers(-64, 64, n) + 0.5) * 2.0 ** (-7.0 * (t + 1))
    step = sign * np.spacing(np.abs(at.astype(np.float32)))
    tie_pair = ((at + step).astype(np.float32),
                (-step / 2).astype(np.float32))
    half = (sign.astype(np.float32) / 2, np.zeros(n, np.float32))
    kinds = [split(bits48(1, 40)), split(tie), split(broken), pair_tie,
             loose, tie_pair, half, split(bits48(45, 70))]
    pick = rng.integers(0, len(kinds), n)
    hi = np.choose(pick, [k[0] for k in kinds]).reshape(shape)
    lo = np.choose(pick, [k[1] for k in kinds]).reshape(shape)
    hi[::7] = 0.0
    lo[::7] = 0.0
    x = hi.astype(np.float64) + lo.astype(np.float64)
    assert np.array_equal(x - hi.astype(np.float64), lo.astype(np.float64))
    assert np.abs(x).max() <= 0.5
    return hi, lo


def host_chain(x, s: int):
    """``_peel_slices`` in IEEE binary64 with numpy, independent of the
    library: the integer from the f32 round of ``r 2^7(t+1)``, the residual
    less the stored slice. Every residual is exact in binary64, so on a sum
    of two f32 planes this is the exact peel the kernel must give."""
    out, r = [], np.asarray(x, np.float64)
    for t in range(s):
        sc = 2.0 ** (7 * (t + 1))
        i = np.round((r * sc).astype(np.float32)).astype(np.int8)
        out.append(i)
        r = r - i.astype(np.float64) * (1.0 / sc)
    return np.stack(out)


def check(shapes=SHAPES, products=PRODUCTS, slices=(7, 8),
          interpret=False) -> dict:
    """``{"stack": .., "exact": .., "product": ..}``: per case the number of
    elements (of products: 0 or 1) that differ from the chain."""
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.tile_ops import ozaki as oz

    def differ(a, b):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape)
        return int((a != b).reshape(a.shape[0], -1).any(axis=0).sum())

    out = {"stack": {}, "exact": {}, "product": {}}
    normalize = jax.jit(lambda v: oz._normalize(v, oz._scale(v, -1)))
    for shape in shapes:
        rng = np.random.default_rng(sum(shape))
        scaled = rng.standard_normal(shape) \
            * 10.0 ** rng.integers(-6, 6, (shape[0], 1))
        for s in slices:
            one = jax.jit(lambda v, s=s: oz._peel_stack(v, s))
            chain = jax.jit(lambda v, s=s: jnp.stack(oz._peel_slices(v, s)))
            key = f"{shape[0]}x{shape[1]}-s{s}"
            for src, xn in (("adversarial", jnp.asarray(
                    adversarial_block(shape, sum(shape) + s))),
                    ("normalized", normalize(jnp.asarray(scaled)))):
                out["stack"][f"{key}-{src}"] = differ(one(xn), chain(xn))
            hi, lo = double_f32_pairs(shape, sum(shape) + s)
            kernel = jax.jit(lambda h, l, s=s: oz._peel_stack_tpu(
                h, l, s, interpret=interpret))
            out["exact"][key] = differ(
                kernel(jnp.asarray(hi), jnp.asarray(lo)),
                host_chain(hi.astype(np.float64) + lo, s))
    for m, k, n in products:
        rng = np.random.default_rng(m + k + n)
        a = jnp.asarray(rng.standard_normal((m, k))
                        * 10.0 ** rng.integers(-6, 6, (m, 1)))
        b = jnp.asarray(rng.standard_normal((k, n)))
        assert oz._sequenced_form(m, n, k, 7) == "slices"
        one_pass = np.asarray(jax.jit(
            lambda x, y: oz.matmul_f64(x, y, slices=7))(a, b))
        keep = oz._peel_stack
        oz._peel_stack = lambda xn, s: jnp.stack(oz._peel_slices(xn, s))
        try:
            by_chain = np.asarray(jax.jit(
                lambda x, y: oz.matmul_f64(x, y, slices=7))(a, b))
        finally:
            oz._peel_stack = keep
        out["product"][f"{m}x{k}x{n}"] = \
            int(one_pass.tobytes() != by_chain.tobytes())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "peel_check.json"))
    ap.add_argument("--cpu", action="store_true",
                    help="run off a TPU, the kernel in interpret mode")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_enable_x64", True)
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"refused: the first device is {device.platform}, not a TPU",
              flush=True)
        return 2
    result = check(interpret=device.platform != "tpu")
    result["device"] = {"platform": device.platform,
                        "kind": device.device_kind}
    bad = sum(v for part in ("stack", "exact", "product")
              for v in result[part].values())
    result["differing"] = bad
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
