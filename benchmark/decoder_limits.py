#!/usr/bin/env python3
"""The two readings each limit of the ``decoder_serve`` check lies between,
on the chip, in one run of the cell (not part of a run; its result goes into
`decoder_serve_cell.LIMITS`' comment and PERF.md section 2 by hand):

    python benchmark/decoder_limits.py --workload dsv2-serve-resident --seed 11 --seconds 30

The first reading is the cell's own judgement (the bfloat16 program against
the float32 reference). Each control then judges the same tokens against a
reference computed in the nearest precision BELOW the configuration's — a
program that computed that part so would differ from the sound reference by
as much:

- ``bf16_residual``: the residual stream rounded to bfloat16 after every add
  (the configuration states float32);
- ``bf16_router``: the router's input and weights rounded to bfloat16
  (float32 stated);
- ``fp8_routed_experts``: every expert layer's routed experts rounded to
  float8_e4m3fn and brought back (bfloat16 stated);
- ``fp8_everything``: every matrix so.

The fp8 controls must come out not correct by one of the cell's limits. The
two bfloat16 ones read what the sound program reads (0.047 / 0.157 and
0.045 / 0.167 beside 0.045 / 0.158, PR 29): the program's matmuls round
their inputs to bfloat16 already, so at the published widths the check
cannot see them (they fail the float32 limits at the small size,
`tests/test_decoder_check.py`); they stay here so that a later check can be
held to them. The fp8 controls replace the weights in place, tensor by
tensor (a second copy of 7.5 GB of experts does not fit beside the first),
so they come last.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import decoder_serve_cell
    import run
    from lstm_tensorspark_tpu.utils.compile_cache import place_compile_cache
    from reference import deepseek_v2 as reference

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("JAX found no TPU: the readings are the chip's")
    place_compile_cache()
    _, _, config, traffic = run.load_cell(args.workload)
    cell = run.Cell(name=args.workload, config=config, traffic=traffic, chips=1,
                    seed=args.seed % (2 ** 31 - 1), seconds=args.seconds,
                    trace=False, t0=time.perf_counter(),
                    workdir=tempfile.mkdtemp(prefix="limits-"), rehearsal=False)

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    @contextlib.contextmanager
    def patched(name, make):
        sound = getattr(reference, name)
        setattr(reference, name, make(sound))
        try:
            yield
        finally:
            setattr(reference, name, sound)

    def residual_rounded(part):
        # x + (round(x + part(x)) - x) is the rounded sum, to float32's own
        # rounding: `forward` adds what this returns to ``x``
        return lambda layer, model, x, *a, **k: (
            bf16(x + part(layer, model, x, *a, **k)) - x)

    @contextlib.contextmanager
    def bf16_residual(params):
        with patched("attention", residual_rounded), \
                patched("mlp", residual_rounded):
            yield params

    @contextlib.contextmanager
    def bf16_router(params):
        def rounded(route):
            return lambda xn, layer, model: route(
                bf16(xn), dict(layer, w_router=bf16(layer["w_router"])), model)
        with patched("route", rounded):
            yield params

    def to_fp8(tree, keys=None):
        """Round the matrices of ``tree`` (a dict of arrays; ``keys``: only
        those) to fp8 IN PLACE: each replaced array is deleted."""
        for k, x in tree.items():
            if getattr(x, "ndim", 0) >= 2 and (keys is None or k in keys):
                tree[k] = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                tree[k].block_until_ready()
                x.delete()

    @contextlib.contextmanager
    def fp8_routed_experts(params):
        for layer in params["layers"]:
            if "w_router" in layer:
                to_fp8(layer, ("w_gate_up", "w_down"))
        yield params

    @contextlib.contextmanager
    def fp8_everything(params):
        for layer in params["layers"]:
            to_fp8(layer)
        to_fp8(params, ("embedding", "head"))
        yield params

    controls = {"bf16_residual": bf16_residual, "bf16_router": bf16_router,
                "fp8_routed_experts": fp8_routed_experts,
                "fp8_everything": fp8_everything}
    result = decoder_serve_cell.run(cell, controls)
    limits = decoder_serve_cell.LIMITS
    keys = ("logit_q50", "logit_q90", "logit_max", "greedy_q90", "greedy_max",
            "exact_picks", "ok", "tokens")
    for name in ("reference", *(f"reference_{c}" for c in controls)):
        print(json.dumps({name: {k: result["samples"][name][k] for k in keys}}))
    print(json.dumps({"limits": limits,
                      "checks": result["checks"],
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
