#!/usr/bin/env python3
"""The readings each limit of the ``gqa_decoder_serve`` check lies between,
and the faults it must refuse, on the chip, in one run of the cell (not part
of a run; its result goes into `gqa_decoder_serve_cell.LIMITS`' comment and
PERF.md section 2 by hand):

    python benchmark/gqa_decoder_limits.py --workload mellum2-serve-longctx --seed 11 --seconds 20
    python benchmark/gqa_decoder_limits.py --workload mellum2-serve-longctx --fault window_ignored

Without ``--fault``: the first reading is the cell's own judgement (the
bfloat16 program against the float32 reference); each control then judges
the same tokens against a reference computed with a part in the nearest
precision BELOW the configuration's, as `decoder_limits.py` does (a program
that computed that part so would differ from the sound reference by as
much): ``fp8_w_o`` (every layer's output projection rounded to float8_e4m3fn
and back), ``fp8_experts`` (every expert), ``fp8_everything``. They replace
the weights in place, tensor by tensor, so they come last and in this order.

With ``--fault``: the PROGRAM is built with the fault (the configuration or
one function of `models/decoder.py` changed before the engine is built) and
judged against the untouched reference; the run must come out not correct by
``reference``: ``window_ignored`` (the sliding layers' kernel mask has no
window; the cache still returns the pages), ``plain_rope_in_full_layers``.
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

FAULTS = ("window_ignored", "plain_rope_in_full_layers")


def install_fault(name: str) -> None:
    from lstm_tensorspark_tpu.models import decoder
    from lstm_tensorspark_tpu.ops import paged_attention

    if name == "window_ignored":
        sound = paged_attention.paged_attention
        paged_attention.paged_attention = lambda *a, window=None, **k: sound(
            *a, window=None, **k)
    elif name == "plain_rope_in_full_layers":
        sound = decoder.rotated_query_key
        decoder.rotated_query_key = lambda q, k, pos, cfg, kind: sound(
            q, k, pos, cfg, 1)
    else:
        raise SystemExit(f"no fault {name!r} (has: {', '.join(FAULTS)})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", choices=FAULTS, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import gqa_decoder_serve_cell as cellmod
    import run
    from lstm_tensorspark_tpu.utils.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("JAX found no TPU: the readings are the chip's")
    place_compile_cache()
    if args.fault:
        install_fault(args.fault)
    _, _, config, traffic = run.load_cell(args.workload)
    cell = run.Cell(name=args.workload, config=config, traffic=traffic, chips=1,
                    seed=args.seed % (2 ** 31 - 1), seconds=args.seconds,
                    trace=False, t0=time.perf_counter(),
                    workdir=tempfile.mkdtemp(prefix="limits-"), rehearsal=False)

    def to_fp8(tree, keys=None):
        """Round the matrices of ``tree`` (a dict of arrays; ``keys``: only
        those) to fp8 IN PLACE: each replaced array is deleted."""
        for k, x in tree.items():
            if getattr(x, "ndim", 0) >= 2 and (keys is None or k in keys):
                tree[k] = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                tree[k].block_until_ready()
                x.delete()

    def control(keys, ends=False):
        @contextlib.contextmanager
        def rounded(params):
            for layer in params["layers"]:
                to_fp8(layer, keys)
            if ends:
                to_fp8(params, ("embedding", "head"))
            yield params
        return rounded

    controls = {} if args.fault else {
        "fp8_w_o": control(("w_o",)),
        "fp8_experts": control(("w_gate_up", "w_down")),
        "fp8_everything": control(None, ends=True)}
    result = cellmod.run(cell, controls)
    keys = ("logit_q50", "logit_q90", "logit_max", "greedy_q90", "greedy_max",
            "exact_picks", "ok", "tokens")
    for name in ("reference", *(f"reference_{c}" for c in controls)):
        print(json.dumps({name: {k: result["samples"][name][k] for k in keys}}))
    print(json.dumps({"fault": args.fault, "limits": cellmod.LIMITS,
                      "correct": result["correct"], "checks": result["checks"],
                      "wall_s": result["samples"]["wall_s"],
                      "per_request": result["samples"]["reference"]["per_request"],
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
