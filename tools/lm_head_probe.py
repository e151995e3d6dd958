"""What the dense LM head + loss costs on one chip, XLA's operations against
the Pallas kernels of `ops/pallas_xent.py`, at one shape.

Each form is ``jax.value_and_grad`` of `ops/xent.py::dense_xent_mean` with
respect to the hidden states, the head and the bias (what a train step runs
of it), traced over ``--calls`` calls; chip 0's operations are summed by
label and printed in milliseconds a call, longest first.

  xla            XLA's operations (`pallas_xent.plan` says no)
  kernels        the two kernels at each of ``--tiles`` (``plan``: the
                 module's own; else ``ROWSxCOLSxSUB`` for both kernels, or
                 ``FWD+DX`` in that form), the head read as it is stored
                 (float32: cast a tile at a time in VMEM)

``--head-dtype bfloat16`` hands every form a bf16 head.

    python3 tools/lm_head_probe.py --tiles plan,2048x1024x256+1024x2048x128

One JSON line per form on stdout. Needs the chip: times from a CPU run mean
nothing, and the tool refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark import trace_reduce
from lstm_tensorspark_tpu.ops import pallas_xent, xent


def measure(fn, args, calls: int) -> dict:
    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        chip = trace_reduce.load(trace_reduce.find_xplane(d)).chips[0]
    lo, hi = chip.ops[0].start, chip.ops[-1].end
    by_op = trace_reduce.op_seconds(chip, lo, hi)
    ms = {k: round(1e3 * s / calls, 4) for k, s in
          sorted(by_op.items(), key=lambda kv: -kv[1]) if 1e3 * s / calls >= 0.01}
    return {"busy_ms": round(1e3 * trace_reduce.busy_seconds(chip, lo, hi)
                             / calls, 4),
            "ops_ms": ms, "loss": float(out[0])}


def plan_of(tiles: str, *args, **kwargs):
    """`pallas_xent.plan` on the TPU, with the tiles ``tiles`` names."""
    real = REAL_PLAN(*args, **kwargs)
    if tiles == "plan":
        return real
    fwd, _, dx = tiles.partition("+")
    fwd, dx = (pallas_xent.Tiles(*(int(x) for x in t.split("x")))
               for t in (fwd, dx or fwd))
    return real._replace(fwd=fwd, dx=dx)


REAL_PLAN = pallas_xent.plan


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--vocab", type=int, default=50_000)
    ap.add_argument("--head-dtype", default="float32")
    ap.add_argument("--tiles", default="plan")
    ap.add_argument("--forms", default="xla,kernels")
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 1
    n, h, v = args.rows, args.hidden, args.vocab
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    ys = jax.random.normal(k[0], (64, n // 64, h), jnp.float32)
    head = (jax.random.normal(k[1], (h, v), jnp.float32) * 0.05
            ).astype(args.head_dtype)
    bias = jax.random.normal(k[2], (v,), jnp.float32) * 0.1
    targets = jax.random.randint(k[3], (64, n // 64), 0, v, jnp.int32)
    for form in args.forms.split(","):
        for tiles in (args.tiles.split(",") if form != "xla" else ["-"]):
            if form == "xla":
                pallas_xent.plan = lambda *a, **kw: None
            else:
                pallas_xent.plan = (
                    lambda *a, _t=tiles, **kw: plan_of(_t, *a, **kw))
            fn = jax.jit(jax.value_and_grad(  # a fresh trace a form
                lambda y, w, b, t: xent.dense_xent_mean(y, w, b, t,
                                                        jnp.bfloat16),
                argnums=(0, 1, 2)))
            try:
                row = measure(fn, (ys, head, bias, targets), args.calls)
            except Exception as e:  # a tile that does not fit: say so, go on
                row = {"error": repr(e)[:600]}
            pallas_xent.plan = REAL_PLAN
            print(json.dumps({"form": form, "tiles": tiles,
                              "head_dtype": args.head_dtype,
                              "shape": [n, h, v],
                              "device_kind": device.device_kind, **row}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
