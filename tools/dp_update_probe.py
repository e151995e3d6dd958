"""What sharding one leaf's DP weight update costs and saves, by leaf size.

For each float32 leaf shape, two programs over all the chips of the host,
traced, chip 0's operations summed by opcode per iteration:

  replicated  all-reduce the gradient, Adam on the whole leaf on every chip
  sharded     the leaf and its moments live 1/dp a chip: all-gather the
              parameter, reduce-scatter the gradient, Adam on the share
              (along each dimension `--dims` names; default: the one
              `train/sharded_update.py::shard_dim` picks)
  --gather-last  the sharded form with a whole, replicated parameter that
              the new share is gathered back into: a collective cannot
              write a donated buffer, so the leaf is copied in and out

`MIN_SHARDED_BYTES` there is set from this tool's numbers (PERF.md §6).

    chiprun --chips 4 -- python3 tools/dp_update_probe.py \
        --shapes 1024x4096,4096x4096,50000x1024 --dims rule,0

One JSON line per (shape, form) on stdout. Needs the chips: times from a
CPU run mean nothing, and the tool refuses to run without a TPU.
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
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import trace_reduce
from lstm_tensorspark_tpu.train import sharded_update

AXIS = "data"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather")


def build(mesh, shape, dim, gather_last=False):
    """``run(p, m, v, g) -> (p, m, v, used)``: one update of one leaf;
    ``dim`` None is the replicated form. ``g`` is [dp, *shape], a row a
    chip, scaled by a sum over the whole parameter so that the gathered
    leaf is read as a forward pass would read it."""
    dp = mesh.shape[AXIS]
    adam = optax.scale_by_adam()
    part = sharded_update.Partition(AXIS, (dim,))
    mspec = P() if dim is None else P(*([None] * dim), AXIS)
    pspec = P() if gather_last else mspec

    def per_shard(p, m, v, g):
        whole = p if gather_last else part.gather(p)
        used = jnp.sum(whole[:8])
        (grad,) = jax.tree.leaves(part.reduce([g[0]]))
        if gather_last and dim is not None:
            n = p.shape[dim] // dp
            p = lax.dynamic_slice_in_dim(
                p, lax.axis_index(AXIS) * n, n, axis=dim)
        state = optax.ScaleByAdamState(jnp.ones((), jnp.int32), m, v)
        upd, state = adam.update(grad, state)
        p = p - 1e-3 * upd
        if gather_last:
            p = part.gather(p)
        return p, state.mu, state.nu, used

    return jax.jit(shard_map(
        per_shard, mesh=mesh, in_specs=(pspec, mspec, mspec, P(AXIS)),
        out_specs=(pspec, mspec, mspec, P()), check_vma=False),
        donate_argnums=(0, 1, 2)), pspec, mspec


def measure(mesh, shape, dim, calls, gather_last=False):
    run, pspec, mspec = build(mesh, shape, dim, gather_last)
    dp = mesh.shape[AXIS]
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))  # noqa: E731
    p = put(jnp.ones(shape, jnp.float32), pspec)
    m, v = (put(jnp.zeros(shape, jnp.float32), mspec) for _ in range(2))
    g = put(jnp.full((dp, *shape), 1e-3, jnp.float32), P(AXIS))
    p, m, v, _ = run(p, m, v, g)  # compile + warm
    jax.block_until_ready(p)
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        for _ in range(calls):
            p, m, v, _ = run(p, m, v, g)
        jax.block_until_ready(p)
        jax.profiler.stop_trace()
        chip = trace_reduce.load(trace_reduce.find_xplane(d)).chips[0]
    n = calls
    lo, hi = chip.ops[0].start, chip.ops[-1].end
    by = trace_reduce.seconds_by_opcode(chip, lo, hi)
    ms = {k: round(1e3 * s / n, 4) for k, s in by.items()
          if not k.startswith("async:") and 1e3 * s / n >= 0.001}
    coll = sum(s for k, s in ms.items() if k in COLLECTIVES)
    return {"collectives_ms": round(coll, 4),
            "update_ms": round(sum(ms.values()) - coll, 4),
            "busy_ms": round(1e3 * trace_reduce.busy_seconds(chip, lo, hi) / n,
                             4),
            "by_opcode_ms": ms}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="1024x4096,4096x4096,50000x1024")
    ap.add_argument("--dims", default="rule",
                    help="comma list of dimensions to shard along: 'rule' "
                         "(shard_dim's choice at any size) or an index")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--gather-last", action="store_true")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU, found {devices[0].platform}", file=sys.stderr)
        return 1
    mesh = Mesh(np.asarray(devices), (AXIS,))
    dp = len(devices)
    sharded_update.MIN_SHARDED_BYTES = 0  # the rule's dimension, any size
    for text in args.shapes.split(","):
        shape = tuple(int(n) for n in text.split("x"))
        dims = [None]
        for d in args.dims.split(","):
            d = (sharded_update.shard_dim(shape, 4, dp) if d == "rule"
                 else int(d))
            if d is not None and d not in dims:
                dims.append(d)
        for dim in dims:
            print(json.dumps({
                "shape": list(shape), "mbytes": round(4e-6 * np.prod(shape), 1),
                "form": "replicated" if dim is None else (
                    f"sharded_dim{dim}"
                    + ("_gather_last" if args.gather_last else "")),
                "device_kind": devices[0].device_kind, "chips": dp,
                **measure(mesh, shape, dim, args.calls, args.gather_last)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
