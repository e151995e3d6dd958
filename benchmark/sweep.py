#!/usr/bin/env python3
"""Find a serving cell's knee, once, when the cell is defined (not part of a
run; its result is written into the traffic file and PERF.md by hand).

    python benchmark/sweep.py --workload c5-serve-steady --rates 10,20,30,40 --seconds 12

One boot; then, rate after rate, the cell's own traffic at that rate through
the same generator as a run, each followed by a full drain. A rate is
sustained when nothing was shed or failed and the requests in flight at the
window's end are no more than at its middle, or no more than the scheduler
serves at once (``--max-active``: none is waiting then, and the two small
counts only differ by chance); the knee is the highest sustained rate of the
sweep.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    import flops
    import loadgen
    import run
    import serve_cell
    from lstm_tensorspark_tpu.utils.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("JAX found no TPU: a knee is a number of the chip")
    place_compile_cache()
    _, _, config, traffic = run.load_cell(args.workload)
    cell = run.Cell(name=args.workload, config=config, traffic=traffic, chips=1,
                    seed=args.seed, seconds=args.seconds, trace=False,
                    t0=time.perf_counter(), workdir=tempfile.gettempdir(),
                    rehearsal=False)
    sampling, params, cfg, server = serve_cell.build(cell)
    knee = None
    with server:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.traffic = {**traffic, "rate_per_s": rate}
            arrivals = loadgen.make_schedule(cell.traffic, args.seed + i,
                                             args.seconds, preroll_s=3.0)
            opens = time.perf_counter() + 3.25
            closes = opens + args.seconds
            loop = loadgen.OpenLoop(
                arrivals, serve_cell.make_send(
                    cell, server, sampling, give_up_at=lambda: closes + 30.0),
                workers=serve_cell.CLIENT_THREADS)
            c0 = serve_cell.counters(server)
            outcomes = loop.run(opens, drain_s=30.0)
            c1 = serve_cell.counters(server)
            n = serve_cell.window_numbers(outcomes, opens, closes)
            window, ok, ttft, gaps = n["window"], n["ok"], n["ttft"], n["gaps"]
            mid = loadgen.in_flight(outcomes, opens + args.seconds / 2)
            end = loadgen.in_flight(outcomes, closes)
            sustained = len(ok) == len(window) and (
                end <= mid or end <= c1["max_active"])
            if sustained:
                knee = rate
            print(json.dumps({
                "rate_per_s": rate, "sustained": sustained,
                "attempted": len(window), "failed": len(window) - len(ok),
                "errors": serve_cell._count(o.error for o in window if not o.ok),
                "in_flight_mid": mid, "in_flight_end": end,
                "ttft_p50_ms": 1e3 * flops.percentile(ttft, 50),
                "ttft_p95_ms": 1e3 * flops.percentile(ttft, 95),
                "itl_p95_ms": 1e3 * flops.percentile(gaps, 95) if gaps else None,
                "tokens_per_s": n["tokens"] / args.seconds,
                "late_p95_ms": 1e3 * flops.percentile([o.late_s for o in window], 95),
                "compiles": c1["compiles"] - c0["compiles"],
                "windows": serve_cell.windows_between(c0, c1),
            }), flush=True)
    print(json.dumps({"knee_per_s": knee, "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
