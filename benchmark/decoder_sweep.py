#!/usr/bin/env python3
"""Find a ``decoder_serve`` cell's knee, once, when the cell is defined: what
`sweep.py` does for the LSTM cells, on `decoder_serve_cell.py`'s stack.

    python benchmark/decoder_sweep.py --workload dsv2-serve-resident \
        --rates 1.5,2,2.5,3,3.5,4 --seconds 12

One boot (weights, every program, the resident contexts); then, rate after
rate, the cell's own traffic at that rate through the same generator as a
run, each followed by a full drain. Between rates the sessions the rate
opened are released (a run keeps them; a sweep of many rates would fill the
pool with them), the resident ones stay and grow by their turns. A rate is
sustained by `sweep.py`'s rule: nothing shed or failed, and the requests in
flight at the window's end no more than at its middle, or no more than
``--max-active``. Its result is written into the traffic file's ``knee`` and
PERF.md by hand.
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
    ap.add_argument("--preroll", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    import decoder_serve_cell as cellmod
    import flops
    import loadgen
    import run
    import serve_cell
    from lstm_tensorspark_tpu.utils.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("JAX found no TPU: a knee is a number of the chip")
    place_compile_cache()
    _, _, config, traffic = run.load_cell(args.workload)
    t0 = time.perf_counter()
    cell = run.Cell(name=args.workload, config=config, traffic=traffic, chips=1,
                    seed=args.seed, seconds=args.seconds, trace=False,
                    t0=t0, workdir=tempfile.mkdtemp(prefix="sweep-"),
                    rehearsal=False)
    sampling, _, server = cellmod.build(cell)
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "cache": server.engine.cache.stats()}), flush=True)
    knee = None
    with server:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.traffic = {**traffic, "rate_per_s": rate}
            arrivals = loadgen.make_schedule(cell.traffic, args.seed + i,
                                             args.seconds, preroll_s=args.preroll)
            opens = time.perf_counter() + args.preroll + 0.25
            closes = opens + args.seconds
            loop = loadgen.OpenLoop(
                arrivals, cellmod.make_send(
                    cell, server, sampling, give_up_at=lambda: closes + 60.0),
                workers=serve_cell.CLIENT_THREADS)
            c0 = cellmod.counters(server)
            outcomes = loop.run(opens, drain_s=60.0)
            c1 = cellmod.counters(server)
            n = serve_cell.window_numbers(outcomes, opens, closes)
            window, ok, ttft, gaps = n["window"], n["ok"], n["ttft"], n["gaps"]
            mid = loadgen.in_flight(outcomes, opens + args.seconds / 2)
            end = loadgen.in_flight(outcomes, closes)
            sustained = len(ok) == len(window) and (
                end <= mid or end <= c1["max_active"])
            if sustained:
                knee = rate
            d = {k: c1["decoder"][k] - c0["decoder"][k] for k in c1["decoder"]}
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
                "decode_steps": d["decode_steps"],
                "rows_per_step": d["decode_row_steps"] / max(d["decode_steps"], 1),
                "pool_fill": c1["cache"]["latent_pages_in_use"]
                / c1["cache"]["latent_pages_total"],
            }), flush=True)
            cache = server.engine.cache
            for sid in cache.session_ids():
                if not sid.startswith("resident-"):
                    cache.release(sid)
    print(json.dumps({"knee_per_s": knee, "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
