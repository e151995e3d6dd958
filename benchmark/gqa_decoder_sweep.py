#!/usr/bin/env python3
"""Find a ``gqa_decoder_serve`` cell's knee, once, when the cell is defined:
`decoder_sweep.py`'s procedure on `gqa_decoder_serve_cell.py`'s stack (that
file imports the other kind's module and reads its cache's counters by name,
so it cannot drive this kind).

    python benchmark/gqa_decoder_sweep.py --workload mellum2-serve-longctx \
        --rates 2,2.5,3,4,5,6 --seconds 20 --preroll 8

One boot (weights, every program, the resident contexts); then, rate after
rate, the cell's own traffic at that rate through the same generator as a
run, each followed by a full drain. Between rates the sessions the rate
opened are released (a run keeps them; a sweep of many rates would fill the
pools with them), the resident ones stay and grow by their turns. A rate is
sustained by `sweep.py`'s rule: nothing shed or failed, and the requests in
flight at the window's end no more than at its middle, or no more than
``--max-active``. That rule alone passes a rate AT capacity whose queue
happens to stay inside ``--max-active`` for 20 s, so the knee is the highest
sustained rate that is also UNDER capacity: its schedule offers at most
``UNDER_CAPACITY`` of the most output tokens/s that any swept rate delivered
(PR 25's rule as PR 29 read its sweep, here in code). Sweep at least two rates
clearly above saturation, so that "the most delivered" is a plateau and not
one point. The last line printed, also written to
``chiprun_out/gqa_decoder_sweep.json``, goes into the traffic file's ``knee``
as it is.
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

#: a rate is under capacity when the output tokens/s its schedule asks for
#: are at most this share of the most any swept rate delivered
UNDER_CAPACITY = 0.9


def knee_of(rows: list[dict]) -> tuple[float | None, float]:
    """(the knee, the capacity in tokens/s) of a sweep's rows."""
    capacity = max(r["tokens_per_s"] for r in rows)
    under = [r["rate_per_s"] for r in rows if r["sustained"]
             and r["offered_tokens_per_s"] <= UNDER_CAPACITY * capacity]
    return (max(under) if under else None), capacity


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--preroll", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    import flops
    import gqa_decoder_serve_cell as cellmod
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
    rows = []
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
            d = {k: c1["decoder"][k] - c0["decoder"][k] for k in c1["decoder"]}
            cache = c1["cache"]
            rows.append({
                "rate_per_s": rate, "sustained": sustained,
                "offered_tokens_per_s": sum(
                    a.new_tokens for a in arrivals
                    if 0 <= a.due < args.seconds) / args.seconds,
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
                "full_pool_fill": cache["full_pages_in_use"] / cache["full_pages_total"],
                "window_pool_fill": cache["window_pages_in_use"]
                / cache["window_pages_total"],
                "window_pages_recycled": cache["window_pages_recycled"]
                - c0["cache"]["window_pages_recycled"],
            })
            print(json.dumps(rows[-1]), flush=True)
            pages = server.engine.cache
            for sid in pages.session_ids():
                if not sid.startswith("resident-"):
                    pages.release(sid)
    knee, capacity = knee_of(rows)
    result = {
        "swept_rates_per_s": [r["rate_per_s"] for r in rows],
        "window_s": args.seconds, "preroll_s": args.preroll,
        "knee_per_s": knee, "capacity_tokens_per_s": capacity,
        "under_capacity_share": UNDER_CAPACITY,
        "offered_tokens_per_s": [r["offered_tokens_per_s"] for r in rows],
        "tokens_per_s": [r["tokens_per_s"] for r in rows],
        "sustained": [r["sustained"] for r in rows],
        "failed": [r["failed"] for r in rows],
        "in_flight_mid_end": [[r["in_flight_mid"], r["in_flight_end"]]
                              for r in rows],
        "ttft_p50_ms": [r["ttft_p50_ms"] for r in rows],
        "itl_p95_ms": [r["itl_p95_ms"] for r in rows],
        "rows_per_decode_step": [r["rows_per_step"] for r in rows],
        "full_pool_fill_at_end": [r["full_pool_fill"] for r in rows],
        "window_pool_fill_at_end": [r["window_pool_fill"] for r in rows],
        "device": jax.devices()[0].device_kind,
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gqa_decoder_sweep.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
