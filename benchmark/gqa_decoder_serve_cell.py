"""A serving cell of the K/V decoder (``kind: "gqa_decoder_serve"``; Mellum2:
grouped-query attention over a paged K/V cache whose window layers keep only
a session's last pages): the stack `cli serve --model-file <configuration>`
builds, run exactly as `decoder_serve_cell.py` runs its cell. Everything
there that does not name the model is IMPORTED from it (the flags, the build
and its preload of resident contexts through the engine's own chunked
prefill, the fixed schedule, the sender, the watch); this module writes only
what names the model:

- `counters`: the engine's ``decoder`` group (with the (query, key) pairs by
  kind of page) and the cache's pages by kind;
- `judge_sample`: the same logit comparison, on `reference/mellum2.py`;
- `LIMITS`, measured with `gqa_decoder_limits.py`;
- `residents_hold_their_window_pages`: a resident session must hold, beside
  its full pages, exactly the window pages its length implies;
- `run`, which differs from `decoder_serve_cell.run` only in calling these.

(A `benchmark` issue should let a configuration name its reference and its
counters, so that the two modules become one: PERF.md section 7.)

Traffic file keys: as `decoder_serve_cell.py`'s.
"""

from __future__ import annotations

import math
import os
import threading
import time

import numpy as np

import flops
import loadgen
import serve_cell
import trace_reduce
from decoder_serve_cell import (LIMITS_FLOAT32, _with_model_vocab, build,
                                context_of, make_schedule, make_send,
                                resident_lengths, residents_hold_their_pages,
                                ttft_medians)
from serve_cell import (CLIENT_THREADS, DRAIN_S, FOLLOW_UPS, JUDGE_REQUESTS,
                        PREROLL_S, TRACE_SECONDS, Watch, resident_id)

#: The comparison that decides `correct`, as `decoder_serve_cell.LIMITS`
#: defines it: quantiles, over the generated tokens of the judged requests,
#: of ``logit`` (|the program's logit of the token it chose - the
#: reference's logit of that token|) and of ``greedy`` (the reference's
#: largest logit less its logit of the chosen token). The floor under the
#: median is the same as there: a bfloat16 program and a float32 reference
#: part wherever the router's 8th and 9th experts are a near tie. Each limit
#: lies between two readings of THIS model on the chip
#: (`gqa_decoder_limits.py` and the cell's own runs; my chip runs, PR 34;
#: PERF.md section 2): the sound bfloat16 program's over the seeds run, and
#: the reference with a part computed in the precision below:
#:   logit_q50   sound 0.0396-0.0446 | every layer's w_o in fp8 0.088
#:   logit_q90   sound 0.114-0.133   | every layer's w_o in fp8 0.234
#:   greedy_q90  sound 0.022-0.052   | every layer's w_o in fp8 0.170
#: (sound: every sound run of the cell at the published widths, 26 of them;
#: all experts in fp8: 0.114 / 0.286 / 0.242; every matrix: 0.192 / 0.481 /
#: 0.457). The faults the issue names read far above these limits at the
#: published widths: the window ignored in the sliding layers 0.214 / 0.628 /
#: 0.589, plain rope in the full layers 0.402 / 0.933 / 0.943.
LIMITS = {"logit_q50": 0.06, "logit_q90": 0.18, "greedy_q90": 0.1}
#: judged sequences are padded to one of these lengths: few shapes compile
JUDGE_LENGTHS = (2048, 4096, 8192, 16384, 32768, 49152)


def counters(server) -> dict:
    c = serve_cell.counters(server)
    e = server.engine.stats()
    c["decoder"] = dict(e["decoder"])
    c["cache"] = {k: e["cache"][k] for k in (
        "full_pages_in_use", "full_pages_total", "window_pages_in_use",
        "window_pages_total", "window_pages_recycled", "full_tokens",
        "live_sessions", "pages_allocated", "pages_freed")}
    return c


def residents_hold_their_window_pages(cell, cache) -> bool:
    """Every resident session holds exactly the window pages its length
    implies: from the page of the first key its next query sees to the page
    of its last token (one more where its last request was promised a token
    it did not emit)."""
    window = cache.kinds[1].window
    for i in range(int(cell.traffic["resident_sessions"])):
        slot = cache.lookup(resident_id(i))
        if slot is None:
            return False
        length = int(cache.length[slot])
        base, pages = cache.held(slot, 1)
        ends = base + len(pages) - cache.pages_for(length)
        if base != max(length - (window - 1), 0) // cache.page \
                or ends not in (0, 1):
            return False
    return True


def judge_sample(cell, params, outcomes, followed, program_logits: dict,
                 n: int, *, reference_params=None) -> dict:
    """Teacher-force a seeded sample of completed requests (next turns of
    resident sessions, new sessions, the follow-ups) through the plain
    reference over their WHOLE conversation and hold the program's logits to
    it (`LIMITS`; a float32 program, which only a CPU rehearsal runs, to
    `decoder_serve_cell.LIMITS_FLOAT32`)."""
    from reference import mellum2 as reference

    limits = LIMITS_FLOAT32 if params["embedding"].dtype.itemsize == 4 else LIMITS
    rng = np.random.default_rng([cell.seed, 0x10D6E])

    def some(pool, k):
        return [pool[i] for i in rng.permutation(len(pool))[:k]]

    picked = [o for o in followed if o.ok]
    rest = n - len(followed)
    picked += some([o for o in outcomes if o.ok and o.continued], rest // 2)
    picked += some([o for o in outcomes if o.ok and not o.continued],
                   n - len(picked))
    block = 8 if cell.rehearsal else 128
    errs, gaps, per_request = [], [], []
    for o in picked:
        ctx, toks = context_of(cell, o), np.asarray(o.tokens)
        seq = np.asarray(ctx + list(o.tokens[:-1]), np.int32)
        padded = (len(seq) + -len(seq) % block if cell.rehearsal else
                  next(x for x in JUDGE_LENGTHS if x >= len(seq)))
        logits = np.asarray(reference.forward(
            reference_params or params, cell.config,
            np.pad(seq, (0, padded - len(seq))),
            want=(len(ctx) - 1, len(seq)), block=block))
        ref_chosen = logits[np.arange(len(toks)), toks]
        got = program_logits[o.arrival.idx][:, 0]
        e, g = np.abs(got - ref_chosen), logits.max(-1) - ref_chosen
        errs.append(e)
        gaps.append(g)
        per_request.append({"idx": o.arrival.idx, "context": len(ctx),
                            "tokens": len(toks), "logit_max": float(e.max()),
                            "greedy_max": float(g.max())})
    if not errs:
        return {"requests": 0, "ok": False}
    errs, gaps = np.concatenate(errs), np.concatenate(gaps)
    read = {"logit_q50": float(np.quantile(errs, 0.5)),
            "logit_q90": float(np.quantile(errs, 0.9)),
            "logit_max": float(errs.max()),
            "greedy_q90": float(np.quantile(gaps, 0.9)),
            "greedy_max": float(gaps.max())}
    return {"requests": len(picked), "tokens": int(errs.size),
            "resident": sum(o.arrival.resident is not None for o in picked),
            "follow_ups": len(followed), **read, "limits": dict(limits),
            "exact_picks": int((gaps == 0).sum()), "per_request": per_request,
            "ok": len(picked) == n and all(read[k] <= limits[k] for k in limits)}


def run(cell, controls=None) -> dict:
    """One run of the cell, as `decoder_serve_cell.run`. ``controls``
    (`gqa_decoder_limits.py`) maps a name to a context manager of the
    program's parameters that yields the parameters a FURTHER judgement gives
    the reference; each lands in ``samples["reference_<name>"]`` and decides
    nothing."""
    import jax

    t_run = time.perf_counter()
    sampling, params, server = build(cell)
    arrivals = make_schedule(cell)
    edges: dict = {}
    trace_dir = os.path.join(cell.workdir, "profile") if cell.trace else None

    with server:
        opens_at = time.perf_counter() + PREROLL_S + 0.25
        closes_at = opens_at + cell.seconds
        token_logits: dict = {}
        send = make_send(cell, server, sampling, logits=token_logits,
                         give_up_at=lambda: closes_at + DRAIN_S)
        loop = loadgen.OpenLoop(arrivals, send, workers=CLIENT_THREADS)
        watch = Watch(server, lambda: loadgen.in_flight(
            [o for o in loop.outcomes if o is not None], time.perf_counter()) > 0)

        def edge(name, at, then=None):
            def fire():
                edges[name] = (time.perf_counter(), counters(server))
                if then:
                    then()
            t = threading.Timer(max(at - time.perf_counter(), 0), fire)
            t.daemon = True
            t.start()
            return t

        timers = [edge("open", opens_at), edge("close", closes_at)]
        if trace_dir:
            t_len = min(TRACE_SECONDS, cell.seconds / 2)
            t_at = opens_at + (cell.seconds - t_len) / 2

            def begin():
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                with jax.profiler.TraceAnnotation("bench:window_open"):
                    edges["trace_open"] = time.perf_counter()
                    edges["trace_c0"] = counters(server)

            def end():
                with jax.profiler.TraceAnnotation("bench:window_close"):
                    edges["trace_close"] = time.perf_counter()
                    edges["trace_c1"] = counters(server)
                jax.profiler.stop_trace()

            timers += [edge("t0", t_at, begin), edge("t1", t_at + t_len, end)]
        watch.start()
        outcomes = loop.run(opens_at, drain_s=DRAIN_S)
        watch.stop()
        for t in timers:
            t.join()
        followed = serve_cell.follow_ups(
            _with_model_vocab(cell), outcomes, make_send(
                cell, server, sampling, logits=token_logits,
                give_up_at=lambda: time.perf_counter() + DRAIN_S), FOLLOW_UPS)
        after = counters(server)
        cache = server.engine.cache
        held = (residents_hold_their_pages(cell, cache)
                and residents_hold_their_window_pages(cell, cache))
        slots = cache.stats()

    # the pools have done their work: their memory is the judge's now
    for pool in cache.pools:
        pool.delete()
    cache.pools = ()
    t_judge = time.perf_counter()
    opened, c0 = edges["open"]
    closed, c1 = edges["close"]
    n = serve_cell.window_numbers(outcomes, opens_at, closes_at)
    window, ok, ttft, gaps, tokens_in_window = (
        n["window"], n["ok"], n["ttft"], n["gaps"], n["tokens"])
    judged = judge_sample(cell, params, outcomes, followed, token_logits,
                          JUDGE_REQUESTS)
    others = {}
    for name, control in (controls or {}).items():
        with control(params) as reference_params:
            others[f"reference_{name}"] = judge_sample(
                cell, params, outcomes, followed, token_logits, JUDGE_REQUESTS,
                reference_params=reference_params)
    compiles = c1["compiles"] - c0["compiles"]
    correct = {"reference": judged["ok"], "no_compile_in_window": compiles == 0,
               "some_completed": bool(ok),
               "residents_hold_their_pages": held and slots["evictions"] == 0,
               "nothing_refused": after["rejected"] == 0 and after["failed"] == 0}
    stamps = sorted(t for o in outcomes for t in o.token_at
                    if opens_at <= t < closes_at)
    pause_s, pause_cpu_s = watch.worst_late(opens_at, closes_at)
    mid = opens_at + cell.seconds / 2
    result = {
        "correct": all(correct.values()), "checks": correct,
        "attempted": len(window), "failed": len(window) - len(ok),
        "setup_s": opens_at - cell.t0,
        "end_to_end": {
            "ttft_p95_ms": 1e3 * flops.percentile(ttft, 95) if ttft else math.nan,
            "ttft_p50_ms": 1e3 * flops.percentile(ttft, 50) if ttft else math.nan,
            "itl_p95_ms": 1e3 * flops.percentile(gaps, 95) if gaps else math.nan,
            "serve_tokens_per_s": tokens_in_window / cell.seconds,
        },
        "samples": {
            "ttft_p95_ms": f"{len(ttft)} requests due in the window "
                           f"({len(ttft) - len(ok)} failed/shed/unfinished); "
                           f"p50 {1e3 * flops.percentile(ttft, 50):.3f} ms, "
                           f"p95 {1e3 * flops.percentile(ttft, 95):.3f} ms, "
                           f"max {1e3 * max(ttft):.3f} ms" if ttft else "none",
            "itl_p95_ms": f"{len(gaps)} gaps of {len(ok)} completed requests",
            "serve_tokens_per_s": f"{tokens_in_window} tokens delivered in "
                                  f"{cell.seconds} s",
            "errors": serve_cell._count(o.error for o in window if not o.ok),
            "continued": sum(o.continued for o in window),
            "ttft_p50_ms_of": ttft_medians(ok, server.engine.prefill_buckets),
            "in_flight_mid": loadgen.in_flight(outcomes, mid),
            "in_flight_end": loadgen.in_flight(outcomes, closes_at),
            "edge_lateness_s": [opened - opens_at, closed - closes_at],
            "reference": judged, "slots": slots, "stall": watch.stall,
            "pool_fill_end": {
                k: slots[f"{k}_pages_in_use"] / slots[f"{k}_pages_total"]
                for k in ("full", "window")},
            "decoder": after["decoder"],
            "preroll_requests": sum(o.due_at < opens_at for o in outcomes),
            "wall_s": {"build": opens_at - PREROLL_S - 0.25 - t_run,
                       "traffic": t_judge - opens_at + PREROLL_S + 0.25,
                       "judge": time.perf_counter() - t_judge},
        },
        "window_s": cell.seconds, "outcomes": window, "ttft_s": ttft,
        "counters": (c0, c1),
        "host_pause_max_s": pause_s,
        "delivery_gap_max_s": max((b - a for a, b in zip(
            [opens_at, *stamps], [*stamps, closes_at])), default=math.nan),
        "param_bytes": params["embedding"].dtype.itemsize,
        "trace": None, "trace_window": None, "sync_mark": None,
    }
    result["samples"].update(others)
    result["samples"].update(
        host_pause_max_s=pause_s, host_pause_cpu_s=pause_cpu_s,
        delivery_gap_max_s=result["delivery_gap_max_s"])
    if trace_dir:
        path = trace_reduce.find_xplane(trace_dir)
        if path:
            result["trace"] = trace_reduce.load(path)
            result["trace_counters"] = (edges["trace_c0"], edges["trace_c1"])
    return result
