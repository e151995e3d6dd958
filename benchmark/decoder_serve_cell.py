"""A serving cell of the decoder family (``kind: "decoder_serve"``): the
stack `cli serve --model-file <configuration>` builds, warmed, its resident
sessions' contexts written into the paged latent cache by the engine's own
chunked prefill, then driven like every serving cell — `serve_cell.py`'s
pre-roll, window, drain, client threads, watch and follow-ups, through
`ServeServer.generate`, on `loadgen.py`'s schedule.

What differs from `serve_cell.py` (and why this is a module of its own):

- **The model is the file's, not the seed's, and so is the schedule.**
  Weights come from the configuration's ``assumed.weights_seed`` and the
  arrivals' times, lengths and sessions from the traffic's
  ``schedule_seed`` (`make_schedule`); ``--seed`` draws the token ids.
  Every run has the same router and the same schedule,
  so no seed changes how much work a run is (PERF.md section 6, the lesson
  of PR 28).
- **Resident sessions hold contexts**, not carries: ``resident_sessions``
  sessions, their lengths the quantiles of ``resident_context_len``, their
  tokens seeded per session, prefilled in chunks during set-up. The judge
  makes any of them again.
- **`correct` compares LOGITS**: `JUDGE_REQUESTS` completed requests (next
  turns of resident sessions, new sessions, follow-ups) are teacher-forced
  through the plain reference's full forward pass — resident context,
  prompt, every generated token — and each generated token's logit as the
  program computed it (`Request.token_logits`) is held to the reference's
  logit for that token; the reference's largest may lead the token's by the
  greedy limits only. Limits and their reasons: `LIMITS` below.
- Counters read: the decoder engine's (``decoder``: pairs routed, pairs
  here, experts touched, decode steps, contexts) and the cache's pages.

Traffic file keys (all required): ``rate_per_s``, ``prompt_len``,
``output_len``, ``continue_share``, ``resident_sessions`` (read by
`loadgen.make_schedule`), ``schedule_seed``, ``resident_context_len`` (a
lognormal with ``median``, ``sigma``, ``min``, ``max``), ``knee``,
``assumed``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import time

import numpy as np

import flops
import loadgen
import serve_cell
import trace_reduce
from serve_cell import (CLIENT_THREADS, DRAIN_S, FOLLOW_UPS, JUDGE_REQUESTS,
                        PREROLL_S, TRACE_SECONDS, Watch, resident_id)

#: The comparison that decides `correct`, over the generated tokens of the
#: judged requests. ``logit``: |the program's logit of the token it chose -
#: the reference's logit of that token|. ``greedy``: the reference's largest
#: logit less its logit of the chosen token (0 where the pick is the
#: reference's own). Logits have standard deviation ~1 (the head's fan-in
#: scaling), so the limits are absolute, and they are on QUANTILES over all
#: judged tokens: a bfloat16 program and a float32 reference part wherever
#: the router's last expert taken and the first left out are a near tie (the
#: program then computes another expert, a whole expert's term, at that
#: position and, through its cached latent, a little at every later one);
#: that sets a floor under the median and a tail no limit on the worst token
#: could hold (sound 0.85-1.9, every matrix in fp8 2.5). Each limit lies
#: between two readings on the chip (`decoder_limits.py`, my chip runs, PR
#: 29; PERF.md section 2): the sound program's largest over eleven seeds,
#: and the reference with a part computed in the precision below:
#:   logit_q50   sound 0.039-0.051 | all routed experts in fp8 0.070
#:   logit_q90   sound 0.126-0.167 | every layer's w_o in fp8 0.54
#:   greedy_q90  sound 0.028-0.081 | every layer's w_o in fp8 0.51
#: (every matrix in fp8: 0.46 / 1.08 / 1.11). What they cannot show: the
#: residual stream or the router in bfloat16 read 0.047 / 0.157 and 0.045 /
#: 0.167, as the sound program does (the program's matmuls already round
#: their inputs to bfloat16); those two fail only the float32 limits below.
LIMITS = {"logit_q50": 0.06, "logit_q90": 0.3, "greedy_q90": 0.2}
#: A rehearsal on the CPU runs the program in float32 (XLA:CPU has no bf16
#: dot): there the two agree to rounding of float32 sums (1e-5 measured), and
#: the limits are those of a float32 program, so that one bfloat16 rounding
#: anywhere (1e-2) fails them.
LIMITS_FLOAT32 = {"logit_q50": 1e-4, "logit_q90": 2e-4, "logit_max": 2e-3,
                  "greedy_q90": 2e-4, "greedy_max": 2e-3}
JUDGE_PAD = 2048   # judged sequences are padded to a multiple: few shapes compile


def flags_of(cell, model_file: str) -> list[str]:
    extra = (["--weights-dtype", "float32", "--interpret-kernels"]
             if cell.rehearsal else [])
    return ["--http", "--model-file", model_file, *cell.config["serve"]["flags"],
            *extra, "--seed", str(cell.seed)]


def build(cell):
    """``(sampling, params, server)``: the stack as `cli serve` builds it
    from the configuration, every program compiled, residents in place."""
    from lstm_tensorspark_tpu import cli

    model_file = os.path.join(cell.workdir, "model.json")
    with open(model_file, "w") as f:
        json.dump(cell.config, f)
    args = cli.build_serve_parser().parse_args(flags_of(cell, model_file))
    params, _, server = cli._build_serve_stack(args, 1)
    sampling = cli._serve_sampling(args)
    server.warmup(sampling, prompt_lens=tuple(server.engine.prefill_buckets))
    preload(cell, server.engine)
    return sampling, params, server


def resident_lengths(cell) -> np.ndarray:
    """Context length of resident session ``i``: the distribution's
    quantiles, dealt to the sessions by the traffic's ``schedule_seed`` (the
    same deal in every run: see `make_schedule`)."""
    n, d = int(cell.traffic["resident_sessions"]), cell.traffic["resident_context_len"]
    q = loadgen._lognormal_quantiles(n, d["median"], d["sigma"], d["min"], d["max"])
    return np.random.default_rng(
        [int(cell.traffic["schedule_seed"]), 0x5E55]).permutation(q)


def resident_tokens(cell, i: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([cell.seed, 0xC0DE, i])
    return rng.integers(2, cell.config["vocab_size"], size=n).astype(np.int32)


def preload(cell, engine) -> None:
    """The working set: every resident session's context prefilled into its
    pages through the engine's own chunk program, `max_prefill_batch` rows a
    dispatch, then left idle (unpinned) as a kept session is."""
    cache, chunk = engine.cache, engine.max_prompt_len
    rows = []
    for i, n in enumerate(resident_lengths(cell)):
        slot, _ = cache.acquire(resident_id(i))
        cache.commit(slot, int(n))
        rows.append([slot, resident_tokens(cell, i, int(n)), 0])
    rows.sort(key=lambda r: -r[1].size)
    while rows:
        batch = rows[:engine.max_prefill_batch]
        engine.prefill_chunk([(slot, slot, at == 0, toks[at:at + chunk])
                              for slot, toks, at in batch])
        for r in batch:
            r[2] += chunk
        rows = [r for r in rows if r[2] < r[1].size]
        rows.sort(key=lambda r: r[2] - r[1].size)    # most left first
    for i in range(int(cell.traffic["resident_sessions"])):
        cache.unpin(resident_id(i))
    import jax

    jax.block_until_ready(cache.pools)


def make_schedule(cell) -> list:
    """The run's arrivals. Everything that sizes the work comes from the
    traffic file's ``schedule_seed`` and is the same in every run: WHEN each
    request is due, how long its prompt and its answer are, which resident
    session a turn continues (and, in `resident_lengths`, how long that
    session's context is). ``--seed`` draws the token ids, of the prompts
    and of the resident contexts. Why not `loadgen.make_schedule` on
    ``--seed`` as the LSTM cells do: answers here are hundreds of tokens
    (3-10 s of a 51 s window), so which answers straddle the window's edges
    moves the tokens delivered INSIDE it by 2% (standard deviation over
    seeds; the interquartile spread of six seeds 2-5%, simulated from the
    schedule alone, and 991 / 997 / 1,037 tokens/s in three runs on the
    chip) - more than half of `serve_tokens_per_s`' bound, with nothing of
    the system in it; and which contexts the continued turns meet moves the
    median wait for the first token by a few percent. With the schedule
    fixed every seed is the same work."""
    fixed = loadgen.make_schedule(cell.traffic, int(cell.traffic["schedule_seed"]),
                                  cell.seconds, preroll_s=PREROLL_S)
    return [dataclasses.replace(a, word_seed=(cell.seed, a.idx)) for a in fixed]


def make_send(cell, server, sampling, *, give_up_at, logits=None):
    """``send(outcome)`` as `serve_cell.make_send`'s; ``logits`` (a dict) is
    given each reply's ``token_logits`` under its arrival's ``idx``: what the
    judge compares."""
    from lstm_tensorspark_tpu.serve.batcher import QueueFullError

    vocab = cell.config["vocab_size"]

    def send(o: loadgen.Outcome, session=None, prompt=None) -> None:
        a = o.arrival
        if a.resident is not None:
            session, o.continued = resident_id(a.resident), True
        if prompt is None:
            prompt = loadgen.words(a, vocab, a.prompt_len)
        o.prompt = tuple(int(t) for t in prompt)
        try:
            req = server.generate(
                prompt, max_new_tokens=a.new_tokens, sampling=sampling,
                session_id=session, keep_session=True,
                timeout=max(give_up_at() - time.perf_counter(), 0.05))
        except QueueFullError:
            o.error = "shed"
            return
        except TimeoutError:
            o.error = "timeout"
            return
        o.first_token_at = req.t_first_token
        o.token_at = tuple(req.t_tokens)
        o.done_at = req.t_done
        o.tokens = tuple(int(t) for t in req.tokens)
        o.session_id = req.session_id
        o.phases_ms = req.phase_summary_ms()
        if logits is not None:
            logits[a.idx] = np.asarray(req.token_logits)
        o.ok = len(o.tokens) == a.new_tokens
        if not o.ok:
            o.error = f"failed: {len(o.tokens)} of {a.new_tokens} tokens"

    return send


def counters(server) -> dict:
    c = serve_cell.counters(server)
    e = server.engine.stats()
    c["decoder"] = dict(e["decoder"])
    c["cache"] = {k: e["cache"][k] for k in (
        "latent_pages_in_use", "latent_pages_total", "latent_tokens",
        "live_sessions", "pages_allocated", "pages_freed")}
    return c


def context_of(cell, o) -> list[int]:
    """Every token the session had consumed before ``o``'s generated ones."""
    consumed = list(o.prompt)
    if o.arrival.resident is not None:
        i = o.arrival.resident
        return list(resident_tokens(cell, i, int(resident_lengths(cell)[i]))) + consumed
    if o.continued:                     # a follow-up of a session this run opened
        return list(o.after.prompt) + list(o.after.tokens[:-1]) + consumed
    return consumed


def judge_sample(cell, params, outcomes, followed, program_logits: dict,
                 n: int, *, reference_params=None) -> dict:
    """Teacher-force a seeded sample of completed requests through the plain
    reference and hold the program's logits to it (`LIMITS`; a float32
    program, which only a CPU rehearsal runs, to `LIMITS_FLOAT32`)."""
    from reference import deepseek_v2 as reference

    limits = LIMITS_FLOAT32 if params["embedding"].dtype.itemsize == 4 else LIMITS

    rng = np.random.default_rng([cell.seed, 0x10D6E])

    def some(pool, k):
        return [pool[i] for i in rng.permutation(len(pool))[:k]]

    picked = [o for o in followed if o.ok]
    rest = n - len(followed)
    picked += some([o for o in outcomes if o.ok and o.continued], rest // 2)
    picked += some([o for o in outcomes if o.ok and not o.continued],
                   n - len(picked))
    doc = cell.config
    held = list(range(doc.get("experts_first", 0),
                      doc.get("experts_first", 0) + doc["n_routed_experts"]))
    block = 16 if cell.rehearsal else 256
    errs, gaps, per_request = [], [], []
    for o in picked:
        ctx, toks = context_of(cell, o), np.asarray(o.tokens)
        seq = np.asarray(ctx + list(o.tokens[:-1]), np.int32)
        pad = -len(seq) % (block if cell.rehearsal else JUDGE_PAD)
        logits = np.asarray(reference.forward(
            reference_params or params, doc, np.pad(seq, (0, pad)), held,
            want=(len(ctx) - 1, len(seq)), block=block,
            head_group=2 if cell.rehearsal else 16))
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


def ttft_medians(completed, prefill_buckets) -> dict:
    """Where the median wait for the first token sits: the median of the
    completed requests due in the window, by kind of turn and by the
    prefill bucket the prompt lands in (one dispatch, or chunks)."""
    def p50(some):
        waits = [o.first_token_at - o.due_at for o in some]
        return [len(waits), 1e3 * flops.percentile(waits, 50) if waits else None]

    edges = (0, *prefill_buckets, math.inf)
    by = {"continued": p50([o for o in completed if o.continued]),
          "new": p50([o for o in completed if not o.continued])}
    for lo, hi in zip(edges, edges[1:]):
        by[f"prompt<={hi}"] = p50([o for o in completed
                                   if lo < len(o.prompt) <= hi])
    return by


def residents_hold_their_pages(cell, cache) -> bool:
    lengths = resident_lengths(cell)
    for i, n in enumerate(lengths):
        slot = cache.lookup(resident_id(i))
        if slot is None or cache.length[slot] < n \
                or len(cache.pages_of(slot)) < cache.pages_for(cache.length[slot]):
            return False
    return True


def run(cell, controls=None) -> dict:
    """One run of the cell. ``controls`` (`decoder_limits.py`: the readings
    the limits lie between) maps a name to a context manager of the
    program's parameters that yields the parameters a FURTHER judgement
    gives the reference (say, the routed experts rounded to fp8), and may
    patch the reference meanwhile; each lands in
    ``samples["reference_<name>"]`` and decides nothing."""
    import jax

    traffic = cell.traffic
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
        held = residents_hold_their_pages(cell, cache)
        slots = cache.stats()

    # the pool has done its work: its memory is the judge's now
    for pool in cache.pools:
        pool.delete()
    cache.pools = ()
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
            "pool_fill_end": slots["latent_pages_in_use"] / slots["latent_pages_total"],
            "decoder": after["decoder"],
            "preroll_requests": sum(o.due_at < opens_at for o in outcomes),
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


def _with_model_vocab(cell):
    """`serve_cell.follow_ups` reads the vocabulary at
    ``config["model"]["vocab_size"]``; a decoder's file has it at the top."""
    import copy

    view = copy.copy(cell)
    view.config = dict(cell.config, model={"vocab_size": cell.config["vocab_size"]})
    return view
